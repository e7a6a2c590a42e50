"""The Solar-Open2-shaped model at a tiny size on the CPU against the plain
reference (``benchmarks/reference/solar_open2.py``, whose delta rule is a
scan over tokens): the full pass, the state a prefill hands on, and the
shares of the experts adding up to the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import solar_open2_tiny as tiny  # noqa: E402

reference = tiny.load_reference()


@pytest.fixture(scope="module")
def built():
    return tiny.build()


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_full_pass_matches_the_reference(built, attention):
    module, params = built
    module = module.clone(attention=attention)
    tokens = jax.random.randint(
        jax.random.PRNGKey(5), (2, tiny.POSITIONS), 0, tiny.VOCAB
    )
    got = module.apply({"params": params}, tokens)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack(
            [reference.forward(params, tiny.MODEL, t) for t in tokens]
        )
    # float32 on both sides; the program's delta rule is the chunked form
    # (chunk 8 over 96 tokens) and the reference's a scan over tokens
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=2e-4)
    assert float(jnp.std(want)) > 0.2


@pytest.mark.parametrize(
    "fault",
    [
        {"lowp": True}, {"state_lost_at": 20}, {"beta_halved": True},
        {"decay_a_head": True}, {"shared_dropped": True},
    ],
    ids=lambda f: next(iter(f)),
)
def test_the_reference_control_and_planted_faults_move_the_pass(built, fault):
    """Each of the five changes the logits by far more than the model
    differs from the reference, and a lost state nothing before it."""
    _, params = built
    tokens = jax.random.randint(jax.random.PRNGKey(6), (48,), 0, tiny.VOCAB)
    with jax.default_matmul_precision("highest"):
        sound = reference.forward(params, tiny.MODEL, tokens)
        faulty = reference.forward(params, tiny.MODEL, tokens, **fault)
    assert float(jnp.max(jnp.abs(sound - faulty))) > 100 * 5e-5
    if "state_lost_at" in fault:
        np.testing.assert_array_equal(sound[:20], faulty[:20])


@pytest.mark.parametrize("length", [5, 8, 13, 24])
def test_prefill_state_is_the_state_at_the_sequences_own_length(built, length):
    """Right padding does not advance the rule: a prompt padded to 24
    hands on the state, the convolution's rows and the logits of the same
    prompt at its own length (lengths on and off the chunk of 8); an
    attention layer hands on K/V rows and nothing else, a KDA layer its
    block and no rows."""
    module, params = built
    prompt = jax.random.randint(jax.random.PRNGKey(length), (1, length), 0, tiny.VOCAB)
    padded = jnp.pad(prompt, ((0, 0), (0, 24 - length)), constant_values=7)
    lengths = jnp.asarray([length], jnp.int32)
    apply = lambda t: module.apply({"params": params}, t, lengths, method="prefill")
    want_logits, want = apply(prompt)
    got_logits, got = apply(padded)
    np.testing.assert_allclose(got_logits, want_logits, atol=1e-5, rtol=1e-5)
    for kind, layer_got, layer_want in zip(tiny.FIELDS["layer_types"], got, want):
        if kind == "kda":
            state, conv = layer_got
            assert state.shape == (1, 3, 16, 16) and state.dtype == jnp.float32
            assert conv.shape == (1, 3, 3 * 48)
            np.testing.assert_allclose(state, layer_want[0], atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(conv, layer_want[1], atol=1e-5)
        else:
            k, v = layer_got
            np.testing.assert_allclose(k[:, :length], layer_want[0], atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(built):
    """The guide's test of the cut: at one layer's input, the routed parts
    that all 4 shares give (each the program's block with ``held_experts``
    its own, its expert leaves the column blocks of the uncut layer's),
    with the shared expert counted once, are what the uncut layer of 16
    experts gives; and the reference, given a share, gives that share."""
    from zookeeper_tpu.models.transformer import _Block

    d, f, experts, count = 64, 32, tiny.EXPERTS, tiny.HELD
    fields = dict(
        d_model=d, num_heads=4, mlp_ratio=4, attention="dense",
        dtype=jnp.float32, num_kv_heads=2, head_dim=16, mlp="moe",
        num_experts=experts, experts_per_token=4, expert_dim=f,
        norm_eps=1e-5, attention_gate=True,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, d))
    uncut = _Block(**fields, shared_expert_dim=f)
    whole = uncut.init(jax.random.PRNGKey(2), x, False)["params"]
    mlp = lambda block, params: block.apply({"params": params}, x, method="_mlp") - x

    def share(first, shared):
        params = dict(whole)
        params["experts_gate"] = whole["experts_gate"][:, first * f:(first + count) * f]
        params["experts_up"] = whole["experts_up"][:, first * f:(first + count) * f]
        params["experts_down"] = whole["experts_down"][:, first * d:(first + count) * d]
        if not shared:
            for name in ("shared_gate", "shared_up", "shared_down"):
                del params[name]
        return params

    without = _Block(**fields)
    shared_once = mlp(uncut, whole) - mlp(without, share(0, False) | {
        name: whole[name] for name in ("experts_gate", "experts_up", "experts_down")
    })
    parts = sum(
        mlp(_Block(**fields, held_experts=(first, count)), share(first, False))
        for first in range(0, experts, count)
    )
    np.testing.assert_allclose(parts + shared_once, mlp(uncut, whole), atol=2e-5)
    # a share alone is not the layer: the absent experts' part is real
    first_share = mlp(_Block(**fields, held_experts=(0, count)), share(0, False))
    assert float(jnp.max(jnp.abs(first_share - (mlp(uncut, whole) - shared_once)))) > 1e-2
    # and the reference's view of a share is the program's
    block = _Block(**fields, shared_expert_dim=f, held_experts=(count, count))
    got = mlp(block, share(count, True))
    u2 = x  # the reference's `experts` takes the normed stream
    normed = reference._rms(x, whole["RMSNorm_1"]["scale"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([
            reference.experts(
                normed[i], share(count, True), top_k=4, held=(count, count),
                lowp=False,
            )
            for i in range(2)
        ])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_defaults_build_what_they_built():
    """A model that leaves the new fields alone has the parameters it had:
    no gate, no shared expert, no mixer, a position table."""
    from zookeeper_tpu import configure
    from zookeeper_tpu.models.transformer import TransformerLM

    model = TransformerLM()
    configure(model, {"num_layers": 2, "d_model": 32, "num_heads": 2, "attention": "dense"})
    module = model.build((16,), 64)
    params, _ = model.initialize(module, (16,), seed=0)
    assert sorted(params) == ["RMSNorm_0", "block0", "block1", "embed", "pos"]
    assert sorted(params["block0"]) == ["RMSNorm_0", "RMSNorm_1", "down", "proj", "qkv", "up"]
    assert module.attention_layers == (True, True)
    assert module.slot_state_spec() == ({}, {})


@pytest.mark.parametrize(
    "fields,message",
    [
        ({"layer_types": ["kda"], "kda_heads": 0}, "kda layers need"),
        ({"held_experts": [0, 4]}, "held_experts"),
        ({"mlp": "moe", "num_experts": 8, "experts_per_token": 2, "expert_dim": 8,
          "held_experts": [6, 4]}, "held_experts"),
        ({"shared_expert_dim": 8}, "shared_expert_dim needs"),
        ({"positions": "absolute"}, "positions="),
    ],
    ids=["kda-sizes", "held-without-moe", "held-past-the-router", "shared-without-moe", "positions"],
)
def test_build_refuses_what_it_cannot_build(fields, message):
    from zookeeper_tpu import configure
    from zookeeper_tpu.models.transformer import TransformerLM

    model = TransformerLM()
    configure(model, {"num_layers": 1, "d_model": 32, "num_heads": 2, **fields})
    with pytest.raises(ValueError, match=message):
        model.build((16,), 64)
