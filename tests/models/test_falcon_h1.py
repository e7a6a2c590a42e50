"""The Falcon-H1-shaped model at a tiny size on the CPU against the plain
reference (``benchmarks/reference/falcon_h1.py``, whose recurrence is a
scan over tokens): the full pass, and the state a prefill hands on."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import falcon_h1_tiny as tiny  # noqa: E402

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
    # float32 on both sides; the program's recurrence is the chunked form
    # (chunk 8 over 96 tokens) and the reference's a scan over tokens
    # (the head's multiplier of 1/4 leaves logits that spread over +-1)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=2e-4)
    assert float(jnp.std(want)) > 0.2
    # ...and float8 in the place of float32 is far outside that
    with jax.default_matmul_precision("highest"):
        low = reference.forward(params, tiny.MODEL, tokens[0], lowp=True)
    assert float(jnp.max(jnp.abs(low - want[0]))) > 50 * 5e-5


@pytest.mark.parametrize("fault", ["state_lost_at", "conv_lost_at"])
def test_the_reference_planted_faults_move_the_pass(built, fault):
    """Each fault changes the hidden states from its position on and
    nothing before it."""
    _, params = built
    tokens = jax.random.randint(jax.random.PRNGKey(6), (48,), 0, tiny.VOCAB)
    with jax.default_matmul_precision("highest"):
        sound = reference.hidden_states(params, tiny.MODEL, tokens)
        faulty = reference.hidden_states(params, tiny.MODEL, tokens, **{fault: 20})
    np.testing.assert_array_equal(sound[:20], faulty[:20])
    assert float(jnp.max(jnp.abs(sound[20:24] - faulty[20:24]))) > 1e-2


@pytest.mark.parametrize("length", [5, 8, 13, 24])
def test_prefill_state_is_the_state_at_the_sequences_own_length(built, length):
    """Right padding does not advance the recurrence: a prompt padded to
    24 hands on the state, the convolution's rows and the logits of the
    same prompt at its own length (lengths on and off the chunk of 8)."""
    module, params = built
    prompt = jax.random.randint(jax.random.PRNGKey(length), (1, length), 0, tiny.VOCAB)
    padded = jnp.pad(prompt, ((0, 0), (0, 24 - length)), constant_values=7)
    lengths = jnp.asarray([length], jnp.int32)
    apply = lambda t: module.apply({"params": params}, t, lengths, method="prefill")
    want_logits, want = apply(prompt)
    got_logits, got = apply(padded)
    np.testing.assert_allclose(got_logits, want_logits, atol=1e-5, rtol=1e-5)
    for layer_got, layer_want in zip(got, want):
        k, v, ssm, conv = layer_got
        assert ssm.shape == (1, 4, 8, 16) and ssm.dtype == jnp.float32
        assert conv.shape == (1, 3, 4 * 8 + 2 * 2 * 16)
        np.testing.assert_allclose(ssm, layer_want[2], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(conv, layer_want[3], atol=1e-6)
        np.testing.assert_allclose(k[:, :length], layer_want[0], atol=1e-6)


def test_defaults_trace_no_multiplier_and_build_no_mixer():
    """A model that leaves the new fields alone has the parameters it
    had, and its traced forward holds no multiply by a new constant."""
    from zookeeper_tpu import configure
    from zookeeper_tpu.models.transformer import TransformerLM

    model = TransformerLM()
    configure(model, {"num_layers": 1, "d_model": 32, "num_heads": 2, "attention": "dense"})
    module = model.build((16,), 64)
    params, _ = model.initialize(module, (16,), seed=0)
    assert sorted(params["block0"]) == ["RMSNorm_0", "RMSNorm_1", "down", "proj", "qkv", "up"]
    jaxpr = jax.make_jaxpr(
        lambda p, t: module.apply({"params": p}, t)
    )(params, jnp.zeros((1, 16), jnp.int32))
    assert "softplus" not in str(jaxpr) and "logistic" not in str(jaxpr)


@pytest.mark.parametrize(
    "fields,message",
    [
        ({"mlp": "swiglu", "mlp_dim": 0}, "mlp_dim"),
        ({"ssm_groups": 3}, "ssm_groups"),
        ({"ssm_multipliers": [1.0, 2.0]}, "ssm_multipliers"),
        ({"tie_embeddings": True}, "lm_head_multiplier"),
        ({"layer_types": ["window"], "window": 8}, "window layers"),
    ],
)
def test_build_refuses_what_it_cannot_run(fields, message):
    with pytest.raises(ValueError, match=message):
        tiny.build(**fields)
