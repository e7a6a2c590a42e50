"""The LongCat-Flash-shaped model at a tiny size on the CPU against the plain
reference (``benchmarks/reference/longcat_flash.py``): the full pass, a
prefill then decode through the latent pages, the router's bias and the
identity experts against numbers worked out here, and the held shares of
the experts adding up to the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import longcat_flash_tiny as tiny  # noqa: E402

from zookeeper_tpu.models.transformer import _pool_write_pages  # noqa: E402
from zookeeper_tpu.ops import mla  # noqa: E402
from zookeeper_tpu.ops.moe import route_top_k, sparse_moe  # noqa: E402
from zookeeper_tpu.serving.decode.pages import allocate_page_pool  # noqa: E402

reference = tiny.load_reference()
PAGE, PAGES = 8, 12  # a sequence's pages: 96 positions


@pytest.fixture(scope="module")
def built():
    return tiny.build()


def reference_logits(params, tokens, **faults):
    with jax.default_matmul_precision("highest"):
        return np.asarray(
            reference.forward(params, tiny.MODEL, jnp.asarray(tokens), **faults)
        )


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_full_pass_matches_the_reference(built, attention):
    """float32 on both sides: the sums differ in order only (the flash
    kernel's blocks, the router's sorted rows), hence 1e-4."""
    module, params = built
    module = module.clone(attention=attention)
    tokens = jax.random.randint(
        jax.random.PRNGKey(5), (2, tiny.POSITIONS), 0, tiny.VOCAB
    )
    got = module.apply({"params": params}, tokens)
    want = np.stack([reference_logits(params, t) for t in tokens])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert float(np.std(want)) > 0.5


@pytest.mark.parametrize("fault", list(reference.CONTROLS))
def test_the_reference_control_and_planted_faults_move_the_pass(built, fault):
    """Each changes the logits by far more than the program differs from
    the reference."""
    _, params = built
    tokens = jax.random.randint(jax.random.PRNGKey(6), (64,), 0, tiny.VOCAB)
    sound = reference_logits(params, tokens)
    faulty = reference_logits(params, tokens, **reference.CONTROLS[fault])
    assert float(np.max(np.abs(sound - faulty))) > 100 * 1e-4


def test_the_bias_picks_and_does_not_weigh():
    """Scores (0.40, 0.35, 0.25) a token: the top-1 is expert 0, and a
    bias of +0.2 on expert 2 flips the choice to it with its own score
    0.25 as the weight (times the scale 6, not renormalised)."""
    probs = np.asarray([[0.40, 0.35, 0.25]], np.float32)
    x = jnp.eye(3, dtype=jnp.float32)[:1]
    router = jnp.zeros((3, 3)).at[0].set(jnp.log(probs[0]))
    weights, experts = route_top_k(x, router, 1, renormalize=False)
    np.testing.assert_allclose(weights, [[0.40]], rtol=1e-5)
    assert int(experts[0, 0]) == 0
    bias = jnp.asarray([0.0, 0.0, 0.2])
    weights, experts = route_top_k(x, router, 1, False, bias, 6.0)
    assert int(experts[0, 0]) == 2
    np.testing.assert_allclose(weights, [[6 * 0.25]], rtol=1e-5)
    weights, _ = route_top_k(x, router, 2, True, bias, 6.0)
    np.testing.assert_allclose(weights, [[6 * 0.25 / 0.65, 6 * 0.40 / 0.65]], rtol=1e-5)


def _moe_leaves(seed, d=16, f=8, real=tiny.REAL, zero=tiny.ZERO):
    rng = np.random.default_rng(seed)
    return dict(
        router=jnp.asarray(rng.normal(size=(d, real + zero)), jnp.float32),
        gate=jnp.asarray(rng.normal(size=(d, real * f)) / 4, jnp.float32),
        up=jnp.asarray(rng.normal(size=(d, real * f)) / 4, jnp.float32),
        down=jnp.asarray(rng.normal(size=(f, real * d)) / 3, jnp.float32),
        bias=jnp.asarray(rng.normal(size=(real + zero,)) * 0.1, jnp.float32),
        x=jnp.asarray(rng.normal(size=(40, d)), jnp.float32),
    )


def test_identity_experts_add_their_weight_times_the_input():
    """With every real expert's output zeroed, the layer is each token's
    weight on the identity columns times the token; the counts are the
    real experts' rows, and the third value the identity choices."""
    w = _moe_leaves(1)
    y, counts, zero_choices = sparse_moe(
        w["x"], w["router"], w["gate"], w["up"], jnp.zeros_like(w["down"]),
        k=3, renormalize=False, bias=w["bias"], scale=6.0,
        zero_experts=tiny.ZERO,
    )
    weights, experts = route_top_k(w["x"], w["router"], 3, False, w["bias"], 6.0)
    zero = np.where(np.asarray(experts) >= tiny.REAL, np.asarray(weights), 0.0)
    np.testing.assert_allclose(y, zero.sum(1)[:, None] * np.asarray(w["x"]), atol=1e-5)
    assert counts.shape == (tiny.REAL,)
    assert int(zero_choices) == int((np.asarray(experts) >= tiny.REAL).sum()) > 0
    assert int(counts.sum()) + int(zero_choices) == 40 * 3


def test_the_held_shares_add_up_to_the_uncut_layer(built):
    """One double layer four ways, each holding 2 of the 8 real experts
    (their column blocks of the expert leaves): the parts add up to the
    uncut layer once the part every share computes alike (the attention,
    the dense SwiGLUs, the identity experts) is counted once; and the
    program's uncut experts are the reference's (1e-4, float32)."""
    module, params = built
    f, d = tiny.FIELDS["expert_dim"], tiny.FIELDS["d_model"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 24, d))

    def layer(m, p):
        def run(mod, x):
            return mod.blocks[1](mod.blocks[0](x, False), False)

        return np.asarray(m.apply({"params": p}, x, method=run))

    def share(first, count, down_scale=1.0):
        block = dict(params["block0"])
        for name, width in (("experts_gate", f), ("experts_up", f), ("experts_down", d)):
            block[name] = block[name][:, first * width : (first + count) * width]
        block["experts_down"] = block["experts_down"] * down_scale
        return module.clone(held_experts=(first, count)), dict(params, block0=block)

    uncut = layer(module, params)
    parts = [layer(*share(2 * i, 2)) for i in range(4)]
    alike = layer(*share(0, 2, down_scale=0.0))
    np.testing.assert_allclose(sum(parts) - 3 * alike, uncut, atol=1e-4, rtol=1e-4)
    assert float(np.abs(parts[0] - alike).max()) > 1e-2

    w = params["block0"]
    u = jnp.asarray(np.random.default_rng(2).normal(size=(30, d)), jnp.float32)
    got, _, _ = sparse_moe(
        u, w["router"], w["experts_gate"], w["experts_up"], w["experts_down"],
        k=3, renormalize=False, bias=w["router_bias"], scale=6.0,
        zero_experts=tiny.ZERO,
    )
    with jax.default_matmul_precision("highest"):
        top_w, top_e, _ = reference.route(
            u, w, top_k=3, renormalize=False, bias=True, scale=6.0, real=tiny.REAL
        )
        want = reference.experts(
            u, top_w, top_e, w, cap=256, lowp=False, held=(0, tiny.REAL),
            real=tiny.REAL, identity=True,
        )
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def _pool_with_prompt(module, params, prompt, slots=2):
    """A latent pool with ``prompt``'s rows written by the cold prefill's
    page write at slot 0's pages, and the prefill's own logits."""
    layers = module.num_layers
    pool = allocate_page_pool(
        layers, slots * PAGES, PAGE, 1, 1, jnp.float32, latent=module.mla.row
    )
    table = jnp.arange(slots * PAGES, dtype=jnp.int32).reshape(slots, PAGES)
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    logits, rows = module.apply(
        {"params": params}, tokens, lengths, method="prefill"
    )
    pages = jnp.where(
        jnp.arange(PAGES)[None, :] * PAGE < len(prompt), table[:1], slots * PAGES
    )[:, : -(-len(prompt) // PAGE)]
    pool = tuple(
        _pool_write_pages(layer, {"c": state[0]}, pages)
        for layer, state in zip(pool, rows)
    )
    return pool, table, logits


@pytest.mark.parametrize("flavor", ["reference", "pallas"])
def test_prefill_then_decode_through_the_latent_pages(built, flavor):
    """The cold prefill writes one latent row a token a sub-layer (a leaf
    ``c`` each, 4 for 2 double layers), and decode steps in the absorbed form, teacher-forced with the prompt's
    own next tokens, give the full pass's logits at each position, the
    shortcut's sum carried from the first sub-layer to the second in each
    (1e-4: float32, sums in another order)."""
    module, params = built
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(7), (60,), 0, tiny.VOCAB)
    )
    want = reference_logits(params, tokens)
    prompt = 37
    pool, table, logits = _pool_with_prompt(module, params, tokens[:prompt])
    assert [sorted(layer) for layer in pool] == [["c"]] * 4
    np.testing.assert_allclose(logits[0], want[prompt - 1], atol=1e-4, rtol=1e-4)
    attn = (
        mla.mla_pool_decode_attention if flavor == "reference"
        else mla.mla_cascade_decode_attention
    )
    for pos in range(prompt, 46):
        step = jnp.asarray([tokens[pos], 0], jnp.int32)
        lengths = jnp.asarray([pos, 0], jnp.int32)
        logits, pool = module.apply(
            {"params": params}, step, lengths, pool, table,
            method="decode_step_paged", attention_override=attn,
        )
        np.testing.assert_allclose(logits[0], want[pos], atol=1e-4, rtol=1e-4)
    # an extend of the next 14 through the pool, the expanded form
    window = jnp.zeros((2, 16), jnp.int32).at[0, :14].set(tokens[46:])
    logits, _ = module.apply(
        {"params": params}, window, jnp.asarray([46, 0], jnp.int32), pool,
        table, valid=jnp.asarray([14, 0], jnp.int32), method="decode_verify_paged",
    )
    np.testing.assert_allclose(logits[0, :13], want[46:59], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "fields,message",
    [
        ({"num_layers": 3}, "even num_layers"),
        ({"mlp_dim": 0}, "mlp_dim >= 1"),
        ({"mla_kv_rank": 0, "mla_q_rank": 16}, "mla_q_rank needs latent attention"),
        ({"mlp": "swiglu"}, "need mlp='moe'"),
    ],
    ids=["odd-layers", "no-dense-mlp", "q-rank-alone", "no-experts"],
)
def test_what_the_double_layer_refuses_at_build(fields, message):
    with pytest.raises(ValueError, match=message):
        tiny.build(**fields)
