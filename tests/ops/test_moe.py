"""The sparse expert layer (``ops/moe.py``) against the plain sum over
experts, with the routing computed here and not by the program; the TPU's
grouped kernel in the interpreter against a loop over the groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu.ops import moe

D, F, EXPERTS, TOP_K = 48, 32, 8, 3


def _layer(seed=0):
    rng = np.random.default_rng(seed)
    router = rng.standard_normal((D, EXPERTS)).astype(np.float32) * D ** -0.5
    gate = rng.standard_normal((D, EXPERTS * F)).astype(np.float32) * D ** -0.5
    up = rng.standard_normal((D, EXPERTS * F)).astype(np.float32) * D ** -0.5
    down = rng.standard_normal((F, EXPERTS * D)).astype(np.float32) * F ** -0.5
    return router, gate, up, down


def _plain_sum(x, router, gate, up, down, k):
    """Float64, every token by itself, expert ``e`` the column block ``e``."""
    logits = x.astype(np.float64) @ router.astype(np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        chosen = np.argsort(-probs[t])[:k]
        weights = probs[t, chosen] / probs[t, chosen].sum()
        for e, w in zip(chosen, weights):
            g = x[t] @ gate[:, e * F:(e + 1) * F]
            u = x[t] @ up[:, e * F:(e + 1) * F]
            out[t] += w * ((g / (1.0 + np.exp(-g)) * u) @ down[:, e * D:(e + 1) * D])
    return out


@pytest.mark.parametrize("tokens", [1, 7, 64], ids=lambda t: f"{t}-tokens")
def test_sparse_moe_is_the_plain_sum_over_the_routed_experts(tokens):
    router, gate, up, down = _layer()
    x = np.random.default_rng(tokens).standard_normal((tokens, D)).astype(np.float32)
    got, load = jax.jit(lambda *a: moe.sparse_moe(*a, k=TOP_K))(
        x, router, gate, up, down
    )
    # no token is dropped, whatever the load
    assert int(load.sum()) == tokens * TOP_K
    # float32 matmuls against float64: rounding only
    np.testing.assert_allclose(
        np.asarray(got), _plain_sum(x, router, gate, up, down, TOP_K), atol=1e-4
    )


def test_one_routed_expert_dropped_is_caught():
    """The fault the benchmark plants (a token's last routed expert
    dropped) is far outside the tolerance above."""
    router, gate, up, down = _layer()
    x = np.random.default_rng(5).standard_normal((32, D)).astype(np.float32)
    got, _ = moe.sparse_moe(x, router, gate, up, down, k=TOP_K - 1)
    want = _plain_sum(x, router, gate, up, down, TOP_K)
    assert np.abs(np.asarray(got) - want).max() > 100 * 1e-4


@pytest.mark.parametrize(
    "sizes",
    [
        [0, 130, 1, 0, 200, 53, 0, 100],   # empty groups, shared tiles, 28 rows over
        [512, 0, 0, 0, 0, 0, 0, 0],        # one group takes every tile
        [64] * 8,                          # two groups a tile
        [0] * 7 + [3],                     # a last group of three rows
    ],
    ids=["uneven", "one-group", "even", "nearly-empty"],
)
@pytest.mark.parametrize("n,tn", [(128, None), (256, 128)], ids=["whole", "column-tiles"])
def test_tpu_grouped_kernel_in_the_interpreter(sizes, n, tn):
    m, k, tm = 512, 64, 128
    rng = np.random.default_rng(1)
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((k, len(sizes) * n)).astype(np.float32)
    out = np.asarray(
        moe._gmm(
            jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes, jnp.int32),
            out_dtype=jnp.float32, tm=tm, tn=tn, interpret=True,
        )
    )
    start = 0
    for g, size in enumerate(sizes):
        np.testing.assert_allclose(
            out[start:start + size],
            lhs[start:start + size] @ rhs[:, g * n:(g + 1) * n],
            rtol=1e-4, atol=1e-4,
        )
        start += size


def test_router_runs_in_float32_whatever_the_rows_are():
    router, *_ = _layer()
    x = np.random.default_rng(2).standard_normal((16, D)).astype(np.float32)
    w32, e32 = moe.route_top_k(jnp.asarray(x), router, TOP_K)
    w16, e16 = moe.route_top_k(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), router, TOP_K)
    assert w32.dtype == jnp.float32 and e32.dtype == jnp.int32
    np.testing.assert_allclose(np.asarray(w32.sum(-1)), 1.0, atol=1e-6)
    assert np.asarray(w16).shape == (16, TOP_K) and e16.shape == (16, TOP_K)


# -- a chip's share of the experts (``held``) ---------------------------------


def _held_sum(x, router, gate, up, down, k, first, count):
    """:func:`_plain_sum` over the routed experts in ``first .. first +
    count`` only, from the WHOLE layer's leaves."""
    logits = x.astype(np.float64) @ router.astype(np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        chosen = np.argsort(-probs[t])[:k]
        weights = probs[t, chosen] / probs[t, chosen].sum()
        for e, w in zip(chosen, weights):
            if first <= e < first + count:
                g = x[t] @ gate[:, e * F:(e + 1) * F]
                u = x[t] @ up[:, e * F:(e + 1) * F]
                out[t] += w * ((g / (1.0 + np.exp(-g)) * u) @ down[:, e * D:(e + 1) * D])
    return out


def _share(leaves, first, count):
    """The column blocks ``first .. first + count`` of the three expert
    leaves: what a chip that holds those experts holds."""
    gate, up, down = leaves
    return (
        gate[:, first * F:(first + count) * F], up[:, first * F:(first + count) * F],
        down[:, first * D:(first + count) * D],
    )


@pytest.mark.parametrize("first,count", [(0, 2), (2, 2), (5, 3), (0, 8)])
def test_a_share_gives_its_own_experts_part(first, count):
    router, *leaves = _layer()
    x = np.random.default_rng(9).standard_normal((33, D)).astype(np.float32)
    got, load = jax.jit(
        lambda *a: moe.sparse_moe(*a, k=TOP_K, held=(first, count))
    )(x, router, *_share(leaves, first, count))
    np.testing.assert_allclose(
        np.asarray(got), _held_sum(x, router, *leaves, TOP_K, first, count), atol=1e-4
    )
    _, whole = moe.sparse_moe(x, router, *leaves, k=TOP_K)
    np.testing.assert_array_equal(load, whole[first:first + count])


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that 4 shares of 2 experts give, summed, are what
    the layer that holds all 8 gives."""
    router, *leaves = _layer()
    x = np.random.default_rng(10).standard_normal((40, D)).astype(np.float32)
    whole, _ = moe.sparse_moe(x, router, *leaves, k=TOP_K)
    parts = sum(
        moe.sparse_moe(x, router, *_share(leaves, first, 2), k=TOP_K, held=(first, 2))[0]
        for first in range(0, EXPERTS, 2)
    )
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=2e-5)


def test_no_share_is_todays_function_bit_for_bit():
    """``held=None`` computes what the function computed before it knew
    of shares: the same values and the same jaxpr as the lines it had,
    kept here."""
    router, gate, up, down = _layer()
    x = jnp.asarray(np.random.default_rng(11).standard_normal((29, D)), jnp.float32)

    def before(x, router, gate, up, down, k):
        t, d = x.shape
        weights, experts = moe.route_top_k(x, router, k)
        flat_expert = experts.reshape(t * k)
        order = jnp.argsort(flat_expert, stable=True)
        rows = x[order // k]
        group_sizes = jnp.bincount(flat_expert, length=router.shape[1]).astype(jnp.int32)

        def grouped(lhs, rhs, out_dtype):
            return moe.grouped_matmul(lhs, rhs.astype(lhs.dtype), group_sizes, out_dtype)

        hidden = jax.nn.silu(grouped(rows, gate, jnp.float32)) * grouped(rows, up, jnp.float32)
        out = grouped(hidden.astype(x.dtype), down, x.dtype)
        out = out[jnp.argsort(order)].reshape(t, k, d)
        y = jnp.sum(out.astype(jnp.float32) * weights[..., None], axis=1)
        return y.astype(x.dtype), group_sizes

    want = jax.jit(lambda *a: before(*a, TOP_K))(x, router, gate, up, down)
    got = jax.jit(lambda *a: moe.sparse_moe(*a, k=TOP_K))(x, router, gate, up, down)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the all-experts share is the same sum, to rounding
    every, _ = moe.sparse_moe(x, router, gate, up, down, k=TOP_K, held=(0, EXPERTS))
    np.testing.assert_allclose(np.asarray(every), np.asarray(want[0]), atol=1e-6)
