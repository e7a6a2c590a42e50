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
def test_tpu_grouped_kernel_in_the_interpreter(sizes):
    m, k, n, tm = 512, 64, 128, 128
    rng = np.random.default_rng(1)
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((k, len(sizes) * n)).astype(np.float32)
    out = np.asarray(
        moe._gmm(
            jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes, jnp.int32),
            out_dtype=jnp.float32, tm=tm, interpret=True,
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
