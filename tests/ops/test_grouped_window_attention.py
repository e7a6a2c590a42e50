"""Grouped key/value heads and the sliding-window band, in the flash
forward and in the page-pool decode kernel (interpret mode), against
``attention_reference`` with the same mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu.ops import (
    attention_reference,
    cached_attention,
    flash_attention,
    fold_kv_pool,
    pool_decode_attention,
    pool_paged_decode_attention,
    pool_verify_attention,
)


def _qkv(seed, b, s, h, hkv, d, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, s, h, d), dtype)
    k = jax.random.normal(k2, (b, s, hkv, d), dtype)
    v = jax.random.normal(k3, (b, s, hkv, d), dtype)
    return q, k, v


def _band_reference(q, k, v, window):
    """The mask written out, independent of ``attention_reference``."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    i = jnp.arange(q.shape[1])[:, None]
    p = jnp.arange(q.shape[1])[None, :]
    keep = p <= i
    if window is not None:
        keep = keep & (i - p < window)
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("window", [None, 5, 24, 200])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (8, 1)])
def test_reference_band_and_groups(window, heads):
    h, hkv = heads
    q, k, v = _qkv(0, 2, 40, h, hkv, 16)
    got = attention_reference(q, k, v, causal=True, window=window)
    want = _band_reference(q, k, v, window)
    # float32 einsums at the highest precision on both sides
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize(
    "s,window,blocks",
    [
        (64, 16, (16, 16)),   # a band of one block: blocks behind it skip
        (96, 40, (32, 16)),   # unequal blocks, a band that straddles them
        (50, 7, (16, 16)),    # padded rows beyond every key's band
        (64, None, (16, 16)),  # grouped heads alone
        (64, 1000, (32, 32)),  # a band wider than the sequence
    ],
)
@pytest.mark.parametrize("heads", [(4, 2), (8, 1), (2, 2)])
def test_flash_band_and_groups(s, window, blocks, heads):
    h, hkv = heads
    q, k, v = _qkv(1, 2, s, h, hkv, 32)
    got = flash_attention(
        q, k, v, causal=True, window=window,
        block_q=blocks[0], block_k=blocks[1], interpret=True,
    )
    want = attention_reference(q, k, v, causal=True, window=window)
    # float32 operands: the kernel's online softmax reassociates sums
    np.testing.assert_allclose(got, want, atol=3e-6, rtol=3e-6)


def test_flash_band_has_no_backward():
    q, k, v = _qkv(2, 1, 32, 2, 1, 16)

    def loss(q):
        return flash_attention(
            q, k, v, causal=True, window=8, interpret=True
        ).sum()

    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(loss)(q)


def _pool_case(seed, slots, h, hkv, d, ps, max_pages, lengths):
    """A pool with each slot's pages scattered, rows valid to its length."""
    rng = np.random.default_rng(seed)
    num_pages = slots * max_pages + 3
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pages = jax.random.normal(k1, (num_pages, ps, hkv, d), jnp.float32)
    v_pages = jax.random.normal(k2, (num_pages, ps, hkv, d), jnp.float32)
    q = jax.random.normal(k3, (slots, 1, h, d), jnp.float32)
    table = rng.permutation(num_pages)[: slots * max_pages].reshape(
        slots, max_pages
    ).astype(np.int32)
    return (
        q, fold_kv_pool(k_pages), fold_kv_pool(v_pages),
        jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
    )


@pytest.mark.parametrize(
    "h,hkv,d",
    [
        (8, 2, 128),   # the matmul path: a head a column, a group of 4
        (16, 2, 128),  # a group of 8, as the served model has
        (4, 2, 64),    # grouped heads on the lane-reduction path
        (2, 2, 64),    # one member a group: the path as it was
    ],
)
@pytest.mark.parametrize("window", [None, 20, 48])
def test_pool_kernel_groups_and_window(h, hkv, d, window):
    ps, max_pages = 8, 12
    lengths = [0, 5, 19, 20, 47, 95]
    q, kp, vp, table, lens = _pool_case(3, len(lengths), h, hkv, d, ps, max_pages, lengths)
    got = pool_paged_decode_attention(
        q, kp, vp, table, lens, kv_heads=hkv, window=window, interpret=True
    )
    want = pool_decode_attention(
        q, kp, vp, table, lens, kv_heads=hkv, window=window
    )
    # float32 pool: online softmax against one softmax, sums reassociated
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)


def test_pool_kernel_never_reads_behind_the_window():
    """Pages wholly behind ``length - window`` may be released: with
    their table entries at -1 and their rows poisoned the result does
    not move."""
    ps, max_pages, window = 8, 12, 20
    lengths = [30, 64, 95]
    q, kp, vp, table, lens = _pool_case(4, 3, 8, 2, 128, ps, max_pages, lengths)
    clean = pool_paged_decode_attention(
        q, kp, vp, table, lens, kv_heads=2, window=window, interpret=True
    )
    table = np.array(table)
    kp, vp = np.array(kp), np.array(vp)
    for slot, n in enumerate(lengths):
        first = max(n - window + 1, 0) // ps
        for page in table[slot, :first]:
            kp[page] = np.nan
            vp[page] = np.nan
        table[slot, :first] = -1
    # page 0 is what a clipped -1 entry points at: poison it as well
    if 0 not in table:
        kp[0] = np.nan
        vp[0] = np.nan
    released = pool_paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table), lens,
        kv_heads=2, window=window, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(released))


@pytest.mark.parametrize(
    "h,hkv,d", [(16, 2, 128), (4, 2, 64)], ids=["matmul", "lane"]
)
@pytest.mark.parametrize("window", [300, 700])
def test_pool_kernel_window_band_over_blocks_of_pages(h, hkv, d, window):
    """A band of several of the kernel's page blocks whose first page
    is not page 0: the released entries behind it are -1, and every
    page no band touches (behind it, past the length, unallocated) is
    NaN, so a page fetched that should not be poisons the result."""
    from tests.ops.test_pool_attention import only_live_pages
    from zookeeper_tpu.ops import kv_row_width, pool_decode_block_pages

    ps, max_pages = 16, 80
    n = pool_decode_block_pages(ps, kv_row_width(hkv, d), 4, max_pages, window)
    assert n * ps < window or n == (window + ps - 2) // ps + 1
    lengths = [0, window - 1, window, min(window + n * ps + 1, 1200), 1100, 1279]
    q, kp, vp, table, lens = _pool_case(
        8, len(lengths), h, hkv, d, ps, max_pages, lengths
    )
    want = pool_decode_attention(
        q, kp, vp, table, lens, kv_heads=hkv, window=window
    )
    kp, vp, table = only_live_pages(
        np.array(kp), np.array(vp), np.array(table), lengths, window
    )
    got = pool_paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table), lens,
        kv_heads=hkv, window=window, interpret=True,
    )
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    np.testing.assert_array_equal(
        np.asarray(got).argmax(-1), np.asarray(want).argmax(-1)
    )


@pytest.mark.parametrize("window", [None, 12])
def test_pool_verify_groups_and_window(window):
    """The gathered path extend and verify use: at one position it is
    the decode path; over a window of positions each takes its band."""
    ps, max_pages, hkv, h, d = 8, 6, 2, 4, 16
    lengths = [3, 17, 30]
    q1, kp, vp, table, lens = _pool_case(5, 3, h, hkv, d, ps, max_pages, lengths)
    w = 4
    q = jax.random.normal(jax.random.PRNGKey(9), (3, w, h, d), jnp.float32)
    got = pool_verify_attention(
        q, kp, vp, table, lens, kv_heads=hkv, window=window
    )
    for j in range(w):
        want = pool_decode_attention(
            q[:, j:j + 1], kp, vp, table, lens + j, kv_heads=hkv,
            window=window,
        )
        np.testing.assert_allclose(got[:, j:j + 1], want, atol=2e-6, rtol=2e-6)


def test_cached_attention_window_matches_full_pass():
    """The decode oracle at position n is the full pass's row n."""
    q, k, v = _qkv(6, 2, 24, 4, 2, 16)
    full = attention_reference(q, k, v, causal=True, window=9)
    n = 17
    got = cached_attention(
        q[:, n:n + 1], k, v, jnp.full((2,), n, jnp.int32), window=9
    )
    np.testing.assert_allclose(got[:, 0], full[:, n], atol=2e-6, rtol=2e-6)
