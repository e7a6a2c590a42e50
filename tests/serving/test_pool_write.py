"""The page pool's write (``models.transformer._pool_write_rows``,
docs/DESIGN.md §20) against the scatter it replaced: a two-index
``buf.at[pages, offsets].set(rows, mode="drop")`` on a page-shaped
``[num_pages, page_size, heads, head_dim]`` pool. The pool now stores
its rows folded (``ops.fold_kv_rows``); unfolded, it must hold the same
bytes at the same rows, dead entries must leave it untouched, and the
padding lanes stay zero. fp and int8 pools (scale arrays included), one
head shard and two."""

import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu import ops
from zookeeper_tpu.models.transformer import _pool_write_rows
from zookeeper_tpu.serving.decode.pages import allocate_page_pool

NUM_PAGES, PAGE_SIZE, HEADS, HEAD_DIM = 12, 8, 6, 16
MAX_PAGES = 3  # a slot's table row


def old_scatter(pool, rows, pages, offsets):
    """The write as it was, on a page-shaped pool dict."""
    out = dict(pool)
    for name, scale_name in (("k", "k_scale"), ("v", "v_scale")):
        vals = rows[name]
        if scale_name in pool:
            q, s = ops.quantize_kv_rows(vals)
            out[name] = pool[name].at[pages, offsets].set(q, mode="drop")
            out[scale_name] = pool[scale_name].at[pages, offsets].set(
                s, mode="drop"
            )
        else:
            out[name] = pool[name].at[pages, offsets].set(
                vals.astype(pool[name].dtype), mode="drop"
            )
    return out


def decode_case():
    """One row a slot: slot 1 is inactive (its page is the sentinel),
    slot 3's table entry is unallocated (-1)."""
    table = np.array(
        [[4, 7, -1], [2, -1, -1], [9, 0, 5], [11, -1, -1]], np.int32
    )
    lengths = np.array([9, 3, 23, 8], np.int32)
    page = table[np.arange(4), lengths // PAGE_SIZE]
    page = np.where(page < 0, NUM_PAGES, page)
    page[1] = NUM_PAGES
    return page, lengths % PAGE_SIZE, (4,)


def window_case():
    """A five-row window that crosses a page boundary in slot 0, runs
    into an unallocated page in slot 1, and is cut by ``valid`` in
    slot 2 (rows past it write nowhere)."""
    table = np.array([[4, 7, 1], [2, -1, -1], [9, 0, 5]], np.int32)
    lengths = np.array([5, 6, 14], np.int32)
    valid = np.array([5, 5, 2], np.int32)
    pos = lengths[:, None] + np.arange(5)[None, :]
    page = np.take_along_axis(table, pos // PAGE_SIZE, axis=1)
    dead = (page < 0) | (np.arange(5)[None, :] >= valid[:, None])
    return np.where(dead, NUM_PAGES, page), pos % PAGE_SIZE, (3, 5)


def prefill_case():
    """A group of three prompts over a 24-token bucket: a full prompt,
    one that ends inside its second page, and a padding row (an all -1
    table row, length 0)."""
    table = np.array([[4, 7, 1], [2, 10, -1], [-1, -1, -1]], np.int32)
    lengths = np.array([24, 13, 0], np.int32)
    j = np.arange(24)
    page = table[:, j // PAGE_SIZE]
    dead = (j[None, :] >= lengths[:, None]) | (page < 0)
    offsets = np.broadcast_to(j % PAGE_SIZE, page.shape)
    return np.where(dead, NUM_PAGES, page), offsets, (3, 24)


CASES = {"decode": decode_case, "window": window_case, "prefill": prefill_case}


@pytest.mark.parametrize("head_shards", [1, 2])
@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_pool_write_matches_the_two_index_scatter(case, quant, head_shards):
    rng = np.random.default_rng(sorted(CASES).index(case))
    pages, offsets, lead = CASES[case]()
    assert (pages == NUM_PAGES).any() and (pages < NUM_PAGES).any()
    rows = {
        name: jnp.asarray(
            rng.normal(size=lead + (HEADS, HEAD_DIM)), jnp.float32
        )
        for name in ("k", "v")
    }
    # Both pools start from the same non-zero contents, so a write that
    # lands on a dead entry, or misses a live one, shows.
    page_shaped = {
        name: rng.normal(size=(NUM_PAGES, PAGE_SIZE, HEADS, HEAD_DIM))
        for name in ("k", "v")
    }
    scales = {
        name: rng.uniform(0.5, 2.0, size=(NUM_PAGES, PAGE_SIZE, HEADS))
        for name in ("k_scale", "v_scale")
    }
    (layer,) = allocate_page_pool(
        1, NUM_PAGES, PAGE_SIZE, HEADS, HEAD_DIM, jnp.float32,
        quant=quant, head_shards=head_shards,
    )
    old = {}
    for name in ("k", "v"):
        start = jnp.asarray(page_shaped[name] * 20, layer[name].dtype)
        old[name] = start
        folded = ops.fold_kv_pool(start, head_shards)
        assert folded.shape == layer[name].shape
        layer[name] = folded
    if quant == "int8":
        for name in ("k_scale", "v_scale"):
            old[name] = jnp.asarray(scales[name], jnp.float32)
            folded = ops.fold_kv_scales(old[name], head_shards)
            assert folded.shape == layer[name].shape
            layer[name] = folded

    want = old_scatter(old, rows, jnp.asarray(pages), jnp.asarray(offsets))
    got = _pool_write_rows(
        layer, rows, jnp.asarray(pages), jnp.asarray(offsets)
    )

    assert sorted(got) == sorted(want)
    for name in ("k", "v"):
        stored = np.asarray(got[name])
        assert stored.dtype == np.asarray(want[name]).dtype
        unfolded = ops.unfold_kv_rows(
            np.swapaxes(stored, 1, 2), HEADS, HEAD_DIM
        )
        np.testing.assert_array_equal(unfolded, np.asarray(want[name]))
        # The padding lanes (96 values in a 128-lane row) stay zero.
        used = (HEADS // head_shards) * HEAD_DIM
        assert not stored[..., used:].any()
    if quant == "int8":
        for name in ("k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(got[name]),
                np.asarray(ops.fold_kv_scales(want[name], head_shards)),
            )
