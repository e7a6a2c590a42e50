"""The page pool's write (``models.transformer._pool_write_rows``,
docs/DESIGN.md §20) against the scatter it replaced: a two-index
``buf.at[pages, offsets].set(rows, mode="drop")`` on a page-shaped
``[num_pages, page_size, heads, head_dim]`` pool. The pool now stores
its rows folded (``ops.fold_kv_rows``); unfolded, it must hold the same
bytes at the same rows, dead entries must leave it untouched, and the
padding lanes stay zero. fp and int8 pools (scale arrays included), one
head shard and two.

And the cold prefill's write a page at a time
(``_pool_write_pages``) against that row write: the same bytes in every
row below a sequence's length, no page touched that is not a live entry
of the sequence's table, finite values in what is left of its last page."""

import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu import ops
from zookeeper_tpu.models.transformer import (
    _pool_write_pages,
    _pool_write_rows,
)
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


#: name -> (bucket, table [b, pages of the bucket], lengths): what a cold
#: prefill program sees of a group.
PAGE_CASES = {
    # a bucket that is not whole pages (20 of 24 rows), full to its end
    "ragged_bucket": (20, [[4, 7, 1], [2, 10, 6]], [20, 17]),
    # a window layer's table: the pages wholly behind the window are
    # released (-1) and only the prompt's tail has a home
    "window_table": (24, [[-1, 7, 1], [-1, -1, 3]], [24, 22]),
    # a partial group: the second row is padding (all -1, length 1)
    "padding_group": (24, [[4, 7, 1], [-1, -1, -1]], [24, 1]),
    # prompts that end inside a page, one of them inside its first;
    # the pages of the bucket past them have homes (5, 9, 0) that the
    # write must leave alone
    "mid_page": (24, [[4, 7, 5], [2, 9, 0]], [13, 3]),
}


@pytest.mark.parametrize("head_shards", [1, 2])
@pytest.mark.parametrize("pool_dtype", ["bfloat16", "float32", "int8"])
@pytest.mark.parametrize("case", sorted(PAGE_CASES))
def test_page_write_matches_the_row_write(case, pool_dtype, head_shards):
    bucket, table, lengths = PAGE_CASES[case]
    table, lengths = np.asarray(table, np.int32), np.asarray(lengths, np.int32)
    rng = np.random.default_rng(sorted(PAGE_CASES).index(case))
    quant = "int8" if pool_dtype == "int8" else "none"
    (layer,) = allocate_page_pool(
        1, NUM_PAGES, PAGE_SIZE, HEADS, HEAD_DIM,
        jnp.float32 if quant == "int8" else jnp.dtype(pool_dtype),
        quant=quant, head_shards=head_shards,
    )
    # A pool that is not zeros, so that a page written by mistake shows.
    for name, buf in layer.items():
        fill = rng.uniform(0.5, 2.0, size=buf.shape)
        layer[name] = jnp.asarray(fill * (1 if "scale" in name else 20), buf.dtype)
    rows = {
        name: jnp.asarray(
            rng.normal(size=(len(lengths), bucket, HEADS, HEAD_DIM)),
            jnp.float32,
        )
        for name in ("k", "v")
    }

    # by row, as the cold prefill wrote until PR 34
    j = np.arange(bucket)
    page = table[:, j // PAGE_SIZE]
    dead = (j[None, :] >= lengths[:, None]) | (page < 0)
    want = _pool_write_rows(
        layer, rows, jnp.asarray(np.where(dead, NUM_PAGES, page)),
        jnp.asarray(np.broadcast_to(j % PAGE_SIZE, page.shape)),
    )
    # by page, as ``prefill_fn`` asks for it
    first_row = np.arange(table.shape[1]) * PAGE_SIZE
    dead_pages = (first_row[None, :] >= lengths[:, None]) | (table < 0)
    got = _pool_write_pages(
        layer, rows, jnp.asarray(np.where(dead_pages, NUM_PAGES, table))
    )

    assert sorted(got) == sorted(want) == sorted(layer)
    live = np.zeros((NUM_PAGES, PAGE_SIZE), bool)  # rows below a length
    for i, n in enumerate(lengths):
        for pos in range(n):
            if table[i, pos // PAGE_SIZE] >= 0:
                live[table[i, pos // PAGE_SIZE], pos % PAGE_SIZE] = True
    homes = live.any(axis=1)  # pages with such a row
    assert live.any() and not homes.all()
    for name in got:
        g, w, before = (
            np.asarray(x[name]).astype(np.float32) for x in (got, want, layer)
        )
        assert got[name].dtype == layer[name].dtype
        # [pages, shards, rows, x] -> [pages, rows, shards, x]
        g, w, before = (np.swapaxes(x, 1, 2) for x in (g, w, before))
        np.testing.assert_array_equal(g[live], w[live])
        assert (w[live] != before[live]).any()  # the case writes at all
        np.testing.assert_array_equal(g[~homes], before[~homes])
        assert np.isfinite(g).all()


def test_probe_rehearses_the_three_writes_on_the_cpu():
    """``tools/probe_pool_write.py --rehearse`` walks the probe's whole
    control flow at a tiny size (the row write, XLA's page window and
    the Pallas copies, a full table and a window layer's): every line
    names the CPU and carries no time, and both page writes leave the
    row write's bytes below each length."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "probe_pool_write.py"),
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    assert {(x["shape"], x["variant"]) for x in lines} == {
        (shape, variant)
        for shape in ("rehearsal.full", "rehearsal.window")
        for variant in ("rows", "xla_pages", "pallas_pages")
    }
    for line in lines:
        assert line["device"]["platform"] == "cpu"
        assert line["ms_per_layer_write"] is None
        assert line["agrees_below_length"] is (
            None if line["variant"] == "rows" else True
        )
    assert "not measured" in done.stdout  # the table's cells
