"""A layer's K and V write into the page pool alone, on the chip, at each
serving configuration's pool shape and prefill buckets, three ways:

- ``rows``: one scatter index a token (``models.transformer.
  _pool_write_rows``), as a cold prefill wrote until PR 34 and as the
  decode step, the extend program and the prefill chunks still do;
- ``xla_pages``: XLA's scatter with a page as its window
  (``_pool_write_pages``), one index a page;
- ``pallas_pages``: one asynchronous copy a page from the rows to
  ``pool.at[page]``, HBM to HBM, page ids scalar-prefetched, the pools
  aliased in to out (:func:`pallas_write_pages`, kept here: the probe's
  table chose the lowering that shipped, PERF.md section 6, PR 34).

    chiprun --timeout 900 -- python tools/probe_pool_write.py [--only gpt2_xl]

Beside each time, the time the same bytes need at the HBM peak (the live
pages' rows read once and written once, K and V). A write is timed inside
one program of ``LAYERS`` chained writes into one donated pool, each to
pages of its own, so the host's dispatch is a small part of it and the
index arithmetic around the scatter is in it, as in the prefill program.
A prompt fills four fifths of its bucket and ends inside a page; a window
layer's table holds the pages of the prompt's last ``window`` rows only.
Every variant's pool is compared with the row write's below each
sequence's length. Every line names the device it ran on; no time comes
from a CPU (``--rehearse``: small shapes, for the control flow only).
"""

import argparse
import faulthandler
import json
import os
import sys
import time

import numpy as np

# After whatever PYTHONPATH names, so that an older tree given there wins.
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9  # TPU v5e, Google Cloud "TPU v5e"
LAYERS = 24
DISTINCT_ROWS = 4
PAGE = 16


def shapes(rehearse=False):
    """name -> (slots, kv_heads, head_dim, capacity, window, buckets,
    dtype): the serving cells' pools (``benchmarks/configs/*.json``)."""
    if rehearse:
        return {
            "rehearsal.full": (16, 5, 16, 256, None, (40, 64), "bfloat16"),
            "rehearsal.window": (32, 2, 64, 256, 32, (64,), "float32"),
        }
    return {
        "gpt2_xl_24l": (48, 25, 64, 1024, None, (128, 512, 1024), "bfloat16"),
        "mellum2_8l.full": (
            64, 4, 128, 8192, None, (640, 1280, 3584, 7168), "bfloat16"),
        "mellum2_8l.window": (
            64, 4, 128, 8192, 1024, (640, 1280, 3584, 7168), "bfloat16"),
        "falcon_h1_34b_4l": (
            128, 4, 128, 2048, None, (128, 512, 1024), "bfloat16"),
        "solar_open2_ep8_4l": (
            128, 8, 128, 8192, None, (768, 1728, 6144), "bfloat16"),
    }


def pallas_write_pages(k_pool, v_pool, k_vals, v_vals, pages, *, interpret):
    """``vals [n, head_shards, page_size, row_width]`` to ``pool.at[pages
    [n]]``, K and V in one call: every live page's two copies started from
    a loop over the scalar-prefetched ids, then all waited for. An id
    ``== num_pages`` starts none."""
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_pages, n = k_pool.shape[0], pages.shape[0]

    def kernel(pages_ref, k_vals, v_vals, k_in, v_in, k_out, v_out, sem):
        del k_in, v_in  # aliased: the outputs are the pools themselves

        def each(act):
            def body(i, carry):
                page = pages_ref[i]

                @pl.when(page < num_pages)
                def _live():
                    for j, (src, dst) in enumerate(
                        ((k_vals, k_out), (v_vals, v_out))
                    ):
                        act(
                            pltpu.make_async_copy(
                                src.at[i], dst.at[page], sem.at[j]
                            )
                        )

                return carry

            lax.fori_loop(0, n, body, 0)

        each(lambda copy: copy.start())
        each(lambda copy: copy.wait())

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[anywhere] * 4,
            out_specs=[anywhere] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype)
            for pool in (k_pool, v_pool)
        ],
        # operands count the prefetched ids: pages 0, vals 1-2, pools 3-4
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(pages, k_vals, v_vals, k_pool, v_pool)


def write(variant, layer, rows, table, length, interpret):
    """One layer's write of ``rows {k, v: [1, sb, heads, head_dim]}``
    through ``table [1, pages of the bucket]``, the way ``variant`` says."""
    import jax.numpy as jnp

    from zookeeper_tpu import ops
    from zookeeper_tpu.models import transformer

    num_pages, shards, ps, width = layer["k"].shape
    sb = rows["k"].shape[1]
    if variant == "rows":
        j = jnp.arange(sb)
        pages = table[:, j // ps]
        dead = (j[None, :] >= length) | (pages < 0)
        return transformer._pool_write_rows(
            layer, rows, jnp.where(dead, num_pages, pages),
            jnp.broadcast_to(j % ps, (1, sb)),
        )
    first_row = jnp.arange(table.shape[1]) * ps
    dead = (first_row[None, :] >= length) | (table < 0)
    pages = jnp.where(dead, num_pages, table)
    if variant == "xla_pages":
        return transformer._pool_write_pages(layer, rows, pages)
    vals = []
    for name in ("k", "v"):
        folded = ops.fold_kv_rows(rows[name], shards, width)
        folded = jnp.pad(
            folded, [(0, 0), (0, pages.shape[1] * ps - sb), (0, 0), (0, 0)]
        )
        vals.append(
            folded.reshape(-1, ps, shards, width).swapaxes(1, 2)
            .astype(layer[name].dtype)
        )
    k, v = pallas_write_pages(
        layer["k"], layer["v"], *vals, pages[0], interpret=interpret
    )
    return {"k": k, "v": v}


def tables(slots, capacity, window, bucket, length):
    """``[LAYERS, 1, pages of the bucket]``: each layer's write goes to
    pages of its own (a window layer's: the tail's), and how many pages a
    write holds rows of."""
    n = -(-bucket // PAGE)
    span = capacity // PAGE if window is None else window // PAGE + 2
    num_pages = slots * span
    first = 0 if window is None else max(length - window, 0) // PAGE
    live = np.arange(first, -(-length // PAGE))
    if LAYERS * len(live) > num_pages:
        raise ValueError("the pool is too small for a page set a layer")
    perm = np.random.default_rng(34).permutation(num_pages)
    out = np.full((LAYERS, 1, n), -1, np.int32)
    for i in range(LAYERS):
        out[i, 0, live] = perm[i * len(live):(i + 1) * len(live)]
    return out, num_pages, len(live)


def time_variant(variant, pools, rows, table, live, length, reps, interpret):
    """Milliseconds a layer's write (None off the chip), the rows the
    pools hold at ``live`` (page and offset of every position below the
    length, a layer) after one program of ``LAYERS`` writes, and the
    program's text."""
    import jax

    def chain(layer, rows, table):
        for i in range(LAYERS):
            layer = write(
                variant, layer,
                {name: leaf[i % DISTINCT_ROWS] for name, leaf in rows.items()},
                table[i], length, interpret,
            )
        return layer

    fn = jax.jit(chain, donate_argnums=0).lower(pools, rows, table).compile()
    pools = fn(pools, rows, table)
    held = {
        name: np.asarray(leaf[live[0], 0, live[1]])
        for name, leaf in pools.items()
    }
    if jax.default_backend() != "tpu":
        return None, held, fn.as_text()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            pools = fn(pools, rows, table)
        jax.block_until_ready(pools)
        best = min(best, (time.perf_counter() - t0) / (reps * LAYERS))
    return best * 1e3, held, fn.as_text()


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--only", default="")
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--variants", default="rows,xla_pages,pallas_pages")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument(
        "--timeout", type=float, default=180.0,
        help="seconds one variant may take before the process is ended: a "
        "copy waited for and never started hangs the chip, not the host",
    )
    opts = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from zookeeper_tpu import ops
    from zookeeper_tpu.observability.hlo import count_copies_of_size

    device = jax.devices()[0]
    if device.platform != "tpu" and not opts.rehearse:
        print("probe_pool_write: no TPU; a time comes only from the chip")
        return 2
    interpret = device.platform != "tpu"
    table_lines = []
    for name, shape in shapes(opts.rehearse).items():
        if opts.only and opts.only not in name:
            continue
        slots, kv_heads, head_dim, capacity, window, buckets, dtype = shape
        width = ops.kv_row_width(kv_heads, head_dim)
        for bucket in buckets:
            length = bucket * 4 // 5 + 3
            table, num_pages, live_pages = tables(
                slots, capacity, window, bucket, length
            )
            key = jax.random.PRNGKey(bucket)
            rows = {
                leaf: jax.random.normal(
                    jax.random.fold_in(key, i),
                    (DISTINCT_ROWS, 1, bucket, kv_heads, head_dim),
                    jnp.bfloat16,
                )
                for i, leaf in enumerate(("k", "v"))
            }
            itemsize = jnp.dtype(dtype).itemsize
            least_ms = (
                2 * 2 * live_pages * PAGE * width * itemsize
                / HBM_BYTES_PER_S * 1e3
            )
            # page and offset of every position a layer's write owes
            first = 0 if window is None else max(length - window, 0) // PAGE
            pos = np.arange(first * PAGE, length)
            live = (table[:, 0, pos // PAGE].ravel(), np.tile(pos % PAGE, LAYERS))
            want, row_ms = None, None
            for variant in opts.variants.split(","):
                pools = {
                    leaf: jnp.zeros((num_pages, 1, PAGE, width), dtype)
                    for leaf in ("k", "v")
                }
                faulthandler.dump_traceback_later(opts.timeout, exit=True)
                ms, held, text = time_variant(
                    variant, pools, rows, jnp.asarray(table), live, length,
                    opts.reps, interpret,
                )
                faulthandler.cancel_dump_traceback_later()
                del pools
                # Below each sequence's length every variant holds the
                # row write's bytes (the first variant asked for is the
                # yardstick: name ``rows`` first).
                agree = None
                if want is None:
                    want = held
                else:
                    agree = all(
                        np.array_equal(held[leaf], want[leaf])
                        and held[leaf].any()
                        for leaf in ("k", "v")
                    )
                if variant == "rows":
                    row_ms = ms
                line = {
                    "probe": "pool_write", "shape": name, "bucket": bucket,
                    "length": length, "window": window, "variant": variant,
                    "pool": [num_pages, 1, PAGE, width], "dtype": dtype,
                    "live_pages": live_pages, "ms_per_layer_write": ms,
                    "ms_at_hbm_peak": least_ms,
                    "share_of_hbm_peak": ms and least_ms / ms,
                    "times_the_row_write": ms and row_ms and row_ms / ms,
                    "agrees_below_length": agree,
                    "scatters": text.count(" scatter("),
                    "pool_sized_copies": count_copies_of_size(
                        text, {num_pages * PAGE * width}
                    ),
                    "device": {
                        "platform": device.platform,
                        "kind": device.device_kind,
                    },
                }
                print(json.dumps(line), flush=True)
                table_lines.append(line)
                if not opts.rehearse:
                    os.makedirs("chiprun_out", exist_ok=True)
                    with open("chiprun_out/probe_pool_write.jsonl", "a") as f:
                        f.write(json.dumps(line) + "\n")
    print("| pool | bucket | live pages | variant | us a layer's K+V | "
          "us at HBM's peak | of the peak | times the row write |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- |")
    for line in table_lines:
        ms = line["ms_per_layer_write"]
        print(
            f"| `{line['shape']}` | {line['bucket']} | {line['live_pages']} | "
            f"{line['variant']} | "
            + (f"{ms * 1e3:.1f}" if ms else "not measured")
            + f" | {line['ms_at_hbm_peak'] * 1e3:.1f} | "
            + (f"{line['share_of_hbm_peak']:.1%}" if ms else "")
            + " | "
            + (f"{line['times_the_row_write']:.1f}" if ms else "")
            + " |"
        )
    bad = [line for line in table_lines if line["agrees_below_length"] is False]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
