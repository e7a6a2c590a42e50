"""The pool decode kernel alone, on the chip, at the shapes the benchmark's
serving cells give it: milliseconds a call beside the time the live KV's
bytes need at the HBM peak, and the seconds one trace and one lowering of
the kernel take (what every process that builds a decode program pays,
compile cache or not).

    chiprun --timeout 900 -- python tools/probe_pool_decode.py [--halves]
        [--block-bytes 262144,1048576] [--only mellum2]
    PYTHONPATH=<a parent checkout> python tools/probe_pool_decode.py

``--halves`` also times the kernel with its arithmetic stubbed out
(copies only) and with its copies stubbed out (arithmetic only), which
says which of the two bounds a path (``halves`` of the kernel's private
entry); ``--block-bytes`` the kernel at other block sizes
(``ops.blocks._POOL_BLOCK_BYTES``, read while the kernel is traced). Run
against an older tree (``PYTHONPATH``) it times that tree's kernel
through the same public entry point.

``lower_s`` is to be compared between trees and not added to a
``setup_s``: in this process on the v5e's host it read 3.0 s for the lane
path's kernel where a bare script and the serving program read 0.55-0.70 s
(PERF.md, PR 28; cause not found).

A call is timed inside one program of ``LAYERS`` chained calls (each
call's output is the next one's query), so the host's dispatch is not in
it; the index arithmetic around the kernel is. Every variant runs under a
watchdog (``--timeout``): a kernel that waits for a copy nobody started
hangs the chip, not the host, and the process is ended instead of the
call's whole time limit being spent. Every line names the device it ran
on; no time comes from a CPU.
"""

import argparse
import faulthandler
import itertools
import json
import os
import sys
import time
from functools import partial

import numpy as np

# After whatever PYTHONPATH names, so that an older tree given there wins.
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9  # TPU v5e, Google Cloud "TPU v5e"
LAYERS = 8
PAGE = 16


def shapes(rehearse=False):
    """name -> (slots, heads, kv_heads, head_dim, max_pages, window,
    lengths): the serving cells' decode calls (``rehearse``: the same
    control flow at a size the CPU's interpreter walks in seconds)."""
    rng = np.random.default_rng(27)
    if rehearse:
        lengths = np.array([0, 70, 300, 1100])
        return {
            "rehearsal.lane": (4, 4, 4, 64, 80, None, lengths),
            "rehearsal.matmul": (4, 8, 2, 128, 80, 512, lengths),
        }
    chat = np.zeros(48, np.int64)
    chat[[3, 17, 40]] = 20 * PAGE - 5
    mixed = np.minimum(512 * (1 + rng.pareto(1.6, 64)), 7168).astype(np.int64)
    mixed += rng.integers(0, 256, 64)
    return {
        "gpt2_xl.summarize": (48, 25, 25, 64, 64, None,
                              rng.integers(800, 1000, 48)),
        "gpt2_xl.chat": (48, 25, 25, 64, 64, None, chat),
        "mellum2.full": (64, 32, 4, 128, 512, None, mixed),
        "mellum2.window": (64, 32, 4, 128, 512, 1024, mixed),
    }


def operands(slots, heads, kv_heads, head_dim, max_pages, window, lengths):
    import jax
    import jax.numpy as jnp

    from zookeeper_tpu import ops

    width = ops.kv_row_width(kv_heads, head_dim)
    span = max_pages if window is None else window // PAGE + 2
    pages = slots * span
    rng = np.random.default_rng(1)
    table = np.full((slots, max_pages), -1, np.int32)
    perm = rng.permutation(pages).reshape(slots, span)
    for s, n in enumerate(lengths):
        first = 0 if window is None else max(n - window + 1, 0) // PAGE
        live = np.arange(first, n // PAGE + 1)
        table[s, live] = perm[s, live % span]
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    pool = (pages, 1, PAGE, width)
    return (
        jax.random.normal(k1, (slots, 1, heads, head_dim), jnp.bfloat16),
        jax.random.normal(k2, pool, jnp.bfloat16),
        jax.random.normal(k3, pool, jnp.bfloat16),
        jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
    ), width


def time_call(args, kv_heads, window, variant, reps, unseen):
    """Milliseconds a call, the seconds one trace and one lowering of the
    kernel take, and the result. ``unseen`` counts the programs built so
    far: each gives the kernel a softmax scale no earlier one used (off by
    parts in a million), a new static argument, so the kernel is traced
    and lowered anew, at the block size that stands, with everything else
    jax caches left warm."""
    import jax

    from zookeeper_tpu import ops
    from zookeeper_tpu.ops import attention

    def attend(scale, q, k, v, table, lengths):
        if variant == "kernel":
            return ops.pool_paged_decode_attention(
                q, k, v, table, lengths, kv_heads=kv_heads, window=window,
                scale=scale,
            )
        # one half of a work item, through the kernel's private entry
        return attention._pool_paged_decode_call(
            q, k, v, table, lengths, None, None, scale=scale,
            interpret=jax.default_backend() != "tpu",
            kv_heads=kv_heads, window=window,
            halves=(variant.removesuffix("_only"),),
        )

    def unseen_scale():
        return args[0].shape[-1] ** -0.5 * (1 + 1e-6 * next(unseen))

    def chain(scale, q, *pools_and_tables):
        for _ in range(LAYERS):
            q = attend(scale, q, *pools_and_tables)
        return q

    fn = jax.jit(partial(chain, unseen_scale())).lower(*args).compile()
    out = fn(*args).block_until_ready()
    # One trace and one lowering of the kernel alone, as a process that
    # builds a decode program pays them.
    t0 = time.perf_counter()
    traced = jax.jit(partial(attend, unseen_scale())).trace(*args)
    t1 = time.perf_counter()
    traced.lower()
    build = (t1 - t0, time.perf_counter() - t1)
    if jax.default_backend() != "tpu":
        # a rehearsal has no time
        return None, build, np.asarray(out, np.float32)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / (reps * LAYERS))
    return best * 1e3, build, np.asarray(out, np.float32)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--block-bytes", default="")
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--only", default="")
    parser.add_argument("--label", default="")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument(
        "--halves", action="store_true",
        help="also time the copies alone and the arithmetic alone",
    )
    parser.add_argument(
        "--timeout", type=float, default=120.0,
        help="seconds one variant may take before the process is ended: a "
        "copy waited for and never started hangs the chip, not the host",
    )
    opts = parser.parse_args()

    import jax

    from zookeeper_tpu import ops
    from zookeeper_tpu.ops import blocks

    device = jax.devices()[0]
    if device.platform != "tpu" and not opts.rehearse:
        print("probe_pool_decode: no TPU; a time comes only from the chip")
        return 2
    fetches = hasattr(ops, "pool_decode_block_pages")
    variants = ["kernel"]
    sizes = [None]
    if fetches:
        if opts.halves:
            variants += ["copies_only", "arithmetic_only"]
        sizes += [int(x) for x in opts.block_bytes.split(",") if x]
    default_bytes = getattr(blocks, "_POOL_BLOCK_BYTES", None)
    unseen = itertools.count(1)
    for name, shape in shapes(opts.rehearse).items():
        if opts.only and opts.only not in name:
            continue
        slots, heads, kv_heads, head_dim, max_pages, window, lengths = shape
        args, width = operands(*shape)
        rows = lengths + 1
        if window is not None:
            rows = np.minimum(rows, window)
        least_ms = int(rows.sum()) * width * 2 * 2 / HBM_BYTES_PER_S * 1e3
        for size in sizes:
            if fetches:
                blocks._POOL_BLOCK_BYTES = size or default_bytes
            for variant in variants:
                faulthandler.dump_traceback_later(opts.timeout, exit=True)
                ms, build, out = time_call(
                    args, kv_heads, window, variant, opts.reps, unseen
                )
                faulthandler.cancel_dump_traceback_later()
                line = {
                    "probe": "pool_decode", "label": opts.label,
                    "shape": name, "variant": variant,
                    "fetches_own_pages": fetches, "ms_per_call": ms,
                    "trace_s": build[0], "lower_s": build[1],
                    "live_kv_ms_at_hbm_peak": least_ms,
                    "share_of_hbm_roofline": ms and least_ms / ms,
                    "device": {
                        "platform": device.platform,
                        "kind": device.device_kind,
                    },
                }
                if fetches:
                    line["block_bytes"] = blocks._POOL_BLOCK_BYTES
                    line["block_pages"] = ops.pool_decode_block_pages(
                        PAGE, width, 2, max_pages, window
                    )
                if variant == "kernel":
                    line["finite"] = bool(np.isfinite(out).all())
                    line["checksum"] = float(np.abs(out).sum())
                print(json.dumps(line), flush=True)
                if not opts.rehearse:
                    os.makedirs("chiprun_out", exist_ok=True)
                    with open("chiprun_out/probe_pool_decode.jsonl", "a") as f:
                        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
