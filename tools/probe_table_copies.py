"""Does a warmed serving program re-lay the token table out on every call?
An engine bound on the chip at a benchmark configuration's widths (its
``program`` settings, cut to ``--layers`` layers: the table's layout does
not depend on the depth), every program warmed as the cell warms it, and
for each one the number of instructions of its optimised HLO that copy or
transpose an array as large as the token table, as bound
(``vocab x d_model``) or with rows of whole 128-lane tiles. Beside it the
page pool's count (``DecodeEngine.pool_sized_copies``), what the engine
holds of the tree, and the wall time a token of one stream decoding alone
(host dispatch included: a witness, not a metric).

    chiprun --timeout 900 -- python tools/probe_table_copies.py
    PYTHONPATH=<a parent checkout> python tools/probe_table_copies.py

The count is taken here from the compiled programs' text, so a tree from
before ``DecodeEngine.table_sized_copies`` reads the same way; where the
engine has that method the two are compared. Exits 1 where ``--expect``
is given and a program's count differs from it. Every line names the
device it ran on; no time comes from a CPU (``--rehearse``: a small
width, for the control flow only).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# After whatever PYTHONPATH names, so that an older tree given there wins.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(ROOT)


def program_settings(config, layers, rehearse):
    with open(os.path.join(ROOT, "benchmarks", "configs", config + ".json")) as f:
        described = json.load(f)
    settings = dict(described["program"])
    if rehearse:
        settings.update(described["rehearsal"]["program"])
        settings["model.compute_dtype"] = "bfloat16"
    settings["model.num_layers"] = layers
    return {k: tuple(v) if isinstance(v, list) else v for k, v in settings.items()}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="gpt2_xl_24l")
    parser.add_argument("--layers", type=int, default=1)
    parser.add_argument("--tokens", type=int, default=256)
    parser.add_argument("--expect", type=int, default=None)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    import jax

    from zookeeper_tpu import configure
    from zookeeper_tpu.observability.hlo import count_copies_of_size
    from zookeeper_tpu.ops import kv_row_width
    from zookeeper_tpu.serving import LMServingConfig

    device = jax.devices()[0]
    settings = program_settings(args.config, args.layers, args.rehearse)
    vocab, width = settings["vocab_size"], settings["model.d_model"]
    sizes = {vocab * width, vocab * kv_row_width(1, width)}

    service = LMServingConfig()
    configure(service, {**settings, "requests": 0, "verbose": False})
    t0 = time.perf_counter()
    engine, scheduler = service.build_service()
    setup_s = time.perf_counter() - t0
    try:
        copies = {
            "/".join(str(part) for part in key[:-1]): count_copies_of_size(
                compiled.as_text(), sizes
            )
            for key, compiled in engine._compiled_cache.items()
        }
        own = getattr(engine, "table_sized_copies", None)
        held = {
            name: [list(np.shape(leaf)), str(leaf.dtype)]
            for name, leaf in engine._variables["params"].items()
            if not name.startswith("block") and hasattr(leaf, "dtype")
        }
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, vocab, size=24).astype(np.int32)
        scheduler.submit(prompt, max_new_tokens=8).result()  # warm path
        t0 = time.perf_counter()
        stream = scheduler.submit(prompt, max_new_tokens=args.tokens)
        served = len(stream.result())
        token_ms = (time.perf_counter() - t0) / served * 1e3
        report = {
            "device": f"{device.platform}:{device.device_kind}",
            "config": args.config,
            "layers": args.layers,
            "table_elements": sorted(sizes),
            "table_sized_copies": copies,
            "engine_agrees": None if own is None else own() == copies,
            "pool_sized_copies": engine.pool_sized_copies(),
            "held": held,
            "bytes_held": sum(
                int(leaf.nbytes) for leaf in jax.tree.leaves(engine._variables)
            ),
            "flavor": engine.decode_attention_flavor,
            "setup_s": round(setup_s, 2),
            "wall_ms_a_token_one_stream": round(token_ms, 4),
        }
    finally:
        service._teardown_service(suppress=True)
    print("probe_table_copies: " + json.dumps(report), flush=True)
    bad = report["engine_agrees"] is False or (
        args.expect is not None
        and any(count != args.expect for count in copies.values())
    )
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
