"""A traced rehearsal of every serving cell, read record by record: the
leaf rule holds with the records PR 35 adds (no ``X`` record of a thread
encloses another), every dispatch span holds its one boundary event and
follows its prepare leaf, and the new readers that need no device plane
find what they read."""

import json
import os
import subprocess
import sys

import pytest

from zkbench import cells

SCRIPT = r"""
import argparse, json, os, sys
root, name, out_dir = sys.argv[1:4]
sys.path[:0] = [os.path.join(root, "benchmarks"), root]
import run as bench
from zkbench import cells, device
from tests.observability.trace_leaves import overlapping_spans

cell = cells.Cell(name, root)
args = argparse.Namespace(
    seed=2**31 + 35, seconds=2.0, trace=1, rehearse=True, with_control=False,
    keep_trace=None, sweep_rates=None,
)
ctx = bench.Context(args, cell)
ctx.out_dir = out_dir
ctx.device = device.require_chips(cell.chips, True)
ctx.compile_clock = device.CompileClock()
outcome = cell.entry_module().run(ctx)
records = outcome["layer_ctx"]["spans"]
read = bench.layer_metrics(cell, outcome["layer_ctx"], {"kind": "TPU v5 lite"})
spans = [r for r in records if r["phase"] == "X"]
dispatches = [r for r in spans if r["name"].endswith("_dispatch")]
events = [r for r in records if r["name"] == "dispatch_enqueued"]
held = sum(
    1 for s in dispatches for e in events
    if e["thread_id"] == s["thread_id"]
    and s["ts_ns"] <= e["ts_ns"] <= s["ts_ns"] + s["dur_ns"]
)
ends = [r["attrs"] for r in records if r["name"] == "sched_iteration_end"]
print(json.dumps({
    "correct": bool(outcome["correct"]),
    "overlapping": overlapping_spans(records),
    "names": sorted({r["name"] for r in spans}),
    "dispatches": len(dispatches), "enqueued": len(events), "held": held,
    "prepares": sum(1 for r in spans if r["name"] == "dispatch_prepare"),
    "timed_ends": sum(1 for a in ends if 0 <= a["cpu_ns"] <= a["wall_ns"]),
    "ends": len(ends),
    "read": {k: v["value"] for k, v in read.items()},
}))
"""

SERVING = [
    w["name"] for w in cells.load_benchmark()["workloads"]
    if w["traffic"] != "train_b512"
]


@pytest.mark.parametrize("workload", SERVING)
def test_a_traced_rehearsal_keeps_the_leaf_rule_and_feeds_the_new_readers(workload, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-c", SCRIPT, cells.ROOT, workload, str(tmp_path / "out")],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    seen = json.loads(done.stdout.strip().splitlines()[-1])
    assert seen["correct"] is True
    assert seen["overlapping"] == []
    assert {"dispatch_prepare", "decode_dispatch", "sched_deliver"} <= set(seen["names"])
    # the window cuts at most one dispatch from its prepare leaf or its event
    assert seen["dispatches"] >= 10
    assert seen["dispatches"] - 1 <= seen["held"] <= seen["enqueued"] <= seen["dispatches"] + 1
    assert abs(seen["prepares"] - seen["dispatches"]) <= 1
    assert seen["ends"] >= 5 and seen["timed_ends"] == seen["ends"]
    suffix = "chat" if workload == "gpt2_xl.chat_poisson" else "summarize"
    for name in ("decode_enqueue_host_ms", "idle_unspanned_share", "sched_stall_ms"):
        assert f"{name}.{suffix}" in seen["read"], seen["read"]
    assert 0.0 <= seen["read"][f"idle_unspanned_share.{suffix}"] <= 100.0
    assert seen["read"][f"decode_enqueue_host_ms.{suffix}"] > 0.0
