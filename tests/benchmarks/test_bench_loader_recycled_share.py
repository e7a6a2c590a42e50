"""The reader of ``loader_recycled_share``: the share of the training
loader's batches assembled into a recycled host buffer, read from the
``recycled`` attribute of the producer's ``loader_assemble`` spans. On a
hand-made record list, on what a program without the ring gives (nothing,
and no error), and in the training cell's traced rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from zkbench import cells

BENCH = cells.load_benchmark()
NAME = "loader_recycled_share"


def rec(name, ts_ms, dur_ms=None, *, step=None, attrs=None, thread=1):
    """One record as ``Tracer.snapshot()`` gives it (milliseconds in)."""
    return {
        "phase": "i" if dur_ms is None else "X", "name": name,
        "ts_ns": int(ts_ms * 1e6), "dur_ns": int((dur_ms or 0) * 1e6),
        "thread_id": thread, "thread_name": f"t{thread}", "step": step,
        "slab": None, "attrs": attrs, "rid": None,
    }


def read_share(records, window_ms=(0, 1000)):
    (cell_name,) = next(
        m["workloads"] for m in BENCH["per_layer"] if m["name"] == NAME
    )
    cell = cells.Cell(cell_name)
    spec, reader = cell.layer_metric(NAME)
    lo, hi = (int(t * 1e6) for t in window_ms)
    return reader.read({
        "spans": records, "window_host_ns": (lo, hi), "spec": spec,
        "cell": cell, "counters": {}, "work": {},
    })


def test_the_metric_is_listed_for_the_training_cell_alone():
    (spec,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert spec["workloads"] == ["quicknet_large.train_b512"]
    assert spec["moves"] == "train_throughput"
    assert spec["layer"] == "host input"


@pytest.mark.parametrize("records", [
    [],
    [
        rec("data_wait", 1, 90),
        rec("loader_assemble", 10, 5, step=0, thread=2),
        rec("loader_stage", 15, 3, step=0, thread=2),
    ],
], ids=["no_records", "parent_spans"])
def test_a_program_without_the_ring_reads_none(records):
    """The parent's ``loader_assemble`` spans carry no ``recycled``."""
    assert read_share(records) is None


def test_recycled_share_is_over_the_batches_assembled_in_the_untraced_slice():
    """The ``recycled`` attribute of each ``loader_assemble`` span that
    made a batch; the pass's last pull carries none."""
    def batches(first, flags):
        return [
            rec("loader_assemble", first + 10 * i, 5, step=i, thread=2,
                attrs={"recycled": flag})
            for i, flag in enumerate(flags)
        ]

    records = batches(10, [1] * 8)
    records.append(rec("loader_assemble", 95, 0.02, step=8, thread=2,
                       attrs={}))
    records += batches(2000, [0] * 4)  # outside the slice
    assert read_share(records) == pytest.approx(100.0)
    records += batches(200, [0, 0])  # two fresh buffers inside it
    assert read_share(records) == pytest.approx(80.0)


def test_the_training_cells_traced_rehearsal_reads_the_share():
    """``--rehearse --trace 1`` in the training cell drives the loader with
    prefetch on, so the ring engages and the reader finds its spans."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "benchmarks", "run.py"),
         "--workload", "quicknet_large.train_b512", "--rehearse", "--seed",
         str(2**31 + 41), "--seconds", "2", "--trace", "1"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert NAME in last["layer_metrics_read"]
