"""The readers of the program's leaf spans and events (PR 24), each on a
hand-made record list: what the loader's producer thread, the train loop's
sync points and the decode scheduler's iteration record, and what a program
that lacks them (the parent commit) gives: nothing, and no error."""

import numpy as np
import pytest

from zkbench import cells

BENCH = cells.load_benchmark()


def rec(name, ts_ms, dur_ms=None, *, step=None, rid=None, attrs=None,
        thread=1):
    """One record as ``Tracer.snapshot()`` gives it (milliseconds in)."""
    return {
        "phase": "i" if dur_ms is None else "X", "name": name,
        "ts_ns": int(ts_ms * 1e6), "dur_ns": int((dur_ms or 0) * 1e6),
        "thread_id": thread, "thread_name": f"t{thread}", "step": step,
        "slab": None, "attrs": attrs, "rid": rid,
    }


def read_metric(name, records, window_ms=(0, 1000)):
    cell_name = next(
        w for m in BENCH["per_layer"] if m["name"] == name
        for w in m["workloads"]
    )
    cell = cells.Cell(cell_name)
    spec, reader = cell.layer_metric(name)
    lo, hi = (int(t * 1e6) for t in window_ms)
    return reader.read({
        "spans": records, "window_host_ns": (lo, hi), "spec": spec,
        "cell": cell, "counters": {}, "work": {},
    })


NEW_METRICS = [
    "loader_assemble_ms", "loader_stage_ms", "host_mem_growth_mb_per_step",
    "sched_admit_p95_ms", "sched_host_self_ms.chat",
    "sched_host_self_ms.summarize", "token_gap_p95_ms",
]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_that_finds_no_record_returns_none(name):
    """What the parent commit's program gives these readers: its old
    spans and nothing of PR 24's. No value, no error."""
    old = [
        rec("data_wait", 1, 90, step=None),
        rec("dispatch", 92, 2, step=3),
        rec("decode_dispatch", 100, 120),
        rec("decode_request_enqueue", 5, rid=1),
    ]
    assert read_metric(name, []) is None
    assert read_metric(name, old) is None


def test_loader_span_readers_are_per_staged_batch_in_the_untraced_slice():
    records = []
    for i in range(4):  # four batches: 100 ms assemble, 30 stage, 5 wait
        t = 10 + 140 * i
        records += [
            rec("loader_assemble", t, 100, step=i, thread=2),
            rec("loader_stage", t + 100, 30, step=i, thread=2),
            rec("loader_put_wait", t + 130, 5, step=i, thread=2),
        ]
    # the pull that found the pass over: time, but no batch
    records.append(rec("loader_assemble", 570, 0.02, step=4, thread=2))
    # and a batch outside the slice
    records.append(rec("loader_stage", 2000, 999, step=0, thread=2))
    assert read_metric("loader_assemble_ms", records) == pytest.approx(100.005)
    assert read_metric("loader_stage_ms", records) == pytest.approx(30.0)


def test_host_memory_growth_is_the_slope_over_steps_in_megabytes():
    base = 9_000_000_000
    records = [
        rec("host_memory", 10 + 50 * i, step=100 + 4 * i,
            attrs={"in_use_bytes": base + 4 * i * 77_070_336, "rss_bytes": 5})
        for i in range(8)
    ]
    assert read_metric(
        "host_mem_growth_mb_per_step", records
    ) == pytest.approx(77.070336)
    # one sample more, outside the slice, does not move it
    records.append(rec("host_memory", 5000, step=999, attrs={"in_use_bytes": 1}))
    assert read_metric(
        "host_mem_growth_mb_per_step", records
    ) == pytest.approx(77.070336)
    # two sync points are no slope yet
    assert read_metric("host_mem_growth_mb_per_step", records[:2]) is None


def iteration(step, at, admit_ms):
    """One scheduler iteration's records: 1 ms sweep, an admission of
    ``admit_ms`` around one extend, a decode step, 1 ms bookkeeping."""
    out = [rec("sched_sweep", at, 1, step=step)]
    t = at + 1
    out.append(rec("sched_admit_plan", t, 0.5, step=step))
    if admit_ms:
        out.append(rec("prefill_warm_dispatch", t + 0.5, admit_ms, step=step))
        out.append(rec("sched_admit_commit", t + 0.5 + admit_ms, 0.5, step=step))
        t += admit_ms + 0.5
    t += 0.5
    out += [
        rec("sched_decode_plan", t, 1, step=step),
        rec("decode_dispatch", t + 1, 120, step=step),
        rec("sched_deliver", t + 121, 2, step=step),
        rec("sched_bookkeeping", t + 123, 1, step=step),
        rec("sched_iteration_end", t + 124, step=step,
            attrs={"admitted": int(bool(admit_ms)), "decoded": 3, "chunks": 0}),
    ]
    return out


def test_scheduler_readers_sum_the_leaves_of_each_iteration():
    records, at = [], 0.0
    admits = [0] * 15 + [108, 216, 0, 0, 108]
    for step, admit in enumerate(admits, start=1):
        records += iteration(step, at, admit)
        at += 130 + admit
    # an iteration the window cut: closed, but its sweep lies before it.
    # Not whole, so the p95 over iterations leaves it out; the mean counts
    # it as one iteration with the part of it that lies inside
    cut = [r for r in iteration(99, at, 500) if r["name"] != "sched_sweep"]
    # a second scheduler's iteration (another thread) is one of its own
    other = iteration(1, 3.0, 0)
    for r in other:
        r["thread_id"] = 7
    # the training loop's records carry steps too, and are no iteration
    noise = [rec("dispatch", 5, 2, step=1, thread=9)]
    per_iteration = [0.5 + (a + 0.5 if a else 0) for a in admits]
    assert read_metric(
        "sched_admit_p95_ms", records + cut + other + noise
    ) == pytest.approx(np.percentile(per_iteration + [0.5], 95))
    self_ms = [5.5 + (0.5 if a else 0) for a in admits] + [5.0, 5.5]
    for name in ("sched_host_self_ms.chat", "sched_host_self_ms.summarize"):
        assert read_metric(
            name, records + cut + other + noise
        ) == pytest.approx(np.mean(self_ms))


def test_scheduler_mean_reads_an_iteration_longer_than_the_window():
    """A closed loop's first iteration admits every slot by itself and
    outlasts the traced window: neither its sweep nor its end lies inside.
    The mean still reads its leaves (one iteration), the p95 over whole
    iterations has nothing to read."""
    inside = []
    for i in range(30):
        t = 10 + 140 * i
        inside += [
            rec("sched_admit_plan", t, 3, step=15),
            rec("prefill_dispatch", t + 3, 135, step=15),
            rec("sched_admit_commit", t + 138, 0.5, step=15),
            rec("token_delivered", t + 138.2, step=15, rid=i),
        ]
    assert read_metric(
        "sched_host_self_ms.summarize", inside
    ) == pytest.approx(30 * 3.5)
    assert read_metric("sched_admit_p95_ms", inside) is None


def test_token_gaps_are_per_request_and_by_the_delivering_thread():
    records = []
    for rid, first, gap in ((1, 10.0, 120.0), (2, 15.0, 125.0)):
        records += [
            rec("token_delivered", first + gap * j, rid=rid, step=j)
            for j in range(12)
        ]
    # interleaved requests: the gap is within one rid, never across two
    gaps = [120.0] * 11 + [125.0] * 11
    assert read_metric("token_gap_p95_ms", records) == pytest.approx(
        np.percentile(gaps, 95)
    )
    assert read_metric("token_gap_p95_ms", records[:5]) is None  # too few


def test_new_metrics_say_where_each_number_comes_from():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-7:]] == NEW_METRICS
    for name in NEW_METRICS:
        spec, _ = cells.Cell(by_name[name]["workloads"][0]).layer_metric(name)
        for key in ("layer", "unit", "moves", "source"):
            assert spec[key] == by_name[name][key], (name, key)
        assert by_name[name]["better"] == "lower"
    assert by_name["host_mem_growth_mb_per_step"]["source"] == "program_counter"
