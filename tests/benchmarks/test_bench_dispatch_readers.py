"""The three readers PR 35 adds, each on a hand-made record list and device
trace: the parts of a dispatch span add up to it whatever the clock offset's
error, idle time is shared out by overlap, a stall is what an iteration's
wall time holds beyond its CPU time and its readback waits, and a trace
without the new records (the parent commit's) gives nothing and no error."""

import pytest

from zkbench import cells, tracereduce

BENCH = cells.load_benchmark()
CHAT = "gpt2_xl.chat_poisson"
NEW = {
    "decode_enqueue_host_ms", "decode_launch_lag_ms", "decode_readback_lag_ms",
    "idle_no_work_share", "idle_unspanned_share", "sched_stall_ms",
}
NAMES = sorted(
    m["name"] for m in BENCH["per_layer"] if m["name"].rsplit(".", 1)[0] in NEW
)


def rec(name, ts_ms, dur_ms=None, *, step=None, attrs=None, thread=1):
    return {
        "phase": "i" if dur_ms is None else "X", "name": name,
        "ts_ns": int(ts_ms * 1e6), "dur_ns": int((dur_ms or 0) * 1e6),
        "thread_id": thread, "thread_name": f"t{thread}", "step": step,
        "slab": None, "attrs": attrs, "rid": None,
    }


def device_trace(modules, offset_ms=0.0, ops=None):
    """A one-chip trace of ``(name, start_ms, dur_ms)`` programs whose
    ops fill them; the profiler's clock runs ``offset_ms`` ahead of the
    host's."""
    rows = [[n, s * 1e6, d * 1e6, {}] for n, s, d in modules]
    extract = {
        "devices": {"/device:TPU:0": {"ops": ops or rows, "modules": rows}},
        "marks": [["window_start", offset_ms * 1e6], ["window_end", 1e9 + offset_ms * 1e6]],
        "planes": [],
    }
    return tracereduce.DeviceTrace(
        extract, chips=1, mark_host_ns={"window_start": 0, "window_end": int(1e9)}
    )


def read_metric(name, records=(), trace=None):
    cell = cells.Cell(next(m for m in BENCH["per_layer"] if m["name"] == name)["workloads"][0])
    spec, reader = cell.layer_metric(name)
    return reader.read({
        "spans": list(records), "window_host_ns": (0, int(1e9)), "spec": spec,
        "cell": cell, "counters": {}, "work": {}, "trace": trace, "peaks": {},
    })


def iteration(step, at, *, enqueue=1.0, span=5.0, wall=None, cpu=None):
    """One scheduler iteration from ``at`` ms: a plan leaf, the engine's
    prepare leaf, a decode dispatch with its boundary event, delivery."""
    out = [
        rec("sched_sweep", at, 0.05, step=step),
        rec("sched_decode_plan", at + 0.1, 0.3, step=step),
        rec("dispatch_prepare", at + 0.5, 0.4, step=step, attrs={"program": "decode_step"}),
        rec("dispatch_enqueued", at + 1 + enqueue, step=step, attrs={"program": "decode_step"}),
        rec("decode_dispatch", at + 1, span, step=step, attrs={"slots": 48}),
        rec("sched_deliver", at + 1 + span + 0.05, 0.4, step=step),
    ]
    attrs = {"admitted": 0, "decoded": 1, "chunks": 0}
    if wall is not None:
        attrs.update(wall_ns=int(wall * 1e6), cpu_ns=int(cpu * 1e6))
    out.append(rec("sched_iteration_end", at + 1 + span + 0.5, step=step, attrs=attrs))
    return out


# the parent commit's records: leaves, one dispatch span, a closing event
# without times; no boundary event, no prepare leaf, no idle wait
OLD = [
    rec("sched_sweep", 1, 0.1, step=1),
    rec("decode_dispatch", 2, 5, step=1, attrs={"slots": 48}),
    rec("sched_iteration_end", 7.5, step=1, attrs={"admitted": 0, "decoded": 1, "chunks": 0}),
]
MODULES = [("jit_decode_fn(7)", 3.2, 3.0), ("jit_prefill_fn(3)", 20, 4)]


def test_the_benchmark_lists_the_eleven_new_metrics_where_the_issue_put_them():
    assert len(NAMES) == 11
    closed = [w["name"] for w in BENCH["workloads"] if w["traffic"].endswith("_closed")]
    for m in BENCH["per_layer"]:
        if m["name"] not in NAMES:
            continue
        assert m["better"] == "lower"
        if m["name"].endswith(".chat"):
            assert (m["workloads"], m["moves"]) == ([CHAT], "itl_p95_ms")
        else:
            assert (m["workloads"], m["moves"]) == (closed, "serve_tokens_per_s")


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_that_finds_none_of_its_records_returns_none(name):
    """No records at all, the parent's records without a device trace, and
    the parent's records with one: only the unspanned share, which reads
    whatever spans the scheduler's thread has, finds something in the last."""
    assert read_metric(name, []) is None
    assert read_metric(name, OLD) is None
    found = read_metric(name, OLD, device_trace(MODULES))
    if name.startswith(("idle_unspanned_share", "decode_launch_lag_ms", "decode_readback_lag_ms")):
        assert found is not None  # spans and modules the parent has too
    else:
        assert found is None


def test_a_trace_without_the_program_gives_no_lag():
    records = iteration(1, 0)
    trace = device_trace([("jit_prefill_fn(3)", 20, 4)])
    assert read_metric("decode_launch_lag_ms.chat", records, trace) is None
    assert read_metric("decode_readback_lag_ms.chat", records, trace) is None
    assert read_metric("decode_enqueue_host_ms.chat", records, trace) == pytest.approx(1.0)


@pytest.mark.parametrize("suffix", ["chat", "summarize"])
@pytest.mark.parametrize("error_ms", [0.0, 0.5, -0.5])
def test_the_three_parts_add_up_to_the_span_whatever_the_offset(suffix, error_ms):
    """Two dispatches of 5 and 6 ms around programs of 3 ms that start 1.2
    and 1.6 ms in: an offset wrong by half a millisecond moves that much
    from one lag to the other and leaves their sum."""
    records = iteration(1, 0, enqueue=1.0, span=5.0) + iteration(2, 10, enqueue=1.4, span=6.0)
    offset = 250.0
    modules = [
        ("jit_decode_fn(7)", offset + 1 + 1.2, 3.0),
        ("jit_extend_fn(9)", offset + 7, 1.0),
        ("jit_decode_fn(7)", offset + 11 + 1.6, 3.0),
    ]
    trace = device_trace(modules, offset_ms=offset + error_ms)
    launch = read_metric(f"decode_launch_lag_ms.{suffix}", records, trace)
    readback = read_metric(f"decode_readback_lag_ms.{suffix}", records, trace)
    enqueue = read_metric(f"decode_enqueue_host_ms.{suffix}", records, trace)
    assert launch == pytest.approx(1.4 - error_ms)
    assert readback == pytest.approx(1.1 + error_ms)
    assert enqueue == pytest.approx(1.2)
    device = read_metric("decode_step_device_ms", records, trace)
    span = read_metric("decode_dispatch_host_ms", records, trace)
    assert launch + device + readback == pytest.approx(span) == pytest.approx(5.5)


def test_a_span_without_its_program_is_left_out_of_the_lags():
    """The window's edge: the last dispatch's program is not in the trace;
    the lags are the first's alone, the enqueue is both's."""
    records = iteration(1, 0, enqueue=1.0) + iteration(2, 10, enqueue=2.0)
    trace = device_trace([("jit_decode_fn(7)", 2.2, 3.0)])
    assert read_metric("decode_launch_lag_ms.chat", records, trace) == pytest.approx(1.2)
    assert read_metric("decode_readback_lag_ms.chat", records, trace) == pytest.approx(0.8)
    assert read_metric("decode_enqueue_host_ms.chat", records, trace) == pytest.approx(1.5)


def test_idle_time_is_shared_out_by_overlap_not_by_majority():
    """A window of 1 s whose device is busy for 100 ms in all: of 900 idle
    ms the worker waited through 50 + 25 (a gap half under a wait counts
    half), and the scheduler's thread was in some span for 60 more."""
    busy = [("jit_decode_fn(7)", 100, 40), ("jit_decode_fn(7)", 200, 60)]
    records = [
        rec("worker_idle_wait", 0, 50),                  # all idle
        rec("worker_idle_wait", 75, 50),                 # 25 idle, 25 under the program
        rec("sched_decode_plan", 140, 30, step=3),       # idle
        rec("decode_dispatch", 170, 100, step=3),        # 30 idle before the program, 10 after
        rec("sched_iteration_end", 270, step=3),
        rec("data_wait", 400, 300, thread=2),            # another thread's: not the scheduler's
    ]
    trace = device_trace(busy)
    assert read_metric("idle_no_work_share.chat", records, trace) == pytest.approx(100 * 75 / 900)
    for suffix in ("chat", "summarize"):
        assert read_metric(f"idle_unspanned_share.{suffix}", records, trace) == pytest.approx(
            100 * (900 - 75 - 70) / 900
        )
    # the majority rule gives the second wait's gap whole to one label
    labels = dict(trace.idle_gaps([(r["name"], r["ts_ns"], r["dur_ns"]) for r in records if r["phase"] == "X"]))
    assert labels["worker_idle_wait"] == pytest.approx(0.1)


def test_idle_shares_follow_the_clock_offset():
    busy = [("jit_decode_fn(7)", 1000 + 100, 100)]
    records = [rec("worker_idle_wait", 0, 50), rec("sched_iteration_end", 60, step=1)]
    trace = device_trace(busy, offset_ms=1000)
    assert read_metric("idle_no_work_share.chat", records, trace) == pytest.approx(100 * 50 / 900)


@pytest.mark.parametrize("suffix", ["chat", "summarize"])
def test_a_stall_is_wall_less_cpu_less_the_readback_waits(suffix):
    """Iteration 1 of 7 ms on the wall ran for 2.5 and waited 4 for its
    readback: 0.5 ms stalled. Iteration 2 waited 4.6 of 8 and ran 2.4: 1.0.
    An iteration that began before the window and one whose closing event
    carries no times are left out."""
    records = (
        iteration(0, -3, wall=7.0, cpu=2.5)
        + iteration(1, 10, enqueue=1.0, span=5.0, wall=7.0, cpu=2.5)
        + iteration(2, 20, enqueue=1.4, span=6.0, wall=8.0, cpu=2.4)
        + iteration(3, 40)
    )
    records = [r for r in records if r["ts_ns"] >= 0]
    assert read_metric(f"sched_stall_ms.{suffix}", records) == pytest.approx(0.75)
    # a prefill of the same iteration waits too
    more = records + [
        rec("dispatch_enqueued", 20.43, step=2, attrs={"program": "prefill"}),
        rec("prefill_dispatch", 20.41, 0.08, step=2),
    ]
    assert read_metric(f"sched_stall_ms.{suffix}", more) == pytest.approx(0.72)
