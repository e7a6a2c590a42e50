"""Host-side span tracing: recording semantics, the disabled-path
zero-cost contract, ring bounding, thread attribution, and Chrome
trace-event export validity."""

import json
import threading

import pytest

from zookeeper_tpu.observability import trace


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with tracing disabled (the module
    global is process-wide)."""
    trace.disable()
    yield
    trace.disable()


def test_disabled_span_is_shared_noop_no_allocation():
    # The zero-cost contract: the SAME object comes back from every
    # disabled span() call — one flag check, no per-call allocation.
    a = trace.span("x", step=1, slab=2)
    b = trace.span("y")
    assert a is b
    with a:
        pass  # entering/exiting the noop is safe and records nothing
    assert not trace.enabled()
    assert trace.get_tracer() is None


def test_disabled_event_records_nothing():
    trace.event("whatever", step=3, attrs={"k": 1})
    assert trace.get_tracer() is None


def test_span_records_interval_with_attribution():
    tracer = trace.enable(128)
    with trace.span("data_wait", step=7, slab=2, attrs={"rows": 32}):
        pass
    (rec,) = tracer.snapshot()
    assert rec["phase"] == "X"
    assert rec["name"] == "data_wait"
    assert rec["step"] == 7
    assert rec["slab"] == 2
    assert rec["attrs"] == {"rows": 32}
    assert rec["dur_ns"] >= 0
    assert rec["thread_name"] == threading.current_thread().name
    assert rec["thread_id"] == threading.get_ident()


def test_event_records_instant():
    tracer = trace.enable(128)
    trace.event("fault_injected", step=5, attrs={"kind": "kill_at_step"})
    (rec,) = tracer.snapshot()
    assert rec["phase"] == "i"
    assert rec["name"] == "fault_injected"
    assert rec["step"] == 5


def test_ring_is_bounded_and_evicts_oldest():
    tracer = trace.enable(capacity=8)
    for i in range(20):
        trace.event("e", step=i)
    records = tracer.snapshot()
    assert len(records) == 8
    assert [r["step"] for r in records] == list(range(12, 20))


def test_reenable_keeps_existing_ring_first_enable_wins():
    tracer = trace.enable(64)
    trace.event("kept")
    assert trace.enable(64) is tracer
    # A nested enabler with a different capacity must NOT drop the
    # live ring (the outer session's records and tracer reference
    # survive); capacity changes require an explicit disable().
    assert trace.enable(32) is tracer
    assert len(tracer) == 1
    trace.disable()
    fresh = trace.enable(32)
    assert fresh is not tracer and fresh.capacity == 32


def test_drain_clears_snapshotted_records():
    tracer = trace.enable(64)
    trace.event("a")
    trace.event("b")
    drained = tracer.drain()
    assert [r["name"] for r in drained] == ["a", "b"]
    assert len(tracer) == 0


def test_concurrent_recording_is_lossless_under_capacity():
    tracer = trace.enable(capacity=100_000)
    n_threads, per_thread = 8, 500

    def record(tid):
        for i in range(per_thread):
            with trace.span("work", step=i, attrs=None):
                pass
            trace.event("mark", step=i)

    threads = [
        threading.Thread(target=record, args=(t,), name=f"rec-{t}")
        for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tracer) == n_threads * per_thread * 2


def test_chrome_export_is_valid_trace_event_json(tmp_path):
    trace.enable(256)
    with trace.span("dispatch", step=3, slab=1):
        with trace.span("inner"):
            pass
    trace.event("fault_injected", attrs={"kind": "fail_save_io"})

    def other():
        with trace.span("ckpt_write", step=3):
            pass

    t = threading.Thread(target=other, name="zk-async-ckpt")
    t.start()
    t.join()

    path = tmp_path / "trace.json"
    n = trace.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    assert n == len(doc["traceEvents"])
    by_phase = {}
    for e in doc["traceEvents"]:
        by_phase.setdefault(e["ph"], []).append(e)
        # The trace-event contract every viewer relies on.
        assert isinstance(e["name"], str)
        assert isinstance(e["pid"], int)
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # Complete spans, instants, and per-thread name metadata all present.
    assert {e["name"] for e in by_phase["X"]} == {
        "dispatch", "inner", "ckpt_write",
    }
    assert by_phase["i"][0]["args"]["kind"] == "fail_save_io"
    thread_names = {e["args"]["name"] for e in by_phase["M"]}
    assert "zk-async-ckpt" in thread_names
    # step/slab attribution lands in args.
    dispatch = next(e for e in by_phase["X"] if e["name"] == "dispatch")
    assert dispatch["args"] == {"step": 3, "slab": 1}


def test_span_is_exception_safe():
    tracer = trace.enable(64)
    with pytest.raises(ValueError):
        with trace.span("failing"):
            raise ValueError("boom")
    (rec,) = tracer.snapshot()
    assert rec["name"] == "failing"  # recorded despite the raise


# -- PR 24: the thread-local current step, the profiler's clock -----------


def test_current_step_is_taken_by_records_without_a_step_of_their_own():
    tracer = trace.enable(128)
    trace.set_current_step(41)
    with trace.span("sched_sweep"):
        pass
    trace.event("token_delivered", rid=5)
    with trace.span("dispatch", step=7):  # an explicit step wins
        pass
    trace.event("host_memory", step=8)
    trace.set_current_step(None)
    with trace.span("after"):
        pass
    steps = [(r["name"], r["step"]) for r in tracer.snapshot()]
    assert steps == [
        ("sched_sweep", 41), ("token_delivered", 41), ("dispatch", 7),
        ("host_memory", 8), ("after", None),
    ]


def test_current_step_belongs_to_the_thread_that_set_it():
    tracer = trace.enable(128)
    trace.set_current_step(3)
    seen = []

    def other():
        with trace.span("elsewhere"):
            pass
        trace.set_current_step(9)
        trace.event("elsewhere_event")
        seen.append(True)

    t = threading.Thread(target=other, name="zk-test-other")
    t.start()
    t.join()
    with trace.span("here"):
        pass
    trace.set_current_step(None)
    by_name = {r["name"]: r["step"] for r in tracer.snapshot()}
    assert seen and by_name == {
        "elsewhere": None, "elsewhere_event": 9, "here": 3,
    }


def test_disabled_calls_allocate_nothing_and_store_no_step():
    """The cost contract with tracing off, for every call a hot loop now
    makes: the shared no-op comes back, no record is made, no object is
    allocated per call, and the current step is not even stored."""
    import tracemalloc

    assert trace.span("sched_sweep") is trace.span("loader_stage", step=1)
    trace.set_current_step(5)  # off: one global read, no store

    def hot_loop(n):
        for i in range(n):
            with trace.span("sched_admit_plan"):
                pass
            trace.event("token_delivered", rid=1)
            trace.set_current_step(1)
            trace.enabled()

    hot_loop(10)  # warm every code path first
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        hot_loop(2000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    here = [
        s for s in after.compare_to(before, "filename")
        if s.traceback[0].filename.endswith("observability/trace.py")
    ]
    assert sum(s.size_diff for s in here) == 0
    assert trace.get_tracer() is None
    tracer = trace.enable(16)
    with trace.span("first_after_enable"):
        pass
    assert tracer.snapshot()[0]["step"] is None  # the 5 was never stored


def test_per_token_events_are_exported_and_stay_out_of_the_flow_chain():
    trace.enable(64)
    trace.event("decode_request_enqueue", rid=11)
    for _ in range(4):
        trace.event("token_delivered", rid=11)
    trace.event("decode_stream_finish", rid=11)
    doc = trace.to_chrome_trace()
    tokens = [e for e in doc["traceEvents"] if e["name"] == "token_delivered"]
    assert len(tokens) == 4 and all(e["args"]["rid"] == 11 for e in tokens)
    flow = [e["ph"] for e in doc["traceEvents"] if e.get("cat") == "rid"]
    assert sorted(flow) == ["f", "s"]


def test_spans_land_in_the_xplane_host_plane_on_the_profilers_clock(tmp_path):
    """While a ``jax.profiler`` session is open the program's spans are
    written into the trace's host plane next to the device ops, on the
    profiler's clock. The proof that it is ONE clock: a mark taken the way
    the benchmark takes it gives the offset between the profiler's clock
    and ``perf_counter_ns``, and the ring's record plus that offset lands
    on the plane's event to within 0.2 ms."""
    import glob
    import time

    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    tracer = trace.enable(1024)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test_mark"):
            mark_host_ns = time.perf_counter_ns()
        time.sleep(0.002)
        for i in range(3):
            with trace.span("pr24_clock_probe", step=i):
                time.sleep(0.003)
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    marks, probes = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "test_mark":
                    marks.append(ev.start_ns)
                elif ev.name == "pr24_clock_probe":
                    probes.append((ev.start_ns, ev.duration_ns))
    assert len(marks) == 1 and len(probes) == 3
    offset = marks[0] - mark_host_ns
    ring = [r for r in tracer.snapshot() if r["name"] == "pr24_clock_probe"]
    assert len(ring) == 3
    for (start, dur), rec in zip(sorted(probes), ring):
        assert abs(rec["ts_ns"] + offset - start) < 200_000
        assert dur >= rec["dur_ns"]  # the ring's interval lies inside


def test_a_tracer_without_jax_records_to_the_ring_alone(monkeypatch):
    monkeypatch.setattr(trace, "_ANNOTATION", None)
    monkeypatch.setattr(trace, "_resolve_annotation", lambda: None)
    tracer = trace.enable(8)
    with trace.span("no_jax_here"):
        pass
    assert [r["name"] for r in tracer.snapshot()] == ["no_jax_here"]
