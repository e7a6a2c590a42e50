"""What PR 35 records around a dispatch and around the worker's wait
(docs/DESIGN.md §13): every ``*_dispatch`` span holds exactly one
``dispatch_enqueued`` event, its inner boundary; a ``dispatch_prepare``
leaf ends where the dispatch span starts; ``worker_idle_wait`` spans the
worker's wait for work, outside any iteration; ``sched_iteration_end``
carries the iteration's wall and thread CPU time; and with the tracer
off a dispatch records nothing and reads no clock but the two it had.

Since PR 36 a decode step is launched unread: its ``decode_dispatch``
span holds its own boundary event and then waits for the step BEFORE it,
if nobody has read it (one span = launch one step, wait for the one
before); a step nobody has waited for is read in a
``decode_readback`` leaf; and ``sched_iteration_end`` also says what the
pipeline did (``in_flight``, ``dropped``)."""

import time

import numpy as np
import pytest

from zookeeper_tpu.observability import trace
from zookeeper_tpu.serving.decode import engine as engine_mod

from tests.observability.trace_leaves import iterations, overlapping_spans
from tests.serving.test_decode_engine import build_lm, make_engine, make_scheduler

pytestmark = pytest.mark.serving

SLOTS = 3
#: dispatch kind -> (span, the ``program`` its records carry)
KINDS = {
    "prefill": ("prefill_dispatch", "prefill"),
    "prefill_warm": ("prefill_warm_dispatch", "prefill_extend"),
    "prefill_chunk": ("prefill_chunk_dispatch", "prefill_extend"),
    "decode": ("decode_dispatch", "decode_step"),
    "verify": ("verify_dispatch", "verify_step/w2"),
}


@pytest.fixture(scope="module")
def engine():
    module, params, state, _ = build_lm()
    eng = make_engine(
        module, params, state, slots=SLOTS, seq_buckets=(8, 16),
        prefix_cache=True, page_size=4,
    )
    eng.warmup()
    eng.warmup_verify(2)
    return eng


@pytest.fixture
def tracer():
    prior = trace.get_tracer()
    trace.install(trace.Tracer(4096))
    yield trace.get_tracer()
    trace.install(prior)


def emptied(eng):
    """No slot holds pages and no decode step is left unread."""
    if eng._last_step is not None:
        eng._last_step.result()
    for slot in range(SLOTS):
        eng.release_slot(slot)
    return eng


def staged(eng, kind):
    """The pool made ready for one dispatch of ``kind``; returns the
    call that makes it, through the engine's own method."""
    emptied(eng)
    prompt = np.arange(1, 10, dtype=np.int32)
    assert eng.admit_slot(0, prompt) is not None
    if kind == "prefill":
        return lambda: eng.prefill([prompt], [0])
    if kind == "prefill_warm":
        eng.prefill([prompt], [0])
        eng.insert_prefix(0, prompt)
        longer = np.concatenate([prompt[:8], np.arange(20, 24, dtype=np.int32)])
        shared = eng.admit_slot(1, longer)["shared_tokens"]
        assert shared >= 4
        return lambda: eng.prefill_warm([longer], [1], [shared])
    if kind == "prefill_chunk":
        return lambda: eng.prefill_chunk([prompt[:4]], [0], [0])
    tokens = np.zeros((SLOTS,), np.int32)
    lengths = np.zeros((SLOTS,), np.int32)
    lengths[0] = prompt.shape[0]
    assert eng.ensure_rows(0, prompt.shape[0] + 2)
    if kind == "decode":  # launched, and read at once
        return lambda: np.asarray(eng.decode(tokens, lengths))
    return lambda: eng.verify(np.zeros((SLOTS, 2), np.int32), lengths)


def inside(event, span):
    return (
        event["thread_id"] == span["thread_id"]
        and span["ts_ns"] <= event["ts_ns"] <= span["ts_ns"] + span["dur_ns"]
    )


def check_dispatches(records, expect=None):
    """Every ``*_dispatch`` span of ``records`` holds exactly one
    ``dispatch_enqueued`` of its thread, and follows a
    ``dispatch_prepare`` of the same program with nothing between them.
    Returns the dispatch spans."""
    dispatches = [
        r for r in records
        if r["phase"] == "X" and r["name"].endswith("_dispatch")
    ]
    enqueued = [r for r in records if r["name"] == "dispatch_enqueued"]
    assert len(enqueued) == len(dispatches)
    for span in dispatches:
        (event,) = [e for e in enqueued if inside(e, span)]
        assert event["phase"] == "i"
        assert set(event["attrs"]) == {"program"}
        if expect is not None:
            assert event["attrs"]["program"] == expect
        at = records.index(span)
        # the ring holds a span where it ENDS: the prepare leaf closed
        # just before the dispatch span's own records
        before = [
            r for r in records[:at]
            if r["thread_id"] == span["thread_id"] and r["phase"] == "X"
        ]
        prepare = before[-1]
        assert prepare["name"] == "dispatch_prepare"
        assert prepare["step"] == span["step"]
        assert set(prepare["attrs"]) == {"program"}
        assert prepare["attrs"]["program"] == event["attrs"]["program"].split("/")[0]
        gap = span["ts_ns"] - (prepare["ts_ns"] + prepare["dur_ns"])
        assert 0 <= gap < 2_000_000  # the call into the one body
    return dispatches


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_dispatch_span_holds_one_boundary_event(engine, tracer, kind):
    dispatch = staged(engine, kind)
    tracer.clear()
    dispatch()
    records = tracer.snapshot()
    span_name, program = KINDS[kind]
    (span,) = check_dispatches(records, expect=program)
    assert span["name"] == span_name
    assert overlapping_spans(records) == []


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_with_the_tracer_off_a_dispatch_records_and_times_nothing_new(
    engine, kind, monkeypatch
):
    """No record, and no clock read beyond ``_observe_decode``'s two
    ``perf_counter`` calls, which the decode and verify paths had."""
    assert not trace.enabled()
    calls = []

    class Clock:
        def __getattr__(self, name):
            real = getattr(time, name)
            if not callable(real):
                return real

            def counted(*args):
                calls.append(name)
                return real(*args)

            return counted

    dispatch = staged(engine, kind)
    monkeypatch.setattr(engine_mod, "time", Clock())
    dispatch()
    monkeypatch.undo()
    expected = ["perf_counter"] * 2 if kind in ("decode", "verify") else []
    assert calls == expected
    assert trace.get_tracer() is None


def test_a_decode_span_launches_one_step_and_waits_for_the_one_before(engine, tracer):
    """A step comes back unread; the next step's span reads it after its
    own boundary event and leaves its own step unread; read on its own
    (converted to the host array it becomes), a step waits in the
    ``decode_readback`` leaf, once."""
    staged(engine, "decode")
    tokens = np.zeros((SLOTS,), np.int32)
    lengths = np.zeros((SLOTS,), np.int32)
    lengths[0] = 9
    tracer.clear()
    first = engine.decode(tokens, lengths)
    assert first.tokens is None and first.seconds is None and not first.behind
    lengths[0] += 1
    # the second step's input is the first one's output, kept on the device
    second = engine.decode(np.full((SLOTS,), -1, np.int32), lengths)
    assert first.tokens is not None and first.seconds > 0
    assert second.tokens is None and second.behind
    assert first.result() is first.tokens  # no wait left, no leaf
    records = tracer.snapshot()
    assert not [r for r in records if r["name"] == "decode_readback"]
    spans = check_dispatches(records, expect="decode_step")
    assert [r["name"] for r in spans] == ["decode_dispatch"] * 2
    got = np.asarray(second)
    assert got.shape == (SLOTS,) and got.dtype == np.int32 and len(second) == SLOTS
    assert second.seconds > 0  # from the first step's read to its own
    second.result()
    records = tracer.snapshot()
    (leaf,) = [r for r in records if r["name"] == "decode_readback"]
    assert leaf["phase"] == "X" and leaf["ts_ns"] >= spans[-1]["ts_ns"] + spans[-1]["dur_ns"]
    assert overlapping_spans(records) == []
    # the same two steps with the host supplying the token: same answer
    lengths[0] = 9
    again = engine.decode(tokens, lengths)
    np.testing.assert_array_equal(again, first.tokens)
    lengths[0] += 1
    assert engine.decode(again, lengths)[0] == got[0]


def test_a_model_with_experts_reads_its_load_back_inside_and_reports_it_after(tracer):
    """The load rides its dispatch's one readback, and its events follow
    the span that READ it: a prefill's its own span, a decode step's the
    next step's span (which waits for it) or, for the last step, the
    ``decode_readback`` leaf. None lies inside a span."""
    from tests.serving import test_solar_open2_serving as solar

    module, params = solar.tiny.build()
    eng = solar.make_engine(module, params)
    sched = solar.make_scheduler(eng)
    sched.submit(np.arange(9, dtype=np.int32), max_new_tokens=4).result(timeout=600)
    records = tracer.snapshot()
    dispatches = check_dispatches(records)
    assert [r["name"] for r in dispatches] == ["prefill_dispatch"] + ["decode_dispatch"] * 3
    (leaf,) = [r for r in records if r["name"] == "decode_readback"]
    loads = [r for r in records if r["name"] == "moe_tokens_per_expert"]
    held = [r for r in records if r["name"] == "moe_held_choices"]
    assert len(loads) == len(held) == len(dispatches) == 4
    # who read each dispatch: the prefill itself; a decode step's
    # successor; the leaf for the step nothing was launched behind
    readers = [dispatches[0], dispatches[2], dispatches[3], leaf]
    for span, reader, load in zip(dispatches, readers, loads):
        assert load["ts_ns"] >= reader["ts_ns"] + reader["dur_ns"]
        assert load["step"] == reader["step"]
        (event,) = [
            r for r in records
            if r["name"] == "dispatch_enqueued" and inside(r, span)
        ]
        assert load["attrs"]["program"] == event["attrs"]["program"]
    for load in loads + held:
        assert not any(
            inside(load, r) for r in records
            if r["phase"] == "X" and r["name"] != "sched_deliver"
        )
    assert overlapping_spans(records) == []


def test_the_load_is_dropped_on_the_device_while_tracing_is_off():
    """Untraced, a model with experts still returns its tokens (the
    load's half of the output is never read back)."""
    from tests.serving import test_solar_open2_serving as solar

    assert not trace.enabled()
    module, params = solar.tiny.build()
    eng = solar.make_engine(module, params)
    prompt = np.arange(9, dtype=np.int32)
    assert eng.admit_slot(0, prompt) is not None
    first = eng.prefill([prompt], [0])
    assert first.shape == (1,) and first.dtype == np.int32


def test_the_worker_waits_in_a_leaf_of_its_own_and_only_without_work(engine, tracer):
    sched = make_scheduler(emptied(engine), synchronous=False)
    try:
        stream = sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
        stream.result(timeout=120)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            waits = [
                r for r in tracer.snapshot() if r["name"] == "worker_idle_wait"
            ]
            if len(waits) >= 2:
                break
            time.sleep(0.02)
    finally:
        sched.close()
    records = tracer.snapshot()
    waits = [r for r in records if r["name"] == "worker_idle_wait"]
    assert len(waits) >= 2
    ends = [r for r in records if r["name"] == "sched_iteration_end"]
    assert len(ends) >= 4
    thread = ends[0]["thread_id"]
    for wait in waits:
        assert wait["phase"] == "X" and wait["step"] is None
        assert wait["thread_id"] == thread
        assert wait["dur_ns"] < 1_000_000_000  # one wait of 50 ms a span
    # back-to-back iterations: nothing waits between the first
    # iteration's start and the last one's end
    first_start = min(r["ts_ns"] for r in records if r["step"] is not None)
    assert all(
        w["ts_ns"] + w["dur_ns"] <= first_start or w["ts_ns"] >= ends[-1]["ts_ns"]
        for w in waits
    )
    assert overlapping_spans(records) == []


@pytest.mark.parametrize("synchronous", [True, False], ids=["sync", "worker"])
def test_an_iteration_ends_with_its_wall_and_cpu_time(engine, tracer, synchronous):
    sched = make_scheduler(emptied(engine), synchronous=synchronous)
    try:
        sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=5).result(timeout=120)
    finally:
        sched.close()
    records = tracer.snapshot()
    groups = iterations(records)
    assert len(groups) >= 3
    for recs in groups.values():
        (end,) = [r for r in recs if r["name"] == "sched_iteration_end"]
        attrs = end["attrs"]
        assert set(attrs) == {
            "admitted", "decoded", "chunks", "wall_ns", "cpu_ns",
            "in_flight", "dropped",
        }
        assert attrs["in_flight"] in (0, 1) and attrs["dropped"] == 0
        assert 0 <= attrs["cpu_ns"] <= attrs["wall_ns"]
        # the wall time is taken around the leaves: from before the
        # first to after the last
        first = min(r["ts_ns"] for r in recs)
        last = max(r["ts_ns"] + r["dur_ns"] for r in recs if r["phase"] == "X")
        assert attrs["wall_ns"] >= last - first
        waits = sum(
            span["ts_ns"] + span["dur_ns"] - event["ts_ns"]
            for span in recs if span["name"].endswith("_dispatch")
            for event in recs
            if event["name"] == "dispatch_enqueued" and inside(event, span)
        )
        assert waits <= attrs["wall_ns"]
    check_dispatches(records)
    # one stream of five tokens: four steps, all but the first launched
    # with the step before unread, the last read in a leaf of its own
    ends = [r["attrs"] for r in records if r["name"] == "sched_iteration_end"]
    assert sum(a["in_flight"] for a in ends) == 3
    assert len([r for r in records if r["name"] == "decode_readback"]) == 1


def test_prefix_work_is_one_event_an_iteration_that_moved_it(engine, tracer):
    """``kv_prefix_work`` (docs/DESIGN.md §20): one event an iteration in
    which the prefix cache evicted, examined or keyed anything, and
    together they add up to what its counters moved."""
    sched = make_scheduler(emptied(engine), synchronous=True)
    cache = engine.page_pool.prefix
    before = (cache.evicted_pages, cache.evict_nodes_examined, cache.keyed_tokens)
    prompt = np.arange(1, 10, dtype=np.int32)
    try:
        sched.submit(prompt, max_new_tokens=3).result(timeout=120)
        warm = np.concatenate([prompt, [11, 12]]).astype(np.int32)
        sched.submit(warm, max_new_tokens=3).result(timeout=120)
    finally:
        sched.close()
    records = tracer.snapshot()
    events = [r for r in records if r["name"] == "kv_prefix_work"]
    assert events
    steps = [e["step"] for e in events]
    assert len(set(steps)) == len(steps) and None not in steps
    total = {"pages_evicted": 0, "nodes_examined": 0, "tokens_keyed": 0}
    for e in events:
        assert e["phase"] == "i" and set(e["attrs"]) == set(total)
        assert any(e["attrs"].values())
        for key in total:
            total[key] += e["attrs"][key]
    assert total == {
        "pages_evicted": cache.evicted_pages - before[0],
        "nodes_examined": cache.evict_nodes_examined - before[1],
        "tokens_keyed": cache.keyed_tokens - before[2],
    }
    # each prompt keyed about once: its chunks, and the lookup's miss
    assert len(prompt) + len(warm) <= total["tokens_keyed"] <= 2 * len(warm) + 8
