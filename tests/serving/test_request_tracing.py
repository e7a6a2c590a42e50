"""Request-scoped flow tracing through both serving stacks
(docs/DESIGN.md §16): rids minted at submit link submit -> dispatch ->
complete records across threads, every terminal outcome lands one
RequestLog summary, and a chaos-triggered flight-recorder bundle
carries one request's rid in all three places (RequestLog, flow
events, manifest) — the end-to-end correlation acceptance pin."""

import json
import os

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.observability import trace
from zookeeper_tpu.observability import recorder as recorder_mod
from zookeeper_tpu.observability.recorder import FlightRecorder
from zookeeper_tpu.resilience import faults
from zookeeper_tpu.serving import (
    DeadlineExpiredError,
    InferenceEngine,
    MicroBatcher,
    RejectedError,
    ServingMetrics,
    WorkerCrashedError,
)

pytestmark = pytest.mark.serving

FEATURES = 6
CLASSES = 4


@pytest.fixture(scope="module")
def engine():
    from zookeeper_tpu.models.simple import Mlp

    model = Mlp()
    configure(model, {"hidden_units": (16,)}, name="model")
    module = model.build((FEATURES,), CLASSES)
    params, model_state = model.initialize(module, (FEATURES,))
    eng = InferenceEngine()
    configure(eng, {"batch_buckets": (1, 4, 8)}, name="engine")
    eng.bind(module.apply, params, model_state, (FEATURES,))
    eng.warmup()
    return eng


@pytest.fixture
def fresh_tracer():
    prior = trace.get_tracer()
    trace.install(trace.Tracer(4096))
    yield trace.get_tracer()
    trace.install(prior)


@pytest.fixture
def no_global_recorder():
    prior = recorder_mod.get_recorder()
    recorder_mod.uninstall()
    yield
    (
        recorder_mod.install(prior)
        if prior is not None
        else recorder_mod.uninstall()
    )


def make_batcher(engine, **conf):
    metrics = ServingMetrics()
    configure(metrics, {}, name="metrics")
    batcher = MicroBatcher()
    configure(batcher, dict(conf), name="batcher")
    batcher.bind(engine, metrics=metrics)
    return batcher, metrics


def wait_for_bundle(rec, kind, timeout=15.0):
    """Poll for a COMPLETE bundle of trigger ``kind`` (manifest last =
    complete, the recorder's finalize protocol): synchronous bundles
    for crash triggers are written by the crashing worker thread,
    which keeps running briefly after result() has already raised."""
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for path in rec.bundles():
            manifest = os.path.join(path, "manifest.json")
            if os.path.exists(manifest):
                trigger = json.load(open(manifest))["trigger"]
                if trigger["kind"] == kind:
                    return path, trigger
        time.sleep(0.01)
    raise AssertionError(
        f"no complete {kind!r} bundle within {timeout}s: {rec.bundles()}"
    )


def flow_chain(rid):
    doc = trace.to_chrome_trace()
    chain = sorted(
        (
            e
            for e in doc["traceEvents"]
            if e.get("cat") == "rid" and e["id"] == rid
        ),
        key=lambda e: e["ts"],
    )
    names_by_rid = [
        e["name"]
        for e in doc["traceEvents"]
        if e.get("args", {}).get("rid") == rid
    ]
    threads = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "M" and e["name"] == "thread_name":
            threads[e["tid"]] = e["args"]["name"]
    return chain, names_by_rid, threads


def test_sync_rid_links_submit_dispatch_complete(engine, fresh_tracer):
    batcher, _ = make_batcher(engine)
    x = np.ones((3, FEATURES), np.float32)
    handle = batcher.submit(x)
    rid = handle.rid
    assert rid is not None
    out = handle.result()
    assert out.shape == (3, CLASSES)
    chain, names, _ = flow_chain(rid)
    assert [e["ph"] for e in chain] == ["s", "t", "f"]
    assert names == [
        "request_enqueue", "request_dispatch", "request_complete",
    ]
    # The RequestLog summary correlates on the same rid.
    rec = batcher.request_log.find(rid)
    assert rec["outcome"] == "ok"
    assert rec["rows"] == 3
    assert rec["bucket"] == 4
    assert rec["enqueue_ns"] <= rec["dispatch_ns"] <= rec["complete_ns"]
    assert rec["weights_step"] == -1  # bind-time weights


def test_async_rid_flow_crosses_into_microbatcher_thread(
    engine, fresh_tracer
):
    """The cross-thread pin: submit records on the caller thread,
    dispatch/complete on zk-microbatcher, one flow id across both."""
    batcher, _ = make_batcher(engine, synchronous=False, max_delay_ms=1.0)
    try:
        handles = [
            batcher.submit(np.ones((2, FEATURES), np.float32))
            for _ in range(3)
        ]
        for handle in handles:
            assert handle.result(timeout=30).shape == (2, CLASSES)
        for handle in handles:
            chain, names, threads = flow_chain(handle.rid)
            assert [e["ph"] for e in chain] == ["s", "t", "f"]
            assert threads[chain[0]["tid"]] != "zk-microbatcher"
            assert threads[chain[-1]["tid"]] == "zk-microbatcher"
            assert batcher.request_log.find(handle.rid)["outcome"] == "ok"
    finally:
        batcher.close()


def test_shed_and_deadline_outcomes_recorded(engine, fresh_tracer):
    batcher, metrics = make_batcher(engine, shed_above_rows=2)
    # Fill the queue past the shed threshold, then submit one more.
    first = batcher.submit(np.ones((2, FEATURES), np.float32))
    with pytest.raises(RejectedError):
        batcher.submit(np.ones((4, FEATURES), np.float32))
    shed = [
        r
        for r in batcher.request_log.tail()
        if r["outcome"] == "shed"
    ]
    assert len(shed) == 1 and shed[0]["rows"] == 4
    # Drain the queue (an empty queue always admits), then the
    # deadline leg: deadline_ms=0 is expiry-by-construction (the
    # clock-free chaos idiom).
    assert first.result().shape == (2, CLASSES)
    assert batcher.request_log.find(first.rid)["outcome"] == "ok"
    expired = batcher.submit(
        np.ones((1, FEATURES), np.float32), deadline_ms=0
    )
    with pytest.raises(DeadlineExpiredError):
        expired.result()
    rec = batcher.request_log.find(expired.rid)
    assert rec["outcome"] == "deadline_expired"
    assert rec["dispatch_ns"] is None  # never dispatched


@pytest.mark.chaos
def test_worker_crash_outcome_and_flow(engine, fresh_tracer):
    """FaultPlan.serving_worker_crash: the crashed requests' summaries
    say crashed, and their flow still links submit -> complete."""
    batcher, _ = make_batcher(engine, synchronous=False, max_delay_ms=1.0)
    try:
        with faults.injected(faults.FaultPlan(serving_worker_crash=1)):
            handle = batcher.submit(np.ones((2, FEATURES), np.float32))
            with pytest.raises(WorkerCrashedError):
                handle.result(timeout=30)
        rec = batcher.request_log.find(handle.rid)
        assert rec["outcome"] == "crashed"
        assert rec["detail"] == "WorkerCrashedError"
        chain, names, _ = flow_chain(handle.rid)
        assert [e["ph"] for e in chain] == ["s", "f"]
        assert names == ["request_enqueue", "request_complete"]
        # Crash cleanup restarts on the next submit: the follow-up is ok.
        retry = batcher.submit(np.ones((2, FEATURES), np.float32))
        assert retry.result(timeout=30).shape == (2, CLASSES)
        assert batcher.request_log.find(retry.rid)["outcome"] == "ok"
    finally:
        batcher.close()


@pytest.mark.chaos
def test_chaos_bundle_correlates_rid_in_all_three_places(
    engine, tmp_path, fresh_tracer, no_global_recorder
):
    """THE end-to-end correlation acceptance pin (ISSUE 10): a
    chaos-triggered bundle contains one request's rid in (1) the
    RequestLog summary with outcome=crashed, (2) the Chrome flow
    events linking its submit/dispatch records, and (3) sits beside
    the manifest's trigger record naming the crash."""
    batcher, metrics = make_batcher(
        engine, synchronous=False, max_delay_ms=1.0
    )
    rec = FlightRecorder(
        str(tmp_path / "bundles"),
        registries=[metrics.registry],
        request_logs={"serving": batcher.request_log},
        min_interval_s=0.0,
        synchronous=True,
    )
    recorder_mod.install(rec)
    try:
        with faults.injected(faults.FaultPlan(serving_worker_crash=1)):
            handle = batcher.submit(np.ones((3, FEATURES), np.float32))
            with pytest.raises(WorkerCrashedError):
                handle.result(timeout=30)
        rid = handle.rid
        # The crash produced (at least) the worker_crash bundle, fired
        # AFTER the requests were failed; the fault_injected bundle
        # rides alongside. Written by the crashing worker thread, so
        # poll for manifest-complete.
        bundle, _ = wait_for_bundle(rec, "worker_crash")
        # (1) RequestLog tail: outcome=crashed under this rid.
        requestlog = json.load(
            open(os.path.join(bundle, "requestlog.json"))
        )
        summary = [
            r
            for r in requestlog["serving"]["tail"]
            if r["rid"] == rid
        ]
        assert summary and summary[0]["outcome"] == "crashed"
        # (2) Chrome flow events linking the request's records.
        doc = json.load(open(os.path.join(bundle, "trace.json")))
        flow = sorted(
            (
                e
                for e in doc["traceEvents"]
                if e.get("cat") == "rid" and e["id"] == rid
            ),
            key=lambda e: e["ts"],
        )
        assert [e["ph"] for e in flow] == ["s", "f"]
        # (3) The manifest's trigger record names the crash.
        manifest = json.load(
            open(os.path.join(bundle, "manifest.json"))
        )
        assert manifest["trigger"]["kind"] == "worker_crash"
        assert manifest["trigger"]["attrs"]["error"] == "WorkerCrashedError"
    finally:
        recorder_mod.uninstall(rec)
        batcher.close()


# -- decode stack ---------------------------------------------------------


@pytest.fixture(scope="module")
def decode_pair():
    from zookeeper_tpu.serving.decode.metrics import DecodeMetrics

    from tests.serving.test_decode_engine import build_lm, make_engine

    module, params, state, _ = build_lm()
    eng = make_engine(module, params, state, slots=2, seq_buckets=(8,))
    eng.warmup()
    metrics = DecodeMetrics()
    configure(metrics, {}, name="metrics")
    return eng, metrics


def make_scheduler(decode_pair, **conf):
    from zookeeper_tpu.serving.decode import DecodeScheduler

    eng, metrics = decode_pair
    sched = DecodeScheduler()
    configure(sched, dict(conf), name="scheduler")
    sched.bind(eng, metrics=metrics)
    return sched


def test_decode_sync_rid_flow_and_summary(decode_pair, fresh_tracer):
    sched = make_scheduler(decode_pair)
    stream = sched.submit(
        np.arange(1, 5, dtype=np.int32), max_new_tokens=3
    )
    rid = stream.rid
    assert rid is not None
    tokens = stream.result()
    assert tokens.shape[0] == 3
    chain, names, _ = flow_chain(rid)
    # the flow arrow links the request's phases; its three tokens are
    # rid-tagged events too, and stay out of the arrow
    assert [e["ph"] for e in chain] == ["s", "t", "f"]
    assert names == [
        "decode_request_enqueue",
        "decode_request_dispatch",
        "token_delivered",
        "token_delivered",
        "token_delivered",
        "decode_stream_finish",
    ]
    rec = sched.request_log.find(rid)
    assert rec["outcome"] == "ok"
    assert rec["detail"] == "length"  # max_new_tokens finish reason
    assert rec["tokens"] == 3
    assert rec["slot"] is not None


def test_decode_async_rid_flow_crosses_into_worker(
    decode_pair, fresh_tracer
):
    sched = make_scheduler(decode_pair, synchronous=False)
    try:
        stream = sched.submit(
            np.arange(1, 4, dtype=np.int32), max_new_tokens=2
        )
        assert stream.result(timeout=30).shape[0] == 2
        chain, _, threads = flow_chain(stream.rid)
        assert [e["ph"] for e in chain] == ["s", "t", "f"]
        assert threads[chain[0]["tid"]] != "zk-decode-scheduler"
        assert threads[chain[-1]["tid"]] == "zk-decode-scheduler"
        assert sched.request_log.find(stream.rid)["outcome"] == "ok"
    finally:
        sched.close()


def test_decode_shed_and_deadline_summaries(decode_pair, fresh_tracer):
    sched = make_scheduler(decode_pair, shed_above=2)
    first = sched.submit(np.arange(1, 3, dtype=np.int32))
    second = sched.submit(np.arange(1, 3, dtype=np.int32))
    with pytest.raises(RejectedError):
        sched.submit(np.arange(1, 3, dtype=np.int32))
    shed = [
        r for r in sched.request_log.tail() if r["outcome"] == "shed"
    ]
    assert len(shed) == 1
    sched.drain()  # empty the queue: an empty queue always admits
    for stream in (first, second):
        stream.result()
        assert sched.request_log.find(stream.rid)["outcome"] == "ok"
    expired = sched.submit(
        np.arange(1, 3, dtype=np.int32), deadline_ms=0
    )
    with pytest.raises(DeadlineExpiredError):
        expired.result()
    assert (
        sched.request_log.find(expired.rid)["outcome"]
        == "deadline_expired"
    )


@pytest.mark.chaos
def test_decode_crash_bundle_correlates_rid(
    decode_pair, tmp_path, fresh_tracer, no_global_recorder
):
    """Decode half of the correlation pin: FaultPlan.decode_worker_crash
    -> bundle with the stream's rid in RequestLog (crashed), flow
    events, and the decode_worker_crash manifest."""
    eng, metrics = decode_pair
    sched = make_scheduler(decode_pair)
    rec = FlightRecorder(
        str(tmp_path / "bundles"),
        registries=[metrics.registry],
        request_logs={"decode": sched.request_log},
        min_interval_s=0.0,
        synchronous=True,
    )
    recorder_mod.install(rec)
    try:
        with faults.injected(faults.FaultPlan(decode_worker_crash=1)):
            stream = sched.submit(
                np.arange(1, 4, dtype=np.int32), max_new_tokens=2
            )
            with pytest.raises(WorkerCrashedError):
                stream.result()
        rid = stream.rid
        bundle, _ = wait_for_bundle(rec, "decode_worker_crash")
        requestlog = json.load(
            open(os.path.join(bundle, "requestlog.json"))
        )
        summary = [
            r
            for r in requestlog["decode"]["tail"]
            if r["rid"] == rid
        ]
        assert summary and summary[0]["outcome"] == "crashed"
        doc = json.load(open(os.path.join(bundle, "trace.json")))
        flow = [
            e
            for e in doc["traceEvents"]
            if e.get("cat") == "rid" and e["id"] == rid
        ]
        assert {e["ph"] for e in flow} == {"s", "f"}
    finally:
        recorder_mod.uninstall(rec)
        sched.close()


def test_statusz_requests_section_renders(engine, no_global_recorder):
    """ServingConfig exposes the RequestLog as a /statusz section and
    arms the flight recorder from config (flight_recorder_dir=)."""
    import tempfile
    import urllib.request

    from zookeeper_tpu.serving import ServingConfig

    with tempfile.TemporaryDirectory() as tmp:
        svc = ServingConfig()
        configure(
            svc,
            {
                "model": "Mlp",
                "model.hidden_units": (8,),
                "height": 4,
                "width": 4,
                "channels": 1,
                "num_classes": 3,
                "engine.batch_buckets": (1, 4),
                "verbose": False,
                "metrics_port": 0,
                "flight_recorder_dir": os.path.join(tmp, "bundles"),
            },
            name="svc_requests_statusz",
        )
        engine2, batcher = svc.build_service()
        try:
            batcher.submit(np.zeros((2, 4, 4, 1), np.float32)).result()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.obs_server.port}/statusz",
                timeout=10,
            ) as resp:
                statusz = json.loads(resp.read().decode())
            requests_section = statusz["requests"]
            assert requests_section["recorded_total"] == 1
            assert requests_section["tail"][0]["outcome"] == "ok"
            assert statusz["flight_recorder"]["installed"] is True
            # Manual POST /debugz writes a bundle via the config-armed
            # recorder.
            req = urllib.request.Request(
                f"http://127.0.0.1:{svc.obs_server.port}/debugz",
                data=b"",
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                body = json.loads(resp.read().decode())
            assert os.path.isdir(body["bundle"])
        finally:
            svc._teardown_service(suppress=True)
        # Teardown disarms the global recorder.
        assert recorder_mod.get_recorder() is None


# -- PR 24: the scheduler's leaf spans ------------------------------------


SCHED_LEAVES = {
    "sched_sweep", "sched_admit_plan", "sched_admit_commit",
    "sched_decode_plan", "sched_deliver", "sched_bookkeeping",
}
DISPATCH_SPANS = {
    "prefill_dispatch", "prefill_warm_dispatch", "prefill_chunk_dispatch",
    "decode_dispatch",
}


def traced_serving_run(sched, n_requests=5, max_new_tokens=4):
    rng = np.random.default_rng(7)
    streams = [
        sched.submit(
            rng.integers(1, 50, size=int(rng.integers(2, 8))).astype(np.int32),
            max_new_tokens=max_new_tokens,
        )
        for _ in range(n_requests)
    ]
    for stream in streams:
        stream.result(timeout=120)
    return streams


@pytest.fixture(scope="module")
def chunked_pair():
    from tests.serving.test_chunked_prefill import chunked_engine
    from tests.serving.test_decode_engine import build_lm

    module, params, state, _ = build_lm()
    eng = chunked_engine(module, params, state, chunk=4, name="pr24chunk")
    eng.warmup()
    return eng, None


@pytest.fixture(params=["sync", "worker", "chunked"])
def leaf_run(request, decode_pair, fresh_tracer):
    """A tiny traced serving run in each of the scheduler's modes:
    ``(records, streams)``."""
    if request.param == "chunked":
        pair = request.getfixturevalue("chunked_pair")
        sched = make_scheduler(pair)
    else:
        sched = make_scheduler(
            decode_pair, synchronous=request.param == "sync"
        )
    try:
        streams = traced_serving_run(sched)
    finally:
        sched.close()
    return fresh_tracer.snapshot(), streams


def test_no_span_of_the_scheduler_encloses_another(leaf_run):
    from tests.observability.trace_leaves import overlapping_spans

    records, _ = leaf_run
    names = {r["name"] for r in records if r["phase"] == "X"}
    assert SCHED_LEAVES <= names
    assert names & DISPATCH_SPANS
    assert overlapping_spans(records) == []


def test_leaves_of_one_iteration_share_its_step_and_end_in_one_event(leaf_run):
    from tests.observability.trace_leaves import iterations

    records, _ = leaf_run
    groups = iterations(records)
    assert len(groups) >= 3
    # every scheduler leaf and every dispatch span belongs to a closed
    # iteration: none carries no step, none a step that never ended
    for r in records:
        if r["name"] in SCHED_LEAVES | DISPATCH_SPANS:
            assert (r["thread_id"], r["step"]) in groups, r
    previous_end = {}
    for (thread, step), recs in sorted(groups.items()):
        ends = [r for r in recs if r["name"] == "sched_iteration_end"]
        assert len(ends) == 1
        assert set(ends[0]["attrs"]) == {
            "admitted", "decoded", "chunks", "wall_ns", "cpu_ns",
            "in_flight", "dropped",
        }
        assert ends[0]["attrs"]["in_flight"] in (0, 1)
        assert ends[0]["attrs"]["dropped"] == 0  # every stream ends by budget
        spans = [r for r in recs if r["phase"] == "X"]
        assert spans[0]["name"] == "sched_sweep"
        assert spans[-1]["name"] == "sched_bookkeeping"
        # in order, not overlapping, inside the iteration
        at = previous_end.get(thread, 0)
        for r in spans:
            assert r["ts_ns"] >= at
            at = r["ts_ns"] + r["dur_ns"]
        assert at <= ends[0]["ts_ns"]
        previous_end[thread] = ends[0]["ts_ns"]
    admitted = sum(
        r["attrs"]["admitted"] for r in records
        if r["name"] == "sched_iteration_end"
    )
    assert admitted == 5


def test_every_delivered_token_has_one_token_delivered_event(leaf_run):
    records, streams = leaf_run
    for stream in streams:
        events = [
            r for r in records
            if r["name"] == "token_delivered" and r["rid"] == stream.rid
        ]
        tokens = stream.tokens_so_far
        assert len(events) == len(tokens) == 4
        assert len(stream.token_times_ns) == len(tokens)
        assert stream.token_times_ns == sorted(stream.token_times_ns)
        # stamped by the thread that delivers, inside an iteration
        assert all(r["step"] is not None for r in events)
        for stamp, r in zip(stream.token_times_ns, events):
            assert 0 <= r["ts_ns"] - stamp < 5_000_000


def test_token_times_stay_empty_while_tracing_is_off(decode_pair):
    assert not trace.enabled()
    sched = make_scheduler(decode_pair)
    stream = sched.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=3)
    assert stream.result().shape[0] == 3
    assert stream.token_times_ns == []
