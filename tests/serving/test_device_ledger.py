"""Serving half of the device ledger (docs/DESIGN.md §14): warmup
records serve_forward programs, a post-warmup request-path compile is a
DETECTED recompile (event + counter + statusz), and observe_dispatch
feeds the serve watchdog + zk_serve_mfu gauge."""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.observability import trace
from zookeeper_tpu.observability.ledger import default_ledger
from zookeeper_tpu.observability.registry import default_registry
from zookeeper_tpu.serving import InferenceEngine

pytestmark = pytest.mark.serving


def make_engine(buckets=(1, 4), hidden=(16,), features=6, classes=4):
    from zookeeper_tpu.models.simple import Mlp

    model = Mlp()
    configure(model, {"hidden_units": tuple(hidden)}, name="model")
    module = model.build((features,), classes)
    params, model_state = model.initialize(module, (features,), seed=0)
    engine = InferenceEngine()
    configure(engine, {"batch_buckets": tuple(buckets)}, name="engine")
    engine.bind(module.apply, params, model_state, (features,))
    return engine, module, {"params": params, **model_state}


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    yield
    trace.disable()


def test_warmup_records_serve_forward_ledger_rows():
    before = len(
        [r for r in default_ledger().entries() if r.kind == "serve_forward"]
    )
    engine, _, _ = make_engine(buckets=(1, 4))
    assert engine.warmup() == 2
    rows = [
        r for r in default_ledger().entries() if r.kind == "serve_forward"
    ]
    assert len(rows) == before + 2
    keys = {r.key for r in rows[-2:]}
    assert any("b1" in k for k in keys) and any("b4" in k for k in keys)
    for r in rows[-2:]:
        assert r.compile_ms is not None
        assert r.attrs["during_dispatch"] is False


def test_pre_warmup_compiles_are_not_recompiles():
    engine, _, _ = make_engine(buckets=(1, 4))
    engine.infer(np.zeros((2, 6), np.float32))  # cold-start compile
    assert engine.recompiles_detected == 0


def test_post_warmup_recompile_is_detected_and_announced():
    """A post-warmup compile on the request path — the condition the
    bucket ladder exists to prevent (here: a bucket the warmup ladder
    never covered, dispatched directly) — fires recompile_detected,
    bumps zk_serving_recompiles_total, and counts on the engine."""
    tracer = trace.enable()
    engine, _, _ = make_engine(buckets=(1, 4))
    engine.warmup()
    counter = default_registry().counter("zk_serving_recompiles_total")
    base_counter = counter.value
    base_compiles = engine.compile_count
    # An odd-shape dispatch outside the warmed ladder: the cache misses
    # post-warmup, which IS the recompile the watchdog detects.
    engine._compiled(3, None, np.float32, during_dispatch=True)
    assert engine.compile_count == base_compiles + 1
    assert engine.recompiles_detected == 1
    assert counter.value == base_counter + 1
    events = [
        r for r in tracer.drain() if r.get("name") == "recompile_detected"
    ]
    assert len(events) == 1
    assert events[0]["attrs"]["bucket"] == 3
    # Ledger row carries the during_dispatch attribution.
    row = default_ledger().latest("serve_forward")
    assert row.attrs["during_dispatch"] is True


def test_warmed_cache_hits_never_count_as_recompiles():
    engine, _, _ = make_engine(buckets=(1, 4))
    engine.warmup()
    for rows in (1, 3, 4):
        engine.infer(np.zeros((rows, 6), np.float32))
    assert engine.recompiles_detected == 0


def test_rebind_resets_the_warmup_watermark():
    """A rebind is a fresh program family: its cold compiles must not
    read as recompiles."""
    engine, module, variables = make_engine(buckets=(1, 4))
    engine.warmup()
    engine.bind(
        module.apply,
        variables["params"],
        {k: v for k, v in variables.items() if k != "params"},
        (6,),
    )
    engine.infer(np.zeros((2, 6), np.float32))
    assert engine.recompiles_detected == 0


def test_observe_dispatch_feeds_watchdog_and_mfu_gauge(monkeypatch):
    # The CPU backend has no peak anchor of its own; give one.
    monkeypatch.setenv("ZK_BENCH_PEAK_FLOPS", "184e12")
    engine, _, _ = make_engine(buckets=(1, 4))
    engine.warmup()
    engine.infer(np.zeros((4, 6), np.float32))
    reg = default_registry()
    engine.observe_dispatch(4, 0.050)
    assert reg.gauge("zk_serve_dispatch_ms").value == pytest.approx(50.0)
    mfu_value = reg.gauge("zk_serve_mfu").value
    flops = getattr(engine, "_last_dispatch_flops", None)
    if flops:
        # CPU cost analysis exists: the gauge is flops/time/peak.
        from zookeeper_tpu.observability.peaks import reference_peak_flops

        assert mfu_value == pytest.approx(
            flops / 0.050 / reference_peak_flops()[0], rel=1e-6
        )
    else:
        assert mfu_value == -1  # unknown renders as the sentinel


def test_observe_dispatch_ignores_degenerate_durations():
    engine, _, _ = make_engine(buckets=(1, 4))
    engine.observe_dispatch(4, 0.0)
    engine.observe_dispatch(4, -1.0)  # never raises


def test_batcher_dispatch_feeds_observe_dispatch():
    """The MicroBatcher's readback-bounded dispatch wall time reaches
    the engine: the serve_dispatch watchdog baseline moves after one
    real coalesced dispatch."""
    from zookeeper_tpu.serving import MicroBatcher

    engine, _, _ = make_engine(buckets=(1, 4))
    engine.warmup()
    batcher = MicroBatcher()
    configure(batcher, {"max_delay_ms": 1.0}, name="batcher")
    batcher.bind(engine)
    try:
        batcher.submit(np.zeros((2, 6), np.float32)).result()
    finally:
        batcher.close()
    dog = getattr(engine, "_dispatch_watchdog", None)
    assert dog is not None
    assert dog.ewma_seconds is not None and dog.ewma_seconds > 0


def test_statusz_reports_recompiles_and_programs():
    from zookeeper_tpu.serving import ServingConfig

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
        },
        name="serve_ledger_statusz",
    )
    engine, batcher = svc.build_service()
    try:
        import json
        import urllib.request

        batcher.submit(np.zeros((2, 4, 4, 1), np.float32)).result()
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/statusz" % svc.obs_server.port
        ).read()
        doc = json.loads(body)
        assert doc["serving"]["recompiles_detected"] == 0
        # The ledger section renders: serve_forward rows exist.
        kinds = {p["kind"] for p in doc["programs"]["programs"]}
        assert "serve_forward" in kinds
        # The device probe was started with the endpoint: zk_hbm_*
        # gauges exist (value or the -1 no-stats sentinel).
        assert svc.obs_probe is not None and svc.obs_probe.alive
        flat = doc["metrics"]
        assert any(k.startswith("zk_hbm_bytes_in_use") for k in flat)
    finally:
        svc.finish_report(
            warm_compiles=engine.compile_count, n_requests=1, dt=0.1
        )
    assert getattr(svc, "obs_probe", None) is None
