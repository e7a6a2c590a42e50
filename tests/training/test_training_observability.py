"""Training-loop observability: host-span capture across a real run
(the acceptance artifact — data_wait/dispatch/readback/checkpoint spans
covering full slabs, exported as Chrome trace-event JSON), the live
/metrics endpoint, and the profiling-window try/finally fix."""

import json
import urllib.request

import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.observability import trace
from zookeeper_tpu.training import TrainingExperiment


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    yield
    trace.disable()


def make_experiment(tmp_path, extra=None):
    exp = TrainingExperiment()
    conf = {
        "loader.dataset": "SyntheticMnist",
        "loader.dataset.num_train_examples": 256,
        "loader.dataset.num_validation_examples": 0,
        "loader.preprocessing": "ImageClassificationPreprocessing",
        "loader.preprocessing.height": 28,
        "loader.preprocessing.width": 28,
        "loader.preprocessing.channels": 1,
        "loader.host_index": 0,
        "loader.host_count": 1,
        "model": "Mlp",
        "model.hidden_units": (32,),
        "batch_size": 32,
        "epochs": 1,
        "validate": False,
        "verbose": False,
        "checkpointer.directory": str(tmp_path / "ckpt"),
        "checkpointer.synchronous": True,
        **(extra or {}),
    }
    configure(exp, conf, name="obs_experiment")
    return exp


def _spans(doc, name):
    return [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "X" and e["name"] == name
    ]


def test_fused_run_exports_full_slab_phase_trace(tmp_path):
    """The acceptance artifact: a fused (unroll>1) run's host trace is
    valid Chrome trace-event JSON covering >= one full slab with
    data_wait / dispatch / readback / checkpoint spans, each carrying
    step/slab attribution."""
    trace_path = tmp_path / "host_trace.json"
    exp = make_experiment(
        tmp_path,
        {
            "unroll": 2,
            "log_every": 2,
            "checkpointer.save_every_steps": 4,
            "trace_export": str(trace_path),
        },
    )
    exp.run()
    doc = json.loads(trace_path.read_text())
    # 256 examples / 32 batch = 8 steps = 4 slabs of 2.
    dispatch = _spans(doc, "dispatch")
    assert len(dispatch) == 4
    assert [e["args"]["slab"] for e in dispatch] == [0, 1, 2, 3]
    assert all("step" in e["args"] for e in dispatch)
    data_wait = _spans(doc, "data_wait")
    assert len(data_wait) >= 4  # one per slab pull (+ exhaustion probe)
    assert _spans(doc, "readback")  # log_every + epoch-end readbacks
    ckpt = _spans(doc, "checkpoint")
    assert len(ckpt) == 2  # save_every_steps=4 over 8 steps
    # The nested checkpointer-internal span rides the same timeline.
    assert _spans(doc, "ckpt_sync_save")
    # Every complete event is well-formed for the trace viewers.
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # Run-scoped enablement: teardown restored the disabled state.
    assert not trace.enabled()


def test_eager_run_exports_phase_trace(tmp_path):
    trace_path = tmp_path / "host_trace.json"
    exp = make_experiment(
        tmp_path, {"log_every": 4, "trace_export": str(trace_path)}
    )
    exp.run()
    doc = json.loads(trace_path.read_text())
    assert len(_spans(doc, "dispatch")) == 8  # one per eager step
    assert _spans(doc, "data_wait")
    assert _spans(doc, "readback")


def test_trace_export_written_even_when_run_raises(tmp_path):
    """Teardown exports the trace on the failure path too — the trace
    of a crashed run is the one you actually want to look at."""
    from zookeeper_tpu.resilience import faults

    trace_path = tmp_path / "host_trace.json"
    exp = make_experiment(tmp_path, {"trace_export": str(trace_path)})
    with faults.injected(faults.FaultPlan(kill_at_step=3)):
        with pytest.raises(faults.Preempted):
            exp.run()
    doc = json.loads(trace_path.read_text())
    assert _spans(doc, "dispatch")
    # The injected kill is a self-explaining instant on the timeline.
    injected = [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "i" and e["name"] == "fault_injected"
    ]
    assert injected and injected[0]["args"]["kind"] == "kill_at_step"
    assert not trace.enabled()


def test_metrics_endpoint_live_during_run(tmp_path):
    """metrics_port=0 brings up /metrics for the run's lifetime: a
    scrape from inside the run (hooked off the epoch writer call) sees
    the process-global gauges and the experiment's published epoch
    rates; the server is gone after teardown."""
    exp = make_experiment(tmp_path, {"epochs": 2, "metrics_port": 0})
    spe = 8  # 256 / 32
    scraped = {}
    orig_write = exp.writer.write_scalars

    def spy(step, values):
        server = getattr(exp, "obs_server", None)
        if (
            "body" not in scraped
            and server is not None
            and any(k.startswith("train_epoch/") for k in values)
            and step >= 2 * spe
        ):
            base = f"http://127.0.0.1:{server.port}"
            scraped["body"] = (
                urllib.request.urlopen(base + "/metrics").read().decode()
            )
            scraped["statusz"] = json.loads(
                urllib.request.urlopen(base + "/statusz").read()
            )
        return orig_write(step, values)

    exp.writer.write_scalars = spy
    exp.run()
    assert "body" in scraped, "epoch-boundary scrape never fired"
    body = scraped["body"]
    # Epoch-derived rates (published at the END of epoch 1, scraped at
    # epoch 2's writer call) and the process-global prefetch gauge.
    assert "zk_train_loss" in body
    assert "zk_train_examples_per_sec" in body
    assert "zk_train_epoch 1" in body
    assert "zk_prefetch_occupancy" in body
    status = scraped["statusz"]
    assert status["training"]["model"] == "Mlp"
    assert status["training"]["epochs"] == 2
    # Teardown stopped the server and cleared the handle.
    assert getattr(exp, "obs_server", None) is None


def test_prefetch_thread_is_named(tmp_path):
    """Satellite: the device-prefetch producer runs under a zk- name so
    py-spy / host-trace attribution reads as a subsystem, not
    Thread-N."""
    import threading
    import time

    from zookeeper_tpu.data.pipeline import prefetch_to_device

    seen = {}
    release = threading.Event()

    def slow_source():
        for i in range(4):
            yield {"x": i}
            release.wait(1.0)  # keep the producer alive to be observed

    it = prefetch_to_device(slow_source(), size=1)
    first = next(it)
    deadline = time.perf_counter() + 2.0
    while time.perf_counter() < deadline and "name" not in seen:
        names = [t.name for t in threading.enumerate()]
        hits = [n for n in names if n.startswith("zk-prefetch")]
        if hits:
            seen["name"] = hits[0]
        else:
            time.sleep(0.01)
    release.set()
    for _ in it:
        pass
    assert seen.get("name") == "zk-prefetch"
    assert first["x"] == 0


def test_profiling_window_closed_on_mid_capture_exception(
    tmp_path, monkeypatch
):
    """Satellite fix: an exception raised while the jax.profiler
    capture window is open (here: an injected preemption between
    p_start and p_stop) must still stop the trace in teardown —
    previously the window leaked and poisoned the next start_trace."""
    import jax

    from zookeeper_tpu.resilience import faults

    calls = {"start": 0, "stop": 0}
    real_start = jax.profiler.start_trace
    real_stop = jax.profiler.stop_trace

    def start(*a, **k):
        calls["start"] += 1
        return real_start(*a, **k)

    def stop(*a, **k):
        calls["stop"] += 1
        return real_stop(*a, **k)

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)

    exp = make_experiment(
        tmp_path, {"profile_dir": str(tmp_path / "prof")}
    )
    # Eager window is steps p_start=4..p_stop=7 (spe=8): kill at global
    # step 6, strictly inside the open capture.
    with faults.injected(faults.FaultPlan(kill_at_step=6)):
        with pytest.raises(faults.Preempted):
            exp.run()
    assert calls["start"] == 1
    assert calls["stop"] == 1, (
        "teardown must close the dangling capture window"
    )
    assert not getattr(exp, "_jax_trace_active", False)
    # And the next capture starts cleanly in the same process.
    real_start(str(tmp_path / "prof2"))
    real_stop()


def test_profiling_window_still_closed_on_clean_run(tmp_path, monkeypatch):
    """The happy path stops the trace exactly once (in the loop, not
    again in teardown)."""
    import jax

    calls = {"start": 0, "stop": 0}
    real_start = jax.profiler.start_trace
    real_stop = jax.profiler.stop_trace
    monkeypatch.setattr(
        jax.profiler,
        "start_trace",
        lambda *a, **k: (calls.__setitem__("start", calls["start"] + 1),
                         real_start(*a, **k))[1],
    )
    monkeypatch.setattr(
        jax.profiler,
        "stop_trace",
        lambda *a, **k: (calls.__setitem__("stop", calls["stop"] + 1),
                         real_stop(*a, **k))[1],
    )
    exp = make_experiment(
        tmp_path, {"profile_dir": str(tmp_path / "prof")}
    )
    exp.run()
    assert calls["start"] == 1
    assert calls["stop"] == 1


# -- device-side ledger / step-time watchdog / live MFU (docs §14) -------


def _gauge_names(reg):
    return {inst.name for inst in reg.collect()}


def test_live_run_publishes_step_time_gauge(tmp_path):
    """The acceptance artifact: a real (eager, log_every-synced)
    training run publishes zk_train_step_time_ms from its sync points
    and ledgers the step it dispatched. The cost_analysis-based
    zk_train_mfu gauges are gone (PR 24: the flops see no Pallas
    kernel; a share of a peak comes from the benchmark's trace)."""
    from zookeeper_tpu.observability.ledger import default_ledger

    exp = make_experiment(tmp_path, {"log_every": 2})
    exp.run()
    reg = exp.obs_registry
    step_ms = reg.gauge("zk_train_step_time_ms").value
    assert step_ms > 0
    rec = default_ledger().latest("train_step")
    assert rec is not None and rec.dispatches > 0
    assert not {n for n in _gauge_names(reg) if "mfu" in n}


def test_cpu_run_publishes_step_time_without_a_peak(tmp_path, monkeypatch):
    """A backend in no peak table row (the CPU) still gets its
    step-time gauge: it needs no peak anchor and reads no override."""
    monkeypatch.setenv("ZK_BENCH_PEAK_FLOPS", "not-a-number")
    exp = make_experiment(tmp_path, {"log_every": 2})
    exp.run()
    assert exp.obs_registry.gauge("zk_train_step_time_ms").value > 0
    assert "zk_train_mfu" not in _gauge_names(exp.obs_registry)


def test_fused_run_ledgers_multi_step_and_times_per_step(tmp_path):
    """The fused (unroll>1) loop ledgers its slab program with the
    slab's size and publishes PER-STEP time: the sync interval over
    the steps between two sync points, same definition as the eager
    loop."""
    from zookeeper_tpu.observability.ledger import default_ledger

    exp = make_experiment(tmp_path, {"unroll": 2, "log_every": 2})
    exp.run()
    rec = default_ledger().latest("multi_step")
    assert rec is not None
    assert rec.compile_ms is not None
    assert rec.attrs["steps"] == 2
    reg = exp.obs_registry
    step_ms = reg.gauge("zk_train_step_time_ms").value
    assert step_ms > 0
    ewma_ms = reg.gauge(
        "zk_step_time_ewma_ms", labels={"stream": "train_step"}
    ).value
    # one stream feeds both: the gauge is the last per-step sample of
    # the series the watchdog averages
    assert 0 < ewma_ms and step_ms < 50 * ewma_ms


def test_step_time_divides_by_the_steps_between_sync_points(tmp_path):
    """Two sync points three steps apart (a partial slab, a resume):
    the gauge is the interval over the steps actually completed, not
    over the configured unroll."""
    import time

    exp = make_experiment(tmp_path, {"unroll": 8})
    exp._obs_reset_timers()
    exp._obs_timer["sync_t"] = time.perf_counter() - 1.5
    exp._obs_timer["sync_step"] = 4
    exp._obs_sync_point(7)
    assert exp.obs_registry.gauge(
        "zk_train_step_time_ms"
    ).value == pytest.approx(500.0, rel=0.02)


def test_steady_run_fires_no_step_anomalies(tmp_path):
    """False-positive half of the watchdog contract at integration
    level: a short steady run's sync-stream observations sit inside
    the warmup window, so the anomaly counter is exactly zero."""
    exp = make_experiment(tmp_path, {"log_every": 2})
    exp.run()
    reg = exp.obs_registry
    assert reg.counter(
        "zk_step_time_anomalies_total", labels={"stream": "train_step"}
    ).value == 0
    # The dispatch stream baselined (its EWMA gauge moved off zero).
    assert reg.gauge(
        "zk_step_time_ewma_ms", labels={"stream": "train_dispatch"}
    ).value > 0


def test_metrics_endpoint_serves_mfu_and_hbm_series(tmp_path):
    """CI-smoke contract: with metrics_port on, the new gauges render
    as valid exposition text and the zk-device-probe's zk_hbm_* series
    exist from the first scrape (-1 sentinel on statless backends)."""
    import re
    import urllib.request

    seen = {}
    exp = make_experiment(tmp_path, {"log_every": 2, "metrics_port": 0})

    # Scrape DURING the run via the checkpointer save hook (the
    # endpoint tears down at run end).
    orig_save = exp.checkpointer.save

    def save_and_scrape(*a, **k):
        if "body" not in seen and getattr(exp, "obs_server", None):
            url = f"http://127.0.0.1:{exp.obs_server.port}/metrics"
            seen["body"] = urllib.request.urlopen(url).read().decode()
        return orig_save(*a, **k)

    exp.checkpointer.save = save_and_scrape
    exp.run()
    body = seen["body"]
    assert "zk_hbm_bytes_in_use" in body
    line_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$")
    samples = [
        l for l in body.splitlines() if l and not l.startswith("#")
    ]
    assert samples and all(line_re.match(l) for l in samples)
    assert getattr(exp, "obs_probe", None) is None  # torn down


def test_trace_export_with_profile_dir_logs_paired_artifacts(
    tmp_path, capsys
):
    """Satellite: the docs §13 Perfetto merge recipe is automated —
    one teardown writes the host spans AND closes the device capture,
    logging both artifact locations as a pair."""
    prof = tmp_path / "prof"
    out = tmp_path / "host_trace.json"
    exp = make_experiment(
        tmp_path,
        {
            "trace_export": str(out),
            "profile_dir": str(prof),
            "verbose": True,
        },
    )
    exp.run()
    assert out.exists()
    doc = json.loads(out.read_text())
    assert doc["traceEvents"]
    text = capsys.readouterr().out
    assert "paired trace artifacts" in text
    assert str(out) in text and str(prof) in text
    assert not getattr(exp, "_jax_trace_active", False)


# -- flight recorder (docs/DESIGN.md §16) ---------------------------------


@pytest.mark.chaos
def test_nan_halt_and_recovery_each_write_a_bundle(tmp_path):
    """flight_recorder_dir= arms the recorder for the run: the NaN
    halt bundles its evidence at the readback boundary, and the
    supervisor writes one more bundle per recovery — with the recorder
    still installed across the restart (run() teardown leaves it in
    place deliberately)."""
    import os

    from zookeeper_tpu.observability import recorder as recorder_mod
    from zookeeper_tpu.resilience import faults, run_with_recovery

    bundles_dir = tmp_path / "bundles"
    exp = make_experiment(
        tmp_path,
        {
            "nan_policy": "halt",
            "log_every": 1,
            "checkpointer.save_every_steps": 1,
            "flight_recorder_dir": str(bundles_dir),
            "flight_recorder_interval_s": 0.0,
        },
    )
    prior = recorder_mod.get_recorder()
    try:
        with faults.injected(faults.FaultPlan(nan_at_step=3)):
            result = run_with_recovery(
                exp, max_restarts=1, backoff_s=0.0, sleep=lambda s: None
            )
        assert result.restarts == 1
        rec = exp.flight_recorder
        kinds = [
            json.load(open(os.path.join(b, "manifest.json")))["trigger"][
                "kind"
            ]
            for b in rec.bundles()
        ]
        assert "nan_halt" in kinds, kinds
        assert "supervisor_restart" in kinds, kinds
        nan_bundle = rec.bundles()[kinds.index("nan_halt")]
        manifest = json.load(
            open(os.path.join(nan_bundle, "manifest.json"))
        )
        assert manifest["trigger"]["attrs"]["skipped_steps"] >= 1
        # The bundle carries the run's /statusz section + metrics text.
        statusz = json.load(
            open(os.path.join(nan_bundle, "statusz.json"))
        )
        assert statusz["training"]["model"] == "Mlp"
        assert os.path.getsize(os.path.join(nan_bundle, "metrics.prom")) >= 0
    finally:
        (
            recorder_mod.install(prior)
            if prior is not None
            else recorder_mod.uninstall()
        )


# -- PR 24: host_memory at the sync points, leaves on the loop's threads --


@pytest.mark.parametrize("unroll", [1, 2])
def test_traced_run_records_host_memory_at_every_sync_point(tmp_path, unroll):
    """One ``host_memory`` event per sync point (each log_every readback
    and the epoch's end), carrying the machine's memory in use and this
    process's RSS in bytes, ``step`` = the global step."""
    tracer = trace.enable(4096)
    exp = make_experiment(tmp_path, {"log_every": 2, "unroll": unroll})
    exp.run()
    records = tracer.snapshot()
    events = [r for r in records if r["name"] == "host_memory"]
    readbacks = [r for r in records if r["name"] == "readback"]
    # 8 steps, log_every 2: syncs at 2, 4, 6, 8 and the epoch's end at 8
    assert [r["step"] for r in events] == [2, 4, 6, 8, 8]
    assert len(events) == len(readbacks)
    for r in events:
        assert r["phase"] == "i" and set(r["attrs"]) == {
            "in_use_bytes", "rss_bytes",
        }
        assert r["attrs"]["in_use_bytes"] > r["attrs"]["rss_bytes"] > 0


def test_untraced_run_reads_no_proc_file_at_its_sync_points(
    tmp_path, monkeypatch
):
    from zookeeper_tpu.training import experiment as experiment_module

    def never(*a, **k):
        raise AssertionError("host memory read with tracing off")

    monkeypatch.setattr(experiment_module, "_host_memory", never)
    exp = make_experiment(tmp_path, {"log_every": 2})
    exp.run()
    assert exp.obs_registry.gauge("zk_train_step_time_ms").value > 0


def test_no_span_of_the_train_loop_or_the_loader_encloses_another(tmp_path):
    from tests.observability.trace_leaves import overlapping_spans

    tracer = trace.enable(4096)
    exp = make_experiment(tmp_path, {"log_every": 2})
    exp.run()
    records = tracer.snapshot()
    names = {r["name"] for r in records if r["phase"] == "X"}
    assert {
        "data_wait", "dispatch", "readback",
        "loader_assemble", "loader_stage", "loader_put_wait",
    } <= names
    threads = {
        r["name"]: r["thread_name"] for r in records if r["phase"] == "X"
    }
    assert threads["loader_stage"] == "zk-prefetch"
    assert threads["dispatch"] != "zk-prefetch"
    # the one enclosure the program has kept from before the leaf rule:
    # the loop's ``checkpoint`` span around the checkpointer's own
    # (``ckpt_snapshot`` / ``ckpt_sync_save``); no benchmark cell saves
    found = overlapping_spans(records)
    assert [v for v in found if v[1] != "checkpoint"] == []
    assert all(v[2].startswith("ckpt_") for v in found)
