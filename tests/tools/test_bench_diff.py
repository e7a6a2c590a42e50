"""Bench regression gate: direction-aware classification, tolerance
gating, driver-wrapper loading, schema-mismatch downgrade, and the CLI
exit-code contract."""

import json

import pytest

from tools import bench_diff


# -- classification ------------------------------------------------------


@pytest.mark.parametrize(
    "name,expected",
    [
        ("value", "higher"),
        ("vs_baseline", "higher"),
        ("lm_tokens_per_sec_per_chip", "higher"),
        ("host_aug_images_per_sec_per_core", "higher"),
        ("serve_qps_per_chip", "higher"),
        ("mfu_vs_measured_peak", "higher"),
        ("ckpt_steps_overlapped_per_save", "higher"),
        ("serve_p50_ms", "lower"),
        ("serve_p99_ms", "lower"),
        ("recovery_restore_ms", "lower"),
        ("ckpt_async_save_stall_ms", "lower"),
        ("shed_rate", "lower"),
        # Decode-serving leg (ZK_BENCH_DECODE): the two gated keys the
        # acceptance criteria name, plus the ride-along latencies.
        ("serve_decode_tokens_per_sec_per_chip", "higher"),
        ("decode_ttft_p99_ms", "lower"),
        ("decode_ttft_p50_ms", "lower"),
        ("decode_token_p50_ms", "lower"),
        ("decode_prefill_p50_ms", "lower"),
    ],
)
def test_classify_metric_directions(name, expected):
    assert bench_diff.classify_metric(name) == expected


@pytest.mark.parametrize(
    "name",
    [
        "model", "metric", "unit", "n_chips", "batch_size", "unroll",
        "device_kind", "git_sha", "jax_version", "bench_schema_version",
        "peak_flops_source", "binary_compute", "obs_trace_overhead_frac",
        # Peak anchors and FLOP counts are measurement CONTEXT: a
        # re-measured peak (the BENCH_r04 237.9 pathology being fixed)
        # explains the gated numbers and must not gate itself.
        "measured_bf16_peak_tflops", "measured_int8_peak_tops",
        "model_step_tflops",
        # Decode-leg workload shape: config, not performance.
        "decode_requests", "decode_slots", "decode_new_tokens",
        "decode_refills", "decode_generated_tokens",
    ],
)
def test_identity_and_context_keys_never_gate(name):
    assert bench_diff.classify_metric(name) is None


# -- compare -------------------------------------------------------------


def _line(**kw):
    base = {
        "metric": "quicknet_train_images_per_sec_per_chip",
        "value": 1000.0,
        "unit": "images/sec/chip",
        "bench_schema_version": 1,
    }
    base.update(kw)
    return base


def test_no_gate_within_tolerance():
    diff = bench_diff.compare(_line(value=950.0), _line(value=1000.0))
    assert diff.ok
    assert not diff.regressions and not diff.improvements


def test_throughput_drop_beyond_tolerance_is_a_regression():
    diff = bench_diff.compare(_line(value=850.0), _line(value=1000.0))
    assert not diff.ok
    (row,) = diff.regressions
    assert row["name"] == "value"
    assert row["delta"] == pytest.approx(-0.15)
    assert "REGRESSION" in diff.report()


def test_latency_directions_invert():
    cur = _line(serve_p50_ms=12.0)
    prev = _line(serve_p50_ms=10.0)
    diff = bench_diff.compare(cur, prev)
    assert [r["name"] for r in diff.regressions] == ["serve_p50_ms"]
    # A latency DROP is an improvement, not a regression.
    diff2 = bench_diff.compare(prev, _line(serve_p50_ms=14.0))
    assert diff2.ok
    assert [r["name"] for r in diff2.improvements] == ["serve_p50_ms"]


def test_per_metric_tolerance_overrides_default():
    # serve_p99_ms carries a 30% override: +25% is weather, not a gate.
    diff = bench_diff.compare(
        _line(serve_p99_ms=12.5), _line(serve_p99_ms=10.0)
    )
    assert diff.ok
    diff2 = bench_diff.compare(
        _line(serve_p99_ms=14.0), _line(serve_p99_ms=10.0)
    )
    assert not diff2.ok


def test_added_removed_and_drift_never_gate():
    cur = _line(new_leg_tokens_per_sec=5.0, model="QuickNet")
    prev = _line(old_leg_qps=3.0, model="ResNet50")
    diff = bench_diff.compare(cur, prev)
    assert diff.ok
    assert "new_leg_tokens_per_sec" in diff.added
    assert "old_leg_qps" in diff.removed
    assert [d["name"] for d in diff.drift] == ["model"]


def test_schema_mismatch_downgrades_to_report_only():
    cur = _line(value=500.0, bench_schema_version=2)
    prev = _line(value=1000.0, bench_schema_version=1)
    diff = bench_diff.compare(cur, prev)
    assert diff.schema_mismatch
    assert diff.ok  # a 50% drop would gate, but renames would lie
    assert "REPORT-ONLY" in diff.report()


def test_zero_previous_reports_as_drift():
    diff = bench_diff.compare(
        _line(serve_p50_ms=5.0), _line(serve_p50_ms=0.0)
    )
    assert diff.ok
    assert any(d["name"] == "serve_p50_ms" for d in diff.drift)


def test_negative_unknown_sentinel_reports_as_drift():
    # -1.0 is the repo-wide "unknown" sentinel (MFU without cost
    # analysis, HBM without memory_stats): a measurement gap must not
    # gate as a fake regression in either direction.
    diff = bench_diff.compare(
        _line(vs_baseline=-1.0), _line(vs_baseline=0.34)
    )
    assert diff.ok
    assert any(d["name"] == "vs_baseline" for d in diff.drift)
    diff = bench_diff.compare(
        _line(vs_baseline=0.34), _line(vs_baseline=-1.0)
    )
    assert diff.ok
    assert not diff.improvements


def test_bools_and_strings_never_gate():
    diff = bench_diff.compare(
        _line(host_aug_native_available=True, peak_flops_source="measured"),
        _line(host_aug_native_available=False, peak_flops_source="env"),
    )
    assert diff.ok
    assert {d["name"] for d in diff.drift} == {
        "host_aug_native_available",
        "peak_flops_source",
    }


# -- loading -------------------------------------------------------------


def test_load_raw_line_and_driver_wrapper(tmp_path):
    raw = tmp_path / "raw.json"
    raw.write_text(json.dumps(_line()))
    assert bench_diff.load_bench_json(str(raw))["value"] == 1000.0
    # The committed BENCH_r*.json driver wrapper nests the line under
    # "parsed".
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(
        json.dumps({"n": 5, "cmd": "bench", "rc": 0, "parsed": _line()})
    )
    assert bench_diff.load_bench_json(str(wrapped))["value"] == 1000.0


def test_load_rejects_non_bench_documents(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"unrelated": 1}))
    with pytest.raises(ValueError):
        bench_diff.load_bench_json(str(bad))
    notdict = tmp_path / "notdict.json"
    notdict.write_text("[1, 2]")
    with pytest.raises(ValueError):
        bench_diff.load_bench_json(str(notdict))


# -- CLI contract --------------------------------------------------------


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_cli_exit_codes(tmp_path, capsys):
    cur = _write(tmp_path, "cur.json", _line(value=800.0))
    prev = _write(tmp_path, "prev.json", _line(value=1000.0))
    same = _write(tmp_path, "same.json", _line(value=1000.0))
    assert bench_diff.main([same, prev]) == 0
    assert bench_diff.main([cur, prev]) == 3
    assert bench_diff.main([cur, prev, "--allow-regression"]) == 0
    assert bench_diff.main([cur, str(tmp_path / "missing.json")]) == 2
    capsys.readouterr()


def test_cli_writes_diff_artifact(tmp_path, capsys):
    cur = _write(tmp_path, "cur.json", _line(value=800.0))
    prev = _write(tmp_path, "prev.json", _line(value=1000.0))
    out = tmp_path / "diff.json"
    assert bench_diff.main([cur, prev, "--json", str(out)]) == 3
    doc = json.loads(out.read_text())
    assert doc["ok"] is False
    assert doc["regressions"][0]["name"] == "value"
    capsys.readouterr()


def test_cli_custom_tolerance(tmp_path, capsys):
    cur = _write(tmp_path, "cur.json", _line(value=850.0))
    prev = _write(tmp_path, "prev.json", _line(value=1000.0))
    assert bench_diff.main([cur, prev]) == 3  # default 10%
    assert bench_diff.main([cur, prev, "--tol", "0.20"]) == 0
    capsys.readouterr()


def test_bench_main_wires_compare(tmp_path):
    """bench.py --compare parses and threads through to the gate (the
    full bench run needs a device; the arg contract is what CI relies
    on)."""
    import bench

    args = bench.parse_args(["--compare", "BENCH_r05.json"])
    assert args.compare == "BENCH_r05.json"
    assert args.compare_out is None
    args = bench.parse_args([])
    assert args.compare is None


def test_decode_kernel_era_keys_classify():
    """The paged-decode-kernel A/B + MBU keys (DESIGN.md §17) gate
    direction-aware; the flavor tag is config, not perf."""
    assert bench_diff.classify_metric("decode_mbu") == "higher"
    assert bench_diff.classify_metric("decode_kernel_speedup") == "higher"
    for key in (
        "decode_kernel_tokens_per_sec_per_chip",
        "decode_reference_tokens_per_sec_per_chip",
    ):
        assert bench_diff.classify_metric(key) == "higher"
    assert bench_diff.classify_metric("decode_attention_flavor") is None


def test_decode_kernel_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key in (
        "decode_mbu",
        "decode_kernel_speedup",
        "decode_kernel_tokens_per_sec_per_chip",
        "decode_reference_tokens_per_sec_per_chip",
    ):
        tol = TOLERANCES[key]
        prev = {"metric": "x", key: 1.0}
        # Just inside tolerance: no gate; past it: regression.
        ok = compare({"metric": "x", key: 1.0 - tol * 0.9}, prev)
        assert ok.ok, key
        bad = compare({"metric": "x", key: 1.0 - tol * 1.5}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key


def test_speculative_era_keys_classify():
    """The speculative-decode A/B keys (DESIGN.md §18) gate
    direction-aware: both throughputs and the speedup higher-better,
    and acceptance_rate is the one ``_rate$`` where UP is good (checked
    before the lower-better latency family); workload-shape keys are
    config, not perf."""
    for key in (
        "spec_tokens_per_sec_per_chip",
        "spec_plain_tokens_per_sec_per_chip",
        "spec_speedup",
        "spec_acceptance_rate",
    ):
        assert bench_diff.classify_metric(key) == "higher", key
    # The generic rate family stays lower-better.
    assert bench_diff.classify_metric("shed_rate") == "lower"
    for key in (
        "spec_k",
        "spec_teacher_layers",
        "spec_draft_layers",
        "spec_requests",
        "spec_slots",
        "spec_new_tokens",
    ):
        assert bench_diff.classify_metric(key) is None, key


def test_speculative_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key in (
        "spec_tokens_per_sec_per_chip",
        "spec_plain_tokens_per_sec_per_chip",
        "spec_speedup",
        "spec_acceptance_rate",
    ):
        tol = TOLERANCES[key]
        prev = {"metric": "x", key: 1.0}
        ok = compare({"metric": "x", key: 1.0 - tol * 0.9}, prev)
        assert ok.ok, key
        bad = compare({"metric": "x", key: 1.0 - tol * 1.5}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key


def test_binary_kernel_era_keys_classify():
    """The §21 binary-kernel A/B keys gate direction-aware: both
    throughputs, the speedup and the int8-anchored MFU higher-better;
    the workload shape and the flavor/source tags are config, not
    perf."""
    for key in (
        "binary_kernel_images_per_sec_per_chip",
        "binary_reference_images_per_sec_per_chip",
        "binary_kernel_speedup",
        "binary_mfu_vs_measured_int8_peak",
    ):
        assert bench_diff.classify_metric(key) == "higher", key
    for key in (
        "binary_model",
        "binary_batch",
        "binary_image",
        "binary_kernel_flavor",
        "binary_int8_peak_source",
    ):
        assert bench_diff.classify_metric(key) is None, key


def test_binary_kernel_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key in (
        "binary_kernel_images_per_sec_per_chip",
        "binary_reference_images_per_sec_per_chip",
        "binary_kernel_speedup",
        "binary_mfu_vs_measured_int8_peak",
    ):
        tol = TOLERANCES[key]
        prev = {"metric": "x", key: 1.0}
        ok = compare({"metric": "x", key: 1.0 - tol * 0.9}, prev)
        assert ok.ok, key
        bad = compare({"metric": "x", key: 1.0 - tol * 1.5}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key


def test_disagg_era_keys_classify():
    """The §22 disaggregated-serving A/B keys gate direction-aware:
    both topologies' throughputs higher-better, the TTFT tails and the
    per-handoff transfer median lower-better (``transfer_ms_p50``
    names its unit before the percentile — the explicit _LOWER entry);
    role sizes and transfer-volume tallies are config/workload, not
    perf."""
    for key in (
        "disagg_tokens_per_sec_per_chip",
        "disagg_baseline_tokens_per_sec_per_chip",
    ):
        assert bench_diff.classify_metric(key) == "higher", key
    for key in (
        "disagg_ttft_p50_ms",
        "disagg_ttft_p99_ms",
        "disagg_baseline_ttft_p50_ms",
        "disagg_baseline_ttft_p99_ms",
        "transfer_ms_p50",
    ):
        assert bench_diff.classify_metric(key) == "lower", key
    for key in (
        "disagg_requests",
        "disagg_slots",
        "disagg_lanes",
        "disagg_new_tokens",
        "disagg_transfer_handoffs",
        "disagg_transfer_pages",
        "disagg_transfer_bytes",
        "disagg_host_bounces",
        "disagg_generated_tokens",
    ):
        assert bench_diff.classify_metric(key) is None, key


def test_disagg_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key, direction in (
        ("disagg_tokens_per_sec_per_chip", "higher"),
        ("disagg_baseline_tokens_per_sec_per_chip", "higher"),
        ("disagg_ttft_p50_ms", "lower"),
        ("disagg_ttft_p99_ms", "lower"),
        ("disagg_baseline_ttft_p50_ms", "lower"),
        ("disagg_baseline_ttft_p99_ms", "lower"),
        ("transfer_ms_p50", "lower"),
    ):
        tol = TOLERANCES[key]
        sign = -1.0 if direction == "higher" else 1.0
        prev = {"metric": "x", key: 1.0}
        ok = compare({"metric": "x", key: 1.0 + sign * tol * 0.9}, prev)
        assert ok.ok, key
        bad = compare({"metric": "x", key: 1.0 + sign * tol * 1.5}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key


def test_fleet_era_keys_classify():
    """The §23 fleet-serving A/B keys gate direction-aware: both
    passes' aggregate tokens/s and the affinity speedup higher-better,
    the TTFT medians and the routing-decision latency lower-better
    (``fleet_route_ms_p50`` names its unit before the percentile —
    the explicit _LOWER entry, like ``transfer_ms_p50``); replica/
    session/turn counts, token budgets and the workload-determined
    hit rate are config, not perf (hit rate in particular ends in
    ``_rate`` — informational must win over the lower-better
    suffix)."""
    for key in (
        "fleet_tokens_per_sec",
        "fleet_rr_tokens_per_sec",
        "fleet_affinity_ttft_speedup",
    ):
        assert bench_diff.classify_metric(key) == "higher", key
    for key in (
        "fleet_warm_ttft_p50_ms",
        "fleet_rr_ttft_p50_ms",
        "fleet_cold_ttft_p50_ms",
        "fleet_route_ms_p50",
    ):
        assert bench_diff.classify_metric(key) == "lower", key
    for key in (
        "fleet_replicas",
        "fleet_sessions",
        "fleet_turns",
        "fleet_shared_tokens",
        "fleet_tail_tokens",
        "fleet_new_tokens",
        "fleet_affinity_hit_rate",
        "fleet_generated_tokens",
    ):
        assert bench_diff.classify_metric(key) is None, key


def test_fleet_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key, direction in (
        ("fleet_tokens_per_sec", "higher"),
        ("fleet_rr_tokens_per_sec", "higher"),
        ("fleet_affinity_ttft_speedup", "higher"),
        ("fleet_warm_ttft_p50_ms", "lower"),
        ("fleet_rr_ttft_p50_ms", "lower"),
        ("fleet_cold_ttft_p50_ms", "lower"),
        ("fleet_route_ms_p50", "lower"),
    ):
        tol = TOLERANCES[key]
        sign = -1.0 if direction == "higher" else 1.0
        prev = {"metric": "x", key: 1.0}
        ok = compare({"metric": "x", key: 1.0 + sign * tol * 0.9}, prev)
        assert ok.ok, key
        bad = compare({"metric": "x", key: 1.0 + sign * tol * 1.5}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key


def test_trace_slo_era_keys_classify():
    """The §24 guardrails A/B keys gate direction-aware: goodput and
    shed precision higher-better (precision has no suffix family —
    the explicit _HIGHER entry), the admitted p99 TTFT lower-better;
    the baseline pass exists to be WORSE under overload, so every
    ``trace_baseline_*`` key is informational along with the pinned
    workload shape and outcome tallies."""
    for key in (
        "trace_goodput_tokens_per_sec",
        "trace_shed_precision",
    ):
        assert bench_diff.classify_metric(key) == "higher", key
    assert bench_diff.classify_metric(
        "trace_admitted_ttft_p99_ms"
    ) == "lower"
    for key in (
        "trace_baseline_goodput_tokens_per_sec",
        "trace_baseline_admitted_ttft_p99_ms",
        "trace_baseline_deadline_expired",
        "trace_baseline_ok",
        "trace_requests",
        "trace_deadline_ms",
        "trace_shed_total",
        "trace_ok_total",
        "trace_deadline_expired",
    ):
        assert bench_diff.classify_metric(key) is None, key


def test_trace_slo_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key, direction in (
        ("trace_goodput_tokens_per_sec", "higher"),
        ("trace_shed_precision", "higher"),
        ("trace_admitted_ttft_p99_ms", "lower"),
    ):
        tol = TOLERANCES[key]
        sign = -1.0 if direction == "higher" else 1.0
        prev = {"metric": "x", key: 1.0}
        ok = compare({"metric": "x", key: 1.0 + sign * tol * 0.9}, prev)
        assert ok.ok, key
        # 1.2x tolerance keeps the bad value positive even for the
        # loose precision tolerance (a sign flip reads as drift).
        bad = compare({"metric": "x", key: 1.0 + sign * tol * 1.2}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key


def test_chunked_era_keys_classify():
    """The §25 chunked-prefill A/B keys gate direction-aware: the ITL
    improvement ratio and goodput higher-better (the ratio has no
    suffix family — the explicit _HIGHER entry), the chunked ITL/TTFT
    tails lower-better; the monolithic baseline pass exists to STALL,
    so every ``chunked_baseline_*`` key is informational along with
    the pinned workload shape (chunk size, long-prompt length/count,
    request and token tallies)."""
    for key in (
        "chunked_itl_improvement",
        "chunked_goodput_tokens_per_sec",
    ):
        assert bench_diff.classify_metric(key) == "higher", key
    for key in ("chunked_itl_p99_ms", "chunked_ttft_p99_ms"):
        assert bench_diff.classify_metric(key) == "lower", key
    for key in (
        "chunked_baseline_itl_p99_ms",
        "chunked_baseline_ttft_p99_ms",
        "chunked_baseline_goodput_tokens_per_sec",
        "chunked_chunk_tokens",
        "chunked_long_prompt_len",
        "chunked_long_arrivals",
        "chunked_requests",
        "chunked_generated_tokens",
    ):
        assert bench_diff.classify_metric(key) is None, key


def test_chunked_keys_gate_with_registered_tolerances():
    from tools.bench_diff import TOLERANCES, compare

    for key, direction in (
        ("chunked_itl_improvement", "higher"),
        ("chunked_goodput_tokens_per_sec", "higher"),
        ("chunked_itl_p99_ms", "lower"),
        ("chunked_ttft_p99_ms", "lower"),
    ):
        tol = TOLERANCES[key]
        sign = -1.0 if direction == "higher" else 1.0
        prev = {"metric": "x", key: 1.0}
        ok = compare({"metric": "x", key: 1.0 + sign * tol * 0.9}, prev)
        assert ok.ok, key
        bad = compare({"metric": "x", key: 1.0 + sign * tol * 1.2}, prev)
        assert not bad.ok and bad.regressions[0]["name"] == key
