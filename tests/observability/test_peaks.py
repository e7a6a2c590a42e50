"""Peak anchors: live-gauge peak resolution (env > recorded v5e >
datasheet-scaled; a device in no table row has NO anchor) and its
agreement-by-construction with bench.py's offline anchors
(docs/DESIGN.md §14)."""

import pytest

import bench
from zookeeper_tpu.observability import peaks


def test_bench_reexports_the_shared_tables():
    """bench.py and the live gauges must divide by the SAME anchors —
    identity, not equality, so a future edit cannot fork them."""
    assert bench.aggregate_peak_attempts is peaks.aggregate_peak_attempts
    assert (
        bench.check_peak_against_datasheet
        is peaks.check_peak_against_datasheet
    )
    assert bench.datasheet_bf16_peak is peaks.datasheet_bf16_peak
    assert (
        bench.TPU_DATASHEET_BF16_TFLOPS is peaks.TPU_DATASHEET_BF16_TFLOPS
    )
    assert bench.TPU_INT8_FACTOR is peaks.TPU_INT8_FACTOR
    assert bench.BF16_PEAK_FALLBACK == peaks.BF16_PEAK_FALLBACK
    assert bench.INT8_PEAK_FALLBACK == peaks.INT8_PEAK_FALLBACK


def test_reference_peak_env_override_wins():
    value, source = peaks.reference_peak_flops(
        "TPU v5 lite", env={"ZK_BENCH_PEAK_FLOPS": "123e12"}
    )
    assert value == 123e12
    assert source == "env"


def test_reference_peak_bad_env_override_is_ignored():
    # The override resolves inside hot-path gauge updates: a typo'd
    # export must fall through to the device anchor, never raise or
    # poison the gauge with nan/inf.
    for bad in ("garbage", "-1", "0", "nan", "inf", "-inf"):
        value, source = peaks.reference_peak_flops(
            "TPU v5 lite", env={"ZK_BENCH_PEAK_FLOPS": bad}
        )
        assert source == "v5e_measured", bad
        assert value == peaks.BF16_PEAK_FALLBACK, bad
        value, source = peaks.reference_int8_peak_flops(
            "TPU v5 lite", env={"ZK_BENCH_INT8_PEAK_FLOPS": bad}
        )
        assert source == "v5e_measured", bad
        assert value == peaks.INT8_PEAK_FALLBACK, bad


def test_reference_peak_v5e_uses_recorded_measurement():
    value, source = peaks.reference_peak_flops("TPU v5 lite", env={})
    assert value == peaks.BF16_PEAK_FALLBACK
    assert source == "v5e_measured"


def test_reference_peak_other_generations_scale_datasheet():
    value, source = peaks.reference_peak_flops("TPU v4", env={})
    assert value == pytest.approx(peaks.ACHIEVABLE_FRACTION * 275e12)
    assert source == "datasheet_scaled"


def test_unknown_device_has_no_anchor():
    """A device_kind in no table row (a future chip, the CPU backend)
    is never rated against the v5e's peaks: every resolver returns
    (None, "unknown"), which ledger.mfu/mbu map to the gauges' -1."""
    from zookeeper_tpu.observability.ledger import mbu, mfu

    for kind in ("TPU v99", "cpu", "FutureChip 9"):
        for resolve in (
            peaks.reference_peak_flops,
            peaks.reference_int8_peak_flops,
            peaks.reference_hbm_bandwidth,
        ):
            assert resolve(kind, env={}) == (None, "unknown"), (kind, resolve)
    assert mfu(1e12, 0.01, peaks.reference_peak_flops("cpu", env={})[0]) is None
    assert mbu(1e9, 0.01, peaks.reference_hbm_bandwidth("cpu", env={})[0]) is None


def test_reference_peak_total_without_device_kind():
    """Resolution stays total when device_kind is None (it asks jax;
    under the CPU test backend that is an unknown device): a live gauge
    update can never raise."""
    value, source = peaks.reference_peak_flops(None, env={})
    assert (value is None or value > 0) and isinstance(source, str)


def test_reference_int8_peak_factors_by_generation():
    # v4 has no int8 MXU doubling: the int8 anchor IS the bf16 one.
    v4, src4 = peaks.reference_int8_peak_flops("TPU v4", env={})
    assert v4 == pytest.approx(peaks.ACHIEVABLE_FRACTION * 1.0 * 275e12)
    assert src4 == "datasheet_scaled"
    # v5e: the recorded on-chip int8 measurement.
    v5e, src5 = peaks.reference_int8_peak_flops("TPU v5e", env={})
    assert v5e == peaks.INT8_PEAK_FALLBACK
    assert src5 == "v5e_measured"
    # env override wins here too.
    v, s = peaks.reference_int8_peak_flops(
        "TPU v4", env={"ZK_BENCH_INT8_PEAK_FLOPS": "9e12"}
    )
    assert (v, s) == (9e12, "env")


def test_live_anchor_agrees_with_bench_fallback_path():
    """The 10% live-vs-offline agreement contract's anchor half: on a
    v5e, the live reference equals bench's measured-peak fallback
    EXACTLY; on other generations both sides apply the same 0.93x
    datasheet prior, so the anchors are identical by construction."""
    for kind in ("TPU v5 lite", "TPU v4", "TPU v5p", "TPU v6e"):
        live, _ = peaks.reference_peak_flops(kind, env={})
        sheet = peaks.datasheet_bf16_peak(kind)
        offline = (
            peaks.BF16_PEAK_FALLBACK
            if peaks.datasheet_match(kind)[0] in peaks.V5E_KEYS
            else peaks.ACHIEVABLE_FRACTION * sheet
        )
        assert live == pytest.approx(offline)


# -- HBM bandwidth anchors (the decode MBU roofline, DESIGN.md §17) -------


def test_reference_hbm_bandwidth_env_override_wins():
    value, source = peaks.reference_hbm_bandwidth(
        "TPU v5e", env={"ZK_BENCH_HBM_BANDWIDTH": "1.0e12"}
    )
    assert (value, source) == (1.0e12, "env")


def test_reference_hbm_bandwidth_datasheet_by_generation():
    for kind, gbps in (
        ("TPU v5 lite", 819.0),
        ("TPU v4", 1228.0),
        ("TPU v5p", 2765.0),
        ("TPU v6e", 1640.0),
    ):
        value, source = peaks.reference_hbm_bandwidth(kind, env={})
        assert value == pytest.approx(gbps * 1e9)
        assert source == "datasheet"


def test_reference_hbm_bandwidth_malformed_env_ignored(caplog):
    import logging

    with caplog.at_level(logging.WARNING):
        value, source = peaks.reference_hbm_bandwidth(
            "TPU v5e", env={"ZK_BENCH_HBM_BANDWIDTH": "fast"}
        )
    assert source == "datasheet"  # the override was warn-and-ignored
    assert any("ZK_BENCH_HBM_BANDWIDTH" in r.message for r in caplog.records)


def test_mbu_totality_and_value():
    from zookeeper_tpu.observability.ledger import mbu

    assert mbu(819e9, 1.0, 819e9) == pytest.approx(1.0)
    assert mbu(40.95e9, 0.1, 819e9) == pytest.approx(0.5)
    # Unknown bytes / zero time / missing bandwidth -> None (the gauge
    # publishes -1), never a raise.
    assert mbu(None, 0.01, 819e9) is None
    assert mbu(1e9, 0.0, 819e9) is None
    assert mbu(1e9, 0.01, None) is None
    assert mbu(-5.0, 0.01, 819e9) is None
