"""Repo-root script contracts (bench.py): pure-logic checks that the
driver-facing entry points resolve their configuration correctly without
needing TPU hardware."""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _bench_attr(name):
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench

        return getattr(bench, name)
    finally:
        sys.path.pop(0)


def _resolve_bench_config():
    return _bench_attr("resolve_bench_config")


def test_bench_config_resolution():
    """bench.py's env-override resolution: the driver's default is the
    north-star config; overrides select other acceptance-config models,
    with binary_compute applied only where the model has the field."""
    resolve_bench_config = _resolve_bench_config()

    model, name, batch, bc, packres = resolve_bench_config(env={})
    assert (name, batch, bc) == ("QuickNetLarge", 128, "int8")
    assert model.compute_dtype == "bfloat16"
    assert packres is False

    model, name, batch, bc, packres = resolve_bench_config(
        env={
            "ZK_BENCH_MODEL": "ResNet50",
            "ZK_BENCH_BATCH": "256",
            # Requested but unsupported by the fp model: recorded as
            # NOT applied, so the bench output cannot claim a lever
            # that never ran.
            "ZK_BENCH_PACK_RESIDUALS": "1",
        }
    )
    assert (name, batch) == ("ResNet50", 256)
    assert bc is None  # fp model: no binary path field
    assert packres is False

    model, name, batch, bc, packres = resolve_bench_config(
        env={
            "ZK_BENCH_MODEL": "BinaryAlexNet",
            "ZK_BENCH_BINARY_COMPUTE": "mxu",
        }
    )
    assert (name, bc) == ("BinaryAlexNet", "mxu")

    # QuickNet supports the lever: requested -> applied and recorded.
    model, name, batch, bc, packres = resolve_bench_config(
        env={"ZK_BENCH_PACK_RESIDUALS": "1"}
    )
    assert packres is True
    assert model.pack_residuals is True

    with pytest.raises(ValueError, match="not in the zoo"):
        resolve_bench_config(env={"ZK_BENCH_MODEL": "NoSuchNet"})

    # Non-model module attributes (helper functions, the abstract base)
    # fail loudly at resolution, not with a confusing configure error.
    with pytest.raises(ValueError, match="not in the zoo"):
        resolve_bench_config(env={"ZK_BENCH_MODEL": "model_summary"})
    with pytest.raises(ValueError, match="abstract base"):
        resolve_bench_config(env={"ZK_BENCH_MODEL": "Model"})


def test_bench_platform_check(monkeypatch):
    """The platform is tpu unless the CPU was asked for: under an
    explicitly-requested cpu backend (the test env) the check passes;
    a cpu backend nobody requested is refused."""
    import jax

    check = _bench_attr("check_device_reachable")
    check()  # Raises on failure; returning is the pass.
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    asked = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="not 'tpu'"):
            check()
    finally:
        jax.config.update("jax_platforms", asked)


def test_bench_peak_resolution():
    """The MFU anchor: env override wins; a backend that is not a TPU
    has no anchor (it is never rated against the v5e's peak)."""
    resolve_peak_flops = _bench_attr("resolve_peak_flops")

    peak, source = resolve_peak_flops(env={"ZK_BENCH_PEAK_FLOPS": "9e13"})
    assert (peak, source) == (9e13, "env")

    peak, source = resolve_peak_flops(env={})
    # Tests force JAX_PLATFORMS=cpu: no TPU, no anchor.
    assert (peak, source) == (None, "unknown")


def test_bench_compiler_options_resolution():
    """ZK_BENCH_COMPILER_OPTIONS: unset -> None (default compile path);
    a JSON object passes through; non-object JSON is rejected loudly."""
    resolve = _bench_attr("resolve_compiler_options")

    assert resolve(env={}) is None
    assert resolve(env={"ZK_BENCH_COMPILER_OPTIONS": "  "}) is None

    opts = resolve(
        env={
            "ZK_BENCH_COMPILER_OPTIONS": (
                '{"xla_tpu_scoped_vmem_limit_kib": "65536"}'
            )
        }
    )
    assert opts == {"xla_tpu_scoped_vmem_limit_kib": "65536"}

    with pytest.raises(ValueError, match="JSON object"):
        resolve(env={"ZK_BENCH_COMPILER_OPTIONS": '["not", "a", "dict"]'})

    # Flag-syntax (non-JSON) input fails loudly, NAMING the env var —
    # not with a bare JSONDecodeError.
    with pytest.raises(
        ValueError, match="ZK_BENCH_COMPILER_OPTIONS is not valid JSON"
    ):
        resolve(
            env={
                "ZK_BENCH_COMPILER_OPTIONS": (
                    "xla_tpu_scoped_vmem_limit_kib=65536"
                )
            }
        )


def test_bench_peak_aggregation():
    """Agreement-gated median over independent peak attempts — the
    aggregator that replaced max-over-attempts after three fast-side
    failures (268 / 270 / 237.9 TF/s "measured" on a 197 TF/s v5e).
    Pinned off-chip with the observed failure shapes."""
    agg = _bench_attr("aggregate_peak_attempts")

    # Clean session: all attempts agree; median of the cluster.
    assert agg([190e12, 192e12, 189e12, 191e12]) == pytest.approx(
        190.5e12
    )

    # Cache-hit spike (the BENCH_r04 pathology): one above-physics fast
    # outlier must be EXCLUDED, not returned as the max.
    clean = agg([237.9e12, 191e12, 190e12, 192e12])
    assert clean == pytest.approx(191e12)

    # Jitter spike (slow-side outlier, the round-2 ~154 TF/s shape):
    # excluded the same way.
    assert agg([154e12, 190e12, 192e12, 191e12]) == pytest.approx(191e12)

    # Both failure shapes in one session.
    assert agg([154e12, 238e12, 190e12, 192e12]) == pytest.approx(191e12)

    # No two attempts agree: refuse to anchor rather than guess.
    with pytest.raises(ValueError, match="agree"):
        agg([100e12, 150e12, 238e12])

    # Fewer than two positive attempts: refuse.
    with pytest.raises(ValueError, match=">=2"):
        agg([190e12])
    with pytest.raises(ValueError, match=">=2"):
        agg([-1.0, 190e12])

    # Equal-size disjoint clusters (bimodal session): REFUSE — anchoring
    # on the slow cluster inflates MFU (the round-2 114 TF/s lesson),
    # the fast one risks the cache pathology. Neither is trustworthy.
    with pytest.raises(ValueError, match="ambiguous"):
        agg([150e12, 151e12, 237e12, 238e12])
    with pytest.raises(ValueError, match="ambiguous"):
        agg([154e12, 156e12, 190e12, 192e12])

    # But a mild outlier that merely OVERLAPS the clean cluster's band
    # (within tol of its max, not its min) is the same cluster shifted,
    # not a second mode — it must not veto three agreeing attempts.
    assert agg([190e12, 191e12, 192e12, 199.6e12]) == pytest.approx(
        191e12
    )


def test_bench_peak_datasheet_clamp():
    """Generation-specific clamp: a measured peak above ~1.05x the
    datasheet number for the detected device_kind is a measurement
    failure; unknown generations must pass (stale table vs future
    chip)."""
    sheet = _bench_attr("datasheet_bf16_peak")
    check = _bench_attr("check_peak_against_datasheet")

    assert sheet("TPU v5 lite") == pytest.approx(197e12)
    assert sheet("TPU v5e") == pytest.approx(197e12)
    assert sheet("TPU v5p") == pytest.approx(459e12)  # "v5 lite" must not
    assert sheet("TPU v4") == pytest.approx(275e12)
    assert sheet("TPU v6 lite") == pytest.approx(918e12)
    assert sheet("some future chip") is None
    assert sheet(None) is None

    # The exact BENCH_r04 defect: 237.9 TF/s on a v5e must raise.
    with pytest.raises(ValueError, match="datasheet"):
        check(237.9e12, "TPU v5 lite")
    # In-band measurements pass, including slightly above datasheet
    # (within headroom) and legitimately degraded ones.
    check(192.5e12, "TPU v5 lite")
    check(200e12, "TPU v5 lite")
    check(154e12, "TPU v5 lite")
    # Unknown generation: no clamp.
    check(2e15, "TPU v9 hyperlite")


def test_bench_int8_peak_resolution():
    """The second MFU anchor (int8 MXU): env override wins; a backend
    that is not a TPU has no anchor."""
    resolve = _bench_attr("resolve_int8_peak")

    peak, source = resolve(env={"ZK_BENCH_INT8_PEAK_FLOPS": "3.9e14"})
    assert (peak, source) == (3.9e14, "env")

    peak, source = resolve(env={})
    # Tests force JAX_PLATFORMS=cpu: no TPU, no anchor.
    assert (peak, source) == (None, "unknown")
    # The recorded v5e measurement sits below the physical 2x-bf16
    # ceiling.
    assert _bench_attr("INT8_PEAK_FALLBACK") < 2.0 * 197e12


def test_lm_bench_records_flash_blocks_and_sp_degree():
    """The LM leg's bench JSON carries the auto-selected flash block
    sizes (so a flash-policy regression moves a driver-visible number,
    not just the step time) — computed by the same head_dim/VMEM-aware
    policy the compiled step uses, at the leg's bf16 operands."""
    lm_bench_flash_blocks = _bench_attr("lm_bench_flash_blocks")

    # Pinned config (d512/h8 -> head_dim 64, bf16): the measured sweep
    # winner at every power-of-two length.
    assert lm_bench_flash_blocks(8192) == (1024, 1024)
    assert lm_bench_flash_blocks(2048) == (1024, 1024)
    # Awkward lengths fall back exactly like the kernel's policy...
    assert lm_bench_flash_blocks(1100) == (128, 128)
    # ...and extreme head dims demote via the VMEM filter.
    bq, bk = lm_bench_flash_blocks(8192, d_model=4096, num_heads=1,
                                   itemsize=4)
    assert bq == bk and bq < 1024


def test_sp_bench_env_knobs_validate():
    """The SP A/B leg fails fast on an invalid flavor (before any
    multi-device compile)."""
    import pytest

    measure = _bench_attr("measure_sp_ring_throughput")
    with pytest.raises(ValueError, match="ZK_BENCH_SP_FLAVOR"):
        measure(env={"ZK_BENCH_SP_FLAVOR": "dense"})
