"""Benchmark: the north-star workload on real hardware.

Trains **QuickNet-Large at ImageNet shapes** (224x224x3, 1000 classes,
bf16 stem/BN with the binary convs on the int8 MXU path — bit-exact vs
bf16, 2x MXU peak; BASELINE.json's primary metric) and prints ONE JSON
line:

    {"metric", "value", "unit", "vs_baseline", ...extras}

``value`` is measured images/sec/chip for the full jitted train step
(fwd + bwd + Adam + BN, input resident in HBM — compute-bound number; the
host-pipeline overhead is profiled separately in BASELINE.md).

``vs_baseline`` is **MFU**: model FLOPs utilization against the bf16 MXU
peak MEASURED ON THIS CHIP at bench time (4096^3 matmul chain,
BASELINE.md methodology; ``ZK_BENCH_PEAK_FLOPS`` overrides, and the
recorded v5e 184 TFLOP/s is the non-TPU fallback) — a defensible
external anchor (1.0 = hardware roofline) that stays honest on any TPU
generation. The anchor deliberately stays the bf16 peak even though the
binary convs run int8 (whose ceiling is higher), so the number is
conservative. Model FLOPs are taken from XLA's own cost analysis of the
compiled step, so they track the real model, not a hand count.
"""

import json
import os
import sys
import time

#: Version of the BENCH/MULTICHIP JSON contract. Bump when a metric is
#: renamed/removed or its units change, so the perf-trajectory tooling
#: reading BENCH_r*.json can tell a schema break from a regression.
BENCH_SCHEMA_VERSION = 1


def bench_metadata(device_kind=None):
    """Self-describing provenance block stamped into every BENCH /
    MULTICHIP JSON artifact: the git sha + dirty flag say WHICH code
    produced the number, jax version + device kind say on WHAT, and the
    schema version says how to read the keys — so a bench line is
    interpretable years later without the surrounding driver log.
    Every field degrades to a sentinel rather than raising: metadata
    must never be the reason a bench run dies."""
    import subprocess

    meta = {"bench_schema_version": BENCH_SCHEMA_VERSION}
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        ).stdout.strip()
        meta["git_sha"] = sha or "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        ).stdout.strip()
        meta["git_dirty"] = bool(dirty)
    except Exception:
        meta["git_sha"] = "unknown"
        # Unknown provenance must not read as a certified-clean build.
        meta["git_dirty"] = True
    try:
        import jax

        meta["jax_version"] = jax.__version__
        meta["platform"] = jax.devices()[0].platform
        if device_kind is None:
            device_kind = jax.devices()[0].device_kind
    except Exception:
        meta["jax_version"] = "unknown"
    if device_kind is not None:
        meta["device_kind"] = device_kind
    return meta


# The peak-anchor machinery (datasheet tables, the measured-peak
# agreement gate, the datasheet clamp) moved to
# ``zookeeper_tpu.observability.peaks`` so the LIVE MFU gauge
# (``zk_serve_mfu``, docs/DESIGN.md §14) and this
# bench divide by the same anchors; re-exported here unchanged (sweep
# scripts and tests import them as ``bench.*``).
from zookeeper_tpu.observability.peaks import (  # noqa: E402,F401
    ACHIEVABLE_FRACTION,
    BF16_PEAK_FALLBACK,
    DATASHEET_HEADROOM,
    INT8_FACTOR_UPPER_BOUND,
    INT8_PEAK_FALLBACK,
    TPU_DATASHEET_BF16_TFLOPS,
    TPU_INT8_FACTOR,
    V5E_KEYS as _V5E_KEYS,
    aggregate_peak_attempts,
    check_peak_against_datasheet,
    datasheet_bf16_peak,
    datasheet_match as _datasheet_match,
)

# The shared cost-analysis wrapper (ONE call site family across
# summary/ledger/engine/bench — tolerant of None/[dict]/missing keys).
from zookeeper_tpu.observability.ledger import cost_flops  # noqa: E402

# Canonical implementation lives in the library so bench.py and
# measure_fused_loop_time share one copy; re-exported here because the
# sweep scripts import it as ``bench.time_marginal``.
from zookeeper_tpu.training.benchmark import time_marginal  # noqa: E402


def measure_bf16_peak(rounds: int = 4, n_attempts: int = 4) -> float:
    """Measure this chip's achievable bf16 matmul peak (FLOP/s) with the
    BASELINE.md methodology: a 4096^3 matmul iterated in an on-device
    ``fori_loop`` with a data dependency (each iterate feeds the next, the
    final sum is read back — XLA can neither hoist nor dead-code-eliminate
    the chain), marginal over two chain lengths so the fixed per-chain
    sync latency cancels, min over ``rounds`` per attempt.

    ``n_attempts`` independent attempts are combined by
    ``aggregate_peak_attempts`` (agreement-gated median — see its
    docstring for why max-over-attempts is dead), then the result is
    clamped against the device generation's datasheet band
    (``check_peak_against_datasheet``).

    Raises ValueError when the measurement is implausible (no agreement,
    inverted marginals, or above the datasheet band), so
    ``resolve_peak_flops`` retries/falls back instead of recording
    garbage as "measured"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = 4096
    # System-entropy seed: requests must be unique ACROSS RUNS, not
    # just within one. With a fixed seed, every bench invocation
    # replays bit-identical (matrix, salt) requests, which a cache
    # between the host and the device can answer — observed as an
    # above-physics 270 TF/s "measured" peak (the very pathology the
    # within-run salt fixed; the salts themselves cannot carry
    # run-uniqueness because bf16 rounding collapses large salt bases
    # to identical operands). An UNSEEDED generator pulls fresh OS
    # entropy; fresh normal matrices keep the measurement
    # statistically identical.
    rng = np.random.default_rng()
    a = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)

    from functools import partial

    @partial(jax.jit, static_argnums=2)
    def chain(x, salt, iters):
        # ``salt`` makes every invocation a DISTINCT computation: a
        # fast-above-physics 268 TF/s reading showed that repeating the
        # bit-identical request can be served from a cache on the way
        # to the device. The add is one elementwise op against `iters`
        # matmuls.
        x = x + salt

        def body(_, x):
            # 1/64 epilogue scale keeps iterates O(1) (row norms grow by
            # ~sqrt(n)*sigma per matmul); fuses into the matmul.
            return (x @ a) * (1.0 / 64.0)

        return jax.lax.fori_loop(0, iters, body, x).sum()

    x0 = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)
    # 200 marginal matmuls ~ 150 ms of MXU work: the old (20, 60)
    # chains left the ~30 ms marginal inside one host-jitter spike,
    # which once passed a degraded 114 TF/s through the (generation-
    # agnostic, so necessarily wide) plausibility window and inflated
    # that run's MFU.
    n1, n2 = 100, 300
    salt = iter(range(1, 10_000))

    def run_chain(iters):
        s = jnp.bfloat16(next(salt) * 1e-6)
        t0 = time.perf_counter()
        float(jax.device_get(chain(x0, s, iters)))
        return time.perf_counter() - t0

    run_chain(n1)  # Warm both compiles.
    run_chain(n2)
    attempts = []
    for _ in range(n_attempts):
        per_matmul = time_marginal(run_chain, n1, n2, rounds)
        if per_matmul > 0:
            attempts.append(2.0 * n**3 / per_matmul)
    peak = aggregate_peak_attempts(attempts)
    # Plausibility window wide enough for any current/near TPU generation
    # (v2 ~45 bf16 TFLOP/s ... future ~2 PFLOP/s); outside it the number
    # is measurement failure, not hardware.
    if not 1e13 <= peak <= 2e15:
        raise ValueError(f"implausible measured peak {peak:.3g} FLOP/s")
    # Generation-specific clamp: the generic window above cannot catch a
    # 1.2x cache-replay error (BENCH_r04: 237.9 TF/s on a 197 TF/s v5e);
    # the datasheet can.
    check_peak_against_datasheet(peak, jax.devices()[0].device_kind)
    return peak


def measure_int8_peak(rounds: int = 4, n_attempts: int = 4) -> float:
    """Measure this chip's achievable int8 MXU peak (OP/s), same
    protocol as :func:`measure_bf16_peak` (fori_loop chain, marginal
    timing, agreement-gated attempts, datasheet clamp) with int8
    operands kept PRE-CAST: the only in-loop non-matmul work is an
    elementwise int32->int8 squeeze (4096^2 elements against 2*4096^3
    MACs), so the 2x MXU rate is actually observable — round 2's
    177 TOP/s reading carried an in-loop bf16 cast that halved it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = 4096
    rng = np.random.default_rng()  # OS entropy: run-unique requests
    a = jnp.asarray(rng.integers(-127, 128, size=(n, n)), jnp.int8)

    from functools import partial

    @partial(jax.jit, static_argnums=2)
    def chain(x, salt, iters):
        x = x + salt  # distinct request per call (cache-replay guard)

        def body(_, x):
            y = jax.lax.dot_general(
                x, a, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            # Values wrap; only the data dependency matters. >>7 keeps
            # magnitudes spread (each dot sums 4096 +-127^2 terms).
            return (y >> 7).astype(jnp.int8)

        return jax.lax.fori_loop(0, iters, body, x).astype(jnp.int32).sum()

    x0 = jnp.asarray(rng.integers(-127, 128, size=(n, n)), jnp.int8)
    n1, n2 = 100, 300
    salt = iter(range(1, 10_000))

    def run_chain(iters):
        # int8 can hold only 256 salt values; % 251 - 125 keeps every
        # in-run request distinct for far more calls than a measurement
        # makes (~34). Repeating a bit-identical request is exactly the
        # cache-replay pathology the salts exist to kill.
        s = jnp.int8(next(salt) % 251 - 125)
        t0 = time.perf_counter()
        int(jax.device_get(chain(x0, s, iters)))
        return time.perf_counter() - t0

    run_chain(n1)  # Warm both compiles.
    run_chain(n2)
    attempts = []
    for _ in range(n_attempts):
        per_matmul = time_marginal(run_chain, n1, n2, rounds)
        if per_matmul > 0:
            attempts.append(2.0 * n**3 / per_matmul)
    peak = aggregate_peak_attempts(attempts)
    if not 1e13 <= peak <= 4e15:
        raise ValueError(f"implausible measured int8 peak {peak:.3g} OP/s")
    match = _datasheet_match(jax.devices()[0].device_kind)
    if match is not None:
        factor = TPU_INT8_FACTOR.get(match[0], INT8_FACTOR_UPPER_BOUND)
        ceiling = DATASHEET_HEADROOM * factor * match[1]
        if peak > ceiling:
            raise ValueError(
                f"measured int8 peak {peak / 1e12:.1f} TOP/s exceeds "
                f"{factor:.0f}x the bf16 datasheet "
                f"({match[1] / 1e12:.0f} TF/s) — measurement failure, "
                "not hardware"
            )
    return peak


def _resolve_measured_anchor(
    env, env_var, measure, recorded_v5e, datasheet_scale, unit
):
    """Shared anchor-resolution harness (both anchors MUST stay
    mechanically identical — a divergence in one produced the round-4
    defect): ``env_var`` override > on-chip measurement with one retry
    (each attempt pulls fresh OS entropy) > for a v5e, its recorded
    measurement > for another KNOWN generation,
    ``datasheet_scale(bf16_sheet_flops, table_key)`` (v5e's
    0.93x-of-datasheet achievable fraction is the transfer prior).
    Returns ``(peak_flops, source_tag)``; a backend that is not a TPU,
    or a TPU in no table row, has no anchor: ``(None, "unknown")``."""
    import jax

    env = os.environ if env is None else env
    override = env.get(env_var)
    if override:
        return float(override), "env"
    if jax.default_backend() != "tpu":
        return None, "unknown"
    last_err = None
    for _ in range(2):
        try:
            return measure(), "measured"
        except Exception as e:
            last_err = e
    match = _datasheet_match(jax.devices()[0].device_kind)
    # Matched by table KEY, not by datasheet value (float identity
    # would drift if an entry were corrected).
    if match is None:
        anchor = (None, "unknown")
    elif match[0] in _V5E_KEYS:
        anchor = (recorded_v5e, "v5e_recorded")
    else:
        anchor = (datasheet_scale(match[1], match[0]), "fallback_datasheet")
    print(
        f"on-chip peak measurement failed twice ({last_err}); "
        f"using the {anchor[1]} anchor"
        + (f" ({anchor[0] / 1e12:.1f} {unit})" if anchor[0] else ""),
        file=sys.stderr,
        flush=True,
    )
    return anchor


def resolve_peak_flops(env=None):
    """The MFU anchor's bf16 peak — see ``_resolve_measured_anchor``
    for the priority order (``ZK_BENCH_PEAK_FLOPS`` is the override)."""
    return _resolve_measured_anchor(
        env,
        "ZK_BENCH_PEAK_FLOPS",
        measure_bf16_peak,
        BF16_PEAK_FALLBACK,
        lambda sheet, key: ACHIEVABLE_FRACTION * sheet,
        "TF/s",
    )


def resolve_int8_peak(env=None):
    """The int8-MXU anchor — same harness as :func:`resolve_peak_flops`
    (``ZK_BENCH_INT8_PEAK_FLOPS`` overrides); the datasheet fallback
    scales by the generation's measured int8-over-bf16 factor (1x on
    v2-v4, which have no int8 MXU doubling)."""
    return _resolve_measured_anchor(
        env,
        "ZK_BENCH_INT8_PEAK_FLOPS",
        measure_int8_peak,
        INT8_PEAK_FALLBACK,
        lambda sheet, key: (
            ACHIEVABLE_FRACTION * TPU_INT8_FACTOR.get(key, 1.0) * sheet
        ),
        "TOP/s",
    )


def resolve_bench_config(env=None):
    """Bench workload from ZK_BENCH_* env overrides. The default (no
    overrides) is the north-star config the driver runs: QuickNet-Large,
    batch 128, int8 binary convs (BASELINE.md round-3 sweep: the per-chip
    sweet spot — 75% MFU vs 64% for batch-256 bf16-mxu; int8 is bit-exact
    vs the mxu path, so this changes nothing but speed). Overrides record
    the other acceptance configs (ResNet50 bf16 — BASELINE config #5,
    BinaryAlexNet — config #2) with the same harness.

    Returns ``(model, model_name, batch_size, binary_compute,
    pack_residuals)`` with the model configured; ``binary_compute`` is
    None for fp models (no binary path to select), and
    ``pack_residuals`` records whether the 1-bit residual lever was
    actually applied (requested AND supported by the model).
    """
    from zookeeper_tpu import models as zoo
    from zookeeper_tpu.core import configure

    env = os.environ if env is None else env
    model_name = env.get("ZK_BENCH_MODEL", "QuickNetLarge")
    batch_size = int(env.get("ZK_BENCH_BATCH", "128"))
    binary_compute = env.get("ZK_BENCH_BINARY_COMPUTE", "int8")

    from zookeeper_tpu.models import Model

    model_cls = getattr(zoo, model_name, None)
    if not (isinstance(model_cls, type) and issubclass(model_cls, Model)):
        # Base-class helpers and functions live on the module too; only
        # concrete Model subclasses are benchable.
        raise ValueError(f"ZK_BENCH_MODEL={model_name!r} is not in the zoo.")
    if model_cls is Model:
        raise ValueError(
            "ZK_BENCH_MODEL=Model is the abstract base, not a zoo model."
        )
    model = model_cls()
    conf = {"compute_dtype": "bfloat16"}
    if "binary_compute" in type(model).__component_fields__:
        conf["binary_compute"] = binary_compute
    else:
        binary_compute = None
    pack_residuals = (
        _env_flag(env, "ZK_BENCH_PACK_RESIDUALS")
        and "pack_residuals" in type(model).__component_fields__
    )
    if pack_residuals:
        conf["pack_residuals"] = True
    configure(model, conf, name="model")
    return model, model_name, batch_size, binary_compute, pack_residuals


def _env_flag(env, name: str, default: str = "0") -> bool:
    return env.get(name, default).strip().lower() not in ("0", "", "false")


def resolve_compiler_options(env=None):
    """``ZK_BENCH_COMPILER_OPTIONS``: a JSON object of XLA compiler
    options applied to the train-step compile (e.g.
    ``{"xla_tpu_scoped_vmem_limit_kib": "65536"}``): per-compile
    options travel with the computation, without touching the process's
    ``XLA_FLAGS``. Returns None when unset so the default compile path
    is untouched."""
    env = os.environ if env is None else env
    raw = env.get("ZK_BENCH_COMPILER_OPTIONS", "").strip()
    if not raw:
        return None
    try:
        opts = json.loads(raw)
    except json.JSONDecodeError as e:
        raise ValueError(
            f"ZK_BENCH_COMPILER_OPTIONS is not valid JSON ({e}); expected "
            'an object like {"xla_tpu_scoped_vmem_limit_kib": "65536"}'
        ) from None
    if not isinstance(opts, dict):
        raise ValueError(
            "ZK_BENCH_COMPILER_OPTIONS must be a JSON object of "
            f"option-name -> value, got {type(opts).__name__}"
        )
    return opts


def measure_host_aug_throughput(env=None):
    """Host input-pipeline leg (no accelerator involved): augmented
    batch-assembly throughput of the fused native kernel
    (``native.gather_augment_normalize`` through the real
    ``batch_iterator`` fast path) vs the per-example Python reference,
    at the north-star recipe (RandomResizedCrop ``src``->``out``,
    flip, zero-center — the path every real ImageNet-recipe run takes).

    Reported PER CORE so the number is host-size-independent and
    comparable round over round (BASELINE.md's 3,781 un-augmented /
    586 augmented-python img/s/core table): the Python path runs
    single-threaded (rate == rate/core), the native kernel fans out
    across every core (rate / cpu_count). The two paths produce
    bit-identical batches (shared counter RNG), so this is a pure
    like-for-like speed comparison.

    Knobs: ``ZK_BENCH_HOST_AUG_SRC`` / ``_OUT`` (source/output side,
    default 256->224), ``ZK_BENCH_HOST_AUG_EXAMPLES`` (store rows).
    """
    import numpy as np

    from zookeeper_tpu import native
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import (
        ArraySource,
        ImageClassificationPreprocessing,
        batch_iterator,
    )

    env = os.environ if env is None else env
    src_side = int(env.get("ZK_BENCH_HOST_AUG_SRC", "256"))
    out_side = int(env.get("ZK_BENCH_HOST_AUG_OUT", "224"))
    n = int(env.get("ZK_BENCH_HOST_AUG_EXAMPLES", "512"))
    batch = min(128, n)
    rng = np.random.default_rng(0)
    source = ArraySource(
        {
            "image": rng.integers(
                0, 256, size=(n, src_side, src_side, 3), dtype=np.uint8
            ),
            "label": rng.integers(0, 1000, size=(n,)).astype(np.int64),
        }
    )
    conf = {
        "height": out_side, "width": out_side, "channels": 3,
        "augment": True, "random_resized_crop": True,
    }

    def rate(force_python, min_images, min_seconds=0.4):
        pre = ImageClassificationPreprocessing()
        configure(pre, conf, name=f"host_aug_{force_python}")
        if force_python:
            object.__setattr__(
                pre, "native_batch_spec", lambda training: None
            )
        images = 0
        epoch = 0
        t0 = time.perf_counter()
        # Epochs until both floors are met: enough images for the rate
        # to be meaningful AND enough wall time to dominate overhead.
        while True:
            for b in batch_iterator(
                source, pre, batch,
                training=True, shuffle=True, seed=0, epoch=epoch,
            ):
                images += len(b["target"])
                elapsed = time.perf_counter() - t0
                if images >= min_images and elapsed >= min_seconds:
                    return images / elapsed
            epoch += 1

    cores = os.cpu_count() or 1
    # The kernel fans out at most one thread per example: on a host
    # with more cores than the batch size, dividing by cpu_count would
    # understate the per-core rate (cores the kernel never used).
    workers = min(cores, batch)
    native_ok = native.available()
    metrics = {
        "host_cores": cores,
        "host_aug_native_available": native_ok,
    }
    py_rate = rate(True, min_images=batch)
    metrics["host_aug_python_images_per_sec_per_core"] = round(py_rate, 1)
    if native_ok:
        native_rate = rate(False, min_images=4 * batch)
        metrics["host_aug_images_per_sec_per_core"] = round(
            native_rate / workers, 1
        )
        metrics["host_aug_native_speedup_per_core"] = round(
            native_rate / workers / py_rate, 2
        )
    return metrics


def measure_recovery_leg(env=None):
    """Always-on recovery leg: time from supervisor restart to the
    first post-resume train step (``recovery_restore_ms``) — the
    recovery-time number docs/DESIGN.md §10 budgets against, measured
    by actually walking the kill->save->restart->restore path on a
    tiny synthetic experiment (seconds on any backend; the checkpoint
    machinery exercised is byte-for-byte the production path)."""
    import shutil
    import tempfile

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.resilience import measure_recovery_restore_ms
    from zookeeper_tpu.training import TrainingExperiment

    tmp = tempfile.mkdtemp(prefix="zk_bench_recovery_")

    def make_experiment():
        exp = TrainingExperiment()
        configure(
            exp,
            {
                "loader.dataset": "SyntheticMnist",
                "loader.dataset.num_train_examples": 128,
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
                "checkpointer.directory": os.path.join(tmp, "ckpt"),
                "checkpointer.synchronous": True,
                "checkpointer.save_every_epochs": 0,
            },
            name="bench_recovery",
        )
        return exp

    try:
        return measure_recovery_restore_ms(make_experiment, kill_at_step=2)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_shed_overload(env=None):
    """``ZK_BENCH_SHED=1`` leg: drive the async MicroBatcher into
    deliberate overload (submits as fast as Python can issue them
    against a bounded ``shed_above_rows`` queue) and report the shed
    rate plus served-request latency percentiles — the load-shedding
    posture under pressure, through the REAL serving path (engine
    dispatch + worker thread + metrics). Knobs:
    ``ZK_BENCH_SHED_REQUESTS`` (default 400), ``ZK_BENCH_SHED_ROWS``
    (queue threshold, default 64)."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models.simple import Mlp
    from zookeeper_tpu.serving import (
        InferenceEngine,
        MicroBatcher,
        RejectedError,
        ServingMetrics,
    )

    env = os.environ if env is None else env
    n_requests = int(env.get("ZK_BENCH_SHED_REQUESTS", "400"))
    shed_rows = int(env.get("ZK_BENCH_SHED_ROWS", "64"))

    model = Mlp()
    configure(model, {"hidden_units": (64,)}, name="shed_model")
    module = model.build((32,), 10)
    params, model_state = model.initialize(module, (32,))
    engine = InferenceEngine()
    configure(engine, {"batch_buckets": (8, 32)}, name="shed_engine")
    engine.bind(module.apply, params, model_state, (32,))
    engine.warmup()
    metrics = ServingMetrics()
    configure(metrics, {}, name="shed_metrics")
    batcher = MicroBatcher()
    configure(
        batcher,
        {
            "synchronous": False,
            "max_delay_ms": 0.5,
            "shed_above_rows": shed_rows,
        },
        name="shed_batcher",
    )
    batcher.bind(engine, metrics=metrics)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 32)).astype(np.float32)
    handles, shed = [], 0
    try:
        for _ in range(n_requests):
            try:
                handles.append(batcher.submit(x))
            except RejectedError:
                shed += 1
        for h in handles:
            h.result(timeout=120)
    finally:
        batcher.close()
    snap = metrics.snapshot()
    return {
        "shed_requests": n_requests,
        "shed_queue_rows": shed_rows,
        "shed_rate": round(shed / max(1, n_requests), 4),
        "shed_p50_ms": round(snap.get("latency_p50_ms", 0.0), 3),
        "shed_p99_ms": round(snap.get("latency_p99_ms", 0.0), 3),
    }


def measure_checkpoint_stall(env=None):
    """``ZK_BENCH_CKPT=1`` leg: the training-thread cost of a
    checkpoint save, sync vs async, at the same cadence — the number
    the async checkpointer exists to move (docs/DESIGN.md §12). Both
    modes drive the REAL Checkpointer over a real jitted train step:

    - ``ckpt_sync_save_stall_ms``: full blocking serialize+write on the
      training thread (``mode="sync"``, orbax-synchronous).
    - ``ckpt_async_save_stall_ms``: device→host snapshot + queue
      hand-off only (``mode="async"``); the write overlaps the steps
      that follow.
    - ``ckpt_steps_overlapped_per_save``: train steps that completed
      while the async write was still in flight — the work a sync save
      would have stalled.

    Knobs: ``ZK_BENCH_CKPT_HIDDEN`` (Mlp width, default 512 — ~1.2M
    params so the serialize cost is visible), ``ZK_BENCH_CKPT_SAVES``
    (timed saves per mode, default 5)."""
    import shutil
    import tempfile
    import time

    import jax
    import numpy as np
    import optax

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models.simple import Mlp
    from zookeeper_tpu.training import (
        Checkpointer,
        TrainState,
        make_train_step,
    )

    env = os.environ if env is None else env
    hidden = int(env.get("ZK_BENCH_CKPT_HIDDEN", "512"))
    saves = int(env.get("ZK_BENCH_CKPT_SAVES", "5"))

    model = Mlp()
    configure(
        model, {"hidden_units": (hidden, hidden)}, name="ckpt_bench_model"
    )
    module = model.build((28, 28, 1), 10)
    params, model_state = model.initialize(module, (28, 28, 1))
    state0 = TrainState.create(
        apply_fn=module.apply,
        params=params,
        model_state=model_state,
        tx=optax.adam(1e-3),
    )
    state_mb = sum(
        np.asarray(leaf).nbytes for leaf in jax.tree.leaves(state0.params)
    ) / 1e6
    rng = np.random.default_rng(0)
    batch = {
        "input": rng.normal(size=(32, 28, 28, 1)).astype(np.float32),
        "target": rng.integers(0, 10, 32),
    }
    step = jax.jit(make_train_step())
    tmp = tempfile.mkdtemp(prefix="zk_bench_ckpt_")

    def run_mode(mode):
        ck = Checkpointer()
        configure(
            ck,
            {
                "directory": os.path.join(tmp, mode),
                "mode": mode,
                # The sync leg measures the FULL blocking serialize+
                # write (the stall the async mode removes); orbax's own
                # background commit would hide part of it.
                "synchronous": True,
                "save_every_epochs": 0,
                "max_to_keep": 2,
            },
            name=f"ckpt_bench_{mode}",
        )
        st = state0
        stalls, overlapped = [], []
        # saves + 1 rounds: the first save pays one-time manager
        # creation (and, async, writer-thread start) — excluded.
        for i in range(saves + 1):
            for _ in range(2):
                st, m = step(st, batch)
            jax.block_until_ready(m["loss"])
            t0 = time.perf_counter()
            ck.save(st, step=int(jax.device_get(st.step)))
            stall = (time.perf_counter() - t0) * 1e3
            if mode == "async":
                k = 0
                while ck.async_in_flight and k < 10_000:
                    st, m = step(st, batch)
                    jax.block_until_ready(m["loss"])
                    k += 1
                if i > 0:
                    overlapped.append(k)
            ck.wait()
            if i > 0:
                stalls.append(stall)
        ck.close()
        return float(np.mean(stalls)), (
            float(np.mean(overlapped)) if overlapped else 0.0
        )

    try:
        step(state0, batch)  # compile outside every timed window
        sync_ms, _ = run_mode("sync")
        async_ms, steps_overlapped = run_mode("async")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "ckpt_sync_save_stall_ms": round(sync_ms, 3),
        "ckpt_async_save_stall_ms": round(async_ms, 3),
        "ckpt_async_stall_frac": round(async_ms / sync_ms, 4)
        if sync_ms > 0
        else -1.0,
        "ckpt_steps_overlapped_per_save": round(steps_overlapped, 1),
        "ckpt_state_mb": round(state_mb, 2),
    }


def _run_decode_flavor(env, decode_attention, tag):
    """One decode-bench serve at a given ``decode_attention`` flavor:
    build + warm an engine, push the steady-state mixed prefill/decode
    workload through the continuous-batching scheduler, assert
    compile-free, and return ``(tokens, dt, snap, engine, outputs,
    shape)`` where ``shape`` is the env-resolved workload (requests /
    slots / new_tokens — parsed HERE, once, so the reported keys can
    never disagree with the workload actually run). Shared by the
    headline run and the kernel-vs-reference A/B."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import TransformerLM
    from zookeeper_tpu.serving.decode import (
        DecodeEngine,
        DecodeMetrics,
        DecodeScheduler,
    )

    n_requests = int(env.get("ZK_BENCH_DECODE_REQUESTS", "64"))
    slots = int(env.get("ZK_BENCH_DECODE_SLOTS", "8"))
    new_tokens = int(env.get("ZK_BENCH_DECODE_NEW_TOKENS", "32"))
    max_prompt = int(env.get("ZK_BENCH_DECODE_PROMPT", "32"))
    num_layers = int(env.get("ZK_BENCH_DECODE_LAYERS", "4"))
    d_model = int(env.get("ZK_BENCH_DECODE_DMODEL", "256"))
    num_heads = int(env.get("ZK_BENCH_DECODE_HEADS", "4"))
    vocab = 512
    # Positional capacity: prompts + budgets must fit with headroom.
    seq_len = max(128, 2 * (max_prompt + new_tokens))

    model = TransformerLM()
    configure(
        model,
        {
            "num_layers": num_layers,
            "d_model": d_model,
            "num_heads": num_heads,
            "max_seq_len": seq_len,
            # Dense prefill: at <= max_prompt tokens the flash kernels
            # buy nothing (and interpret-mode Pallas would dominate
            # off-TPU); the decode dispatch's flavor is the engine's
            # decode_attention Field.
            "attention": "dense",
        },
        name=f"decode_bench_model_{tag}",
    )
    module = model.build((seq_len,), vocab)
    params, model_state = model.initialize(module, (seq_len,), seed=0)
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots,
            "seq_buckets": (max_prompt,),
            "kv_capacity": seq_len,
            "decode_attention": decode_attention,
        },
        name=f"decode_bench_engine_{tag}",
    )
    engine.bind(module, params, model_state)
    engine.warmup()
    warm_compiles = engine.compile_count
    metrics = DecodeMetrics()
    configure(metrics, {}, name=f"decode_bench_metrics_{tag}")
    scheduler = DecodeScheduler()
    configure(
        scheduler,
        {"max_new_tokens": new_tokens},
        name=f"decode_bench_sched_{tag}",
    )
    scheduler.bind(engine, metrics=metrics)

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, vocab, size=int(rng.integers(1, max_prompt + 1)))
        .astype(np.int32)
        for _ in range(n_requests)
    ]
    t0 = time.perf_counter()
    streams = [scheduler.submit(p) for p in prompts]
    scheduler.drain()
    dt = time.perf_counter() - t0
    outputs = [s.result() for s in streams]
    tokens = sum(int(o.shape[0]) for o in outputs)
    if engine.compile_count != warm_compiles:
        raise RuntimeError(
            f"decode leg ({decode_attention}) recompiled mid-traffic "
            f"({warm_compiles} -> {engine.compile_count}); the "
            "throughput numbers are invalid."
        )
    shape = {
        "requests": n_requests,
        "slots": slots,
        "new_tokens": new_tokens,
    }
    return tokens, dt, metrics.snapshot(), engine, outputs, shape


def measure_decode_throughput(env=None):
    """``ZK_BENCH_DECODE=1`` leg: tokens/s/chip and TTFT percentiles of
    the continuous-batching decode engine under MIXED prefill/decode
    traffic (docs/DESIGN.md §15), plus the paged-decode-kernel A/B
    (§17).

    The workload is the steady-state serving shape: many more requests
    than slots, submitted up front, so after the first cohort every
    prefill dispatch (a finished stream's slot being REFILLED) lands
    between decode dispatches of the still-active streams — prefill and
    decode interleave on one device exactly as they do in production.
    Every flavor's run is asserted compile-free after warmup (a
    recompile would invalidate the numbers AND the engine contract).

    Headline metrics come from the flavor ``decode_attention="auto"``
    resolves to on this backend (the Pallas paged kernel on TPU, the
    reference einsum elsewhere — interpret-mode Pallas is a grid-loop
    interpreter whose timings measure the interpreter, not the
    kernel): ``serve_decode_tokens_per_sec_per_chip`` (generated
    tokens over the serve wall time, per chip),
    ``decode_ttft_p50/p99_ms`` (submit-to-first-token; p99 is the
    interactive-latency gate), ``decode_token_p50_ms`` (one decode
    dispatch = one token for every active slot),
    ``decode_prefill_p50_ms``, the slot-refill count, and
    ``decode_mbu`` (last dispatch's bytes/time/bandwidth — the
    memory-bound roofline, -1 when cost analysis is unavailable).

    The A/B (``ZK_BENCH_DECODE_AB=0`` disables) times BOTH flavors on
    the same workload and reports
    ``decode_kernel_tokens_per_sec_per_chip`` /
    ``decode_reference_tokens_per_sec_per_chip`` /
    ``decode_kernel_speedup``, and asserts the two flavors emitted
    token-identical streams — the bench re-pins the numerics contract
    on every run. On TPU the speedup is the PR's acceptance number
    (length-bounded HBM reads on a memory-bound step); on CPU the
    kernel leg runs interpreted and records the honest (slower) number.

    Knobs: ``ZK_BENCH_DECODE_REQUESTS`` (default 64),
    ``ZK_BENCH_DECODE_SLOTS`` (default 8),
    ``ZK_BENCH_DECODE_NEW_TOKENS`` (per-request budget, default 32),
    ``ZK_BENCH_DECODE_PROMPT`` (max prompt length, default 32),
    ``ZK_BENCH_DECODE_LAYERS``/``_DMODEL``/``_HEADS`` (model geometry,
    default 4/256/4 — small enough to run everywhere, big enough that
    the decode dispatch is device work rather than host overhead)."""
    import numpy as np

    env = os.environ if env is None else env
    # The headline run serves with "auto" — the deployed default — and
    # the RESOLVED flavor is read back from the engine: one source of
    # truth (DecodeEngine._resolve_decode_attention), so a future auto
    # policy change cannot silently desync the bench from production.
    tokens, dt, snap, engine, outputs, shape = _run_decode_flavor(
        env, "auto", tag="auto"
    )
    headline = engine.decode_attention_flavor
    # Per-chip means per chip the engine actually SERVES on (the
    # default bind: one device) — dividing by the host's device_count
    # would make the gated key depend on idle-host topology, an 8x
    # phantom swing between a 1-chip and an 8-chip runner.
    mesh = engine._partitioner.mesh
    n_chips = int(mesh.size) if mesh is not None else 1
    out = {
        "serve_decode_tokens_per_sec_per_chip": round(
            tokens / dt / n_chips, 1
        ),
        "decode_ttft_p50_ms": round(snap.get("ttft_p50_ms", -1.0), 3),
        "decode_ttft_p99_ms": round(snap.get("ttft_p99_ms", -1.0), 3),
        "decode_token_p50_ms": round(snap.get("token_p50_ms", -1.0), 3),
        "decode_prefill_p50_ms": round(snap.get("prefill_p50_ms", -1.0), 3),
        # MBU at the run's MEDIAN dispatch time (the gauge's last-
        # dispatch sample is the drain tail — a single-sample gated key
        # would be flaky by construction).
        "decode_mbu": round(
            engine.decode_mbu_for(snap.get("token_p50_ms", -1.0) / 1e3), 4
        ),
        # Informational context (never gates): the RESOLVED flavor (a
        # geometry-degraded "pallas" reports "reference" — the number
        # must be labeled with the program that produced it), plus the
        # workload shape.
        "decode_attention_flavor": engine.decode_attention_flavor,
        "decode_requests": shape["requests"],
        "decode_slots": shape["slots"],
        "decode_new_tokens": shape["new_tokens"],
        # Admissions beyond the first slot-array cohort = slots that
        # were REFILLED mid-traffic without a drain or recompile.
        "decode_refills": max(
            0,
            int(snap["requests_total"])
            - min(shape["slots"], shape["requests"]),
        ),
        "decode_generated_tokens": tokens,
    }
    if _env_flag(env, "ZK_BENCH_DECODE_AB", "1"):
        other = "reference" if headline == "pallas" else "pallas"
        # Everything the headline engine had to answer is captured in
        # `out`/`headline`: release its device state (KV cache +
        # weights) before building the B-leg engine, or the A/B would
        # DOUBLE the HBM footprint and OOM at cache sizes the headline
        # run alone serves fine.
        engine = None
        tokens_b, dt_b, _, engine_b, outputs_b, _ = _run_decode_flavor(
            env, other, tag=other
        )
        if engine_b.decode_attention_flavor == headline:
            # Geometry degraded the kernel leg to the reference (see
            # DecodeEngine._resolve_decode_attention): both runs timed
            # the SAME program, and recording that as a kernel
            # measurement would seed bench_diff with a fake ~1.0
            # speedup baseline. Omit the A/B keys — absent keys never
            # gate.
            print(
                "bench: decode A/B skipped — both flavors resolved to "
                f"{headline!r} (kernel-unsupported geometry); no "
                "kernel numbers to record",
                file=sys.stderr,
            )
            return out
        mismatch = sum(
            1 for a, b in zip(outputs, outputs_b)
            if not np.array_equal(a, b)
        )
        if mismatch:
            raise RuntimeError(
                f"decode A/B: {mismatch}/{len(outputs)} streams differ "
                "between the kernel and reference flavors — the "
                "token-exact numerics contract is broken; the "
                "throughput comparison is meaningless."
            )
        by_flavor = {
            headline: tokens / dt / n_chips,
            other: tokens_b / dt_b / n_chips,
        }
        out["decode_kernel_tokens_per_sec_per_chip"] = round(
            by_flavor["pallas"], 1
        )
        out["decode_reference_tokens_per_sec_per_chip"] = round(
            by_flavor["reference"], 1
        )
        out["decode_kernel_speedup"] = round(
            by_flavor["pallas"] / by_flavor["reference"], 3
        ) if by_flavor["reference"] > 0 else -1.0
    return out


def measure_prefix_reuse(env=None):
    """``ZK_BENCH_PREFIX=1`` leg: warm-vs-cold shared-prefix TTFT A/B
    on the paged-KV engine (docs/DESIGN.md §20).

    The workload is the millions-of-users traffic shape the prefix
    cache exists for: every request shares one long system prompt and
    differs only in a short tail. Requests are served ONE AT A TIME
    (TTFT then IS the prefill cost — no queue-wait term), twice over:

    - **cold** — the prefix cache is invalidated before every
      admission, so each request pays the full prefill;
    - **warm** — one seeding request populates the cache, then every
      admission shares the resident prefix pages and the warm-extend
      program computes only the tail (CoW at the divergence page).

    Streams are asserted TOKEN-IDENTICAL between the passes (the bench
    re-pins the §20 parity contract on every run) and compile-free
    after warmup. Emits ``prefix_cold_ttft_p50_ms`` /
    ``prefix_warm_ttft_p50_ms`` / ``prefix_ttft_speedup`` (cold/warm —
    the headline; the CPU reference is the conservative floor, the
    saved prefill FLOPs only grow with model size) plus ``kv_pool_fill``
    and the informational workload shape.

    Knobs: ``ZK_BENCH_PREFIX_REQUESTS`` (default 12),
    ``ZK_BENCH_PREFIX_SHARED`` (shared prefix tokens, default 224 —
    long enough that the saved prefill compute dominates the fixed
    per-dispatch host cost on the CPU reference),
    ``ZK_BENCH_PREFIX_TAIL`` (unique tail tokens, default 8),
    ``ZK_BENCH_DECODE_LAYERS``/``_DMODEL``/``_HEADS`` (model geometry,
    shared with the decode leg)."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import TransformerLM
    from zookeeper_tpu.serving.decode import (
        DecodeEngine,
        DecodeMetrics,
        DecodeScheduler,
    )

    env = os.environ if env is None else env
    n_requests = int(env.get("ZK_BENCH_PREFIX_REQUESTS", "12"))
    shared_len = int(env.get("ZK_BENCH_PREFIX_SHARED", "224"))
    tail_len = int(env.get("ZK_BENCH_PREFIX_TAIL", "8"))
    num_layers = int(env.get("ZK_BENCH_DECODE_LAYERS", "4"))
    d_model = int(env.get("ZK_BENCH_DECODE_DMODEL", "256"))
    num_heads = int(env.get("ZK_BENCH_DECODE_HEADS", "4"))
    vocab = 512
    prompt_len = shared_len + tail_len
    seq_len = max(128, 2 * prompt_len)

    model = TransformerLM()
    configure(
        model,
        {
            "num_layers": num_layers,
            "d_model": d_model,
            "num_heads": num_heads,
            "max_seq_len": seq_len,
            "attention": "dense",
        },
        name="prefix_bench_model",
    )
    module = model.build((seq_len,), vocab)
    params, model_state = model.initialize(module, (seq_len,), seed=0)
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": 2,
            # Small bucket for the warm tail, big one for cold prefill:
            # the TTFT gap between them IS the measured effect.
            "seq_buckets": (
                tuple(sorted({16, prompt_len}))
            ),
            "kv_capacity": seq_len,
            "kv_layout": "paged",
        },
        name="prefix_bench_engine",
    )
    engine.bind(module, params, model_state)
    engine.warmup()
    warm_compiles = engine.compile_count
    metrics = DecodeMetrics()
    configure(metrics, {}, name="prefix_bench_metrics")
    scheduler = DecodeScheduler()
    configure(
        scheduler, {"max_new_tokens": 4}, name="prefix_bench_sched"
    )
    scheduler.bind(engine, metrics=metrics)

    rng = np.random.default_rng(0)
    shared = rng.integers(1, vocab, size=shared_len).astype(np.int32)
    prompts = [
        np.concatenate(
            [shared, rng.integers(1, vocab, size=tail_len).astype(np.int32)]
        )
        for _ in range(n_requests)
    ]

    def serve_one_at_a_time(invalidate_each):
        ttfts, outs = [], []
        for p in prompts:
            if invalidate_each:
                engine.invalidate_prefix_cache()
            stream = scheduler.submit(p)
            outs.append(stream.result())
            ttfts.append(stream.ttft_ms)
        return np.asarray(ttfts), outs

    cold_ttft, cold_out = serve_one_at_a_time(invalidate_each=True)
    # Seed the cache once, then measure the warm steady state.
    engine.invalidate_prefix_cache()
    scheduler.generate(prompts[0], max_new_tokens=1)
    warm_ttft, warm_out = serve_one_at_a_time(invalidate_each=False)
    mismatch = sum(
        1 for a, b in zip(cold_out, warm_out) if not np.array_equal(a, b)
    )
    if mismatch:
        raise RuntimeError(
            f"prefix leg: {mismatch}/{n_requests} streams differ between "
            "the cold and warm passes — the §20 parity contract is "
            "broken; the TTFT comparison is meaningless."
        )
    if engine.compile_count != warm_compiles:
        raise RuntimeError(
            f"prefix leg recompiled mid-traffic ({warm_compiles} -> "
            f"{engine.compile_count}); the TTFT numbers are invalid."
        )
    pool = engine.page_pool
    cold_p50 = float(np.percentile(cold_ttft, 50))
    warm_p50 = float(np.percentile(warm_ttft, 50))
    return {
        "prefix_cold_ttft_p50_ms": round(cold_p50, 3),
        "prefix_warm_ttft_p50_ms": round(warm_p50, 3),
        "prefix_ttft_speedup": round(cold_p50 / warm_p50, 3)
        if warm_p50 > 0
        else -1.0,
        "kv_pool_fill": round(pool.used_pages / pool.num_pages, 4),
        # Informational workload shape + cache effectiveness.
        "prefix_hit_rate": round(pool.prefix_hit_rate, 4),
        "prefix_cow_pages": pool.cow_pages,
        "prefix_requests": n_requests,
        "prefix_shared_tokens": shared_len,
        "prefix_tail_tokens": tail_len,
    }


def measure_speculative_throughput(env=None):
    """``ZK_BENCH_SPEC=1`` leg: spec-vs-plain A/B on the SAME teacher
    engine (docs/DESIGN.md §18) at a pinned high-acceptance workload.

    The workload is the zero-tail construction the certification tests
    pin: the teacher's blocks past ``ZK_BENCH_SPEC_DRAFT_LAYERS`` have
    their ``proj``/``down`` kernels zeroed (each contributes exactly
    0.0 to the residual stream — the teacher still pays full per-layer
    compute, XLA cannot know a kernel is zero), and the draft IS the
    teacher's first layers. Draft and teacher therefore agree on
    (nearly) every argmax, pinning acceptance ~1.0 — the schedule's
    throughput ceiling, measured honestly: the reported
    ``spec_acceptance_rate`` labels the number, and production
    acceptance depends on how well the distilled student tracks its
    teacher. The speedup mechanism the leg isolates is REAL on any
    backend: one teacher verify dispatch replaces k+1 teacher decode
    dispatches, with only k cheap draft dispatches added — it cuts
    teacher dispatch count, which is why the win shows on the CPU
    reference box, not just on TPU HBM bandwidth.

    Both modes serve the identical prompt set through fresh scheduler
    bindings over ONE engine (plain first, then speculative); streams
    are asserted TOKEN-IDENTICAL between modes (greedy speculation is
    lossless — the bench re-pins the §18 contract every run) and each
    mode is asserted compile-free after its warmup. Emits
    ``spec_tokens_per_sec_per_chip``,
    ``spec_plain_tokens_per_sec_per_chip``, ``spec_speedup``,
    ``spec_acceptance_rate`` (gated, higher-better) and ``spec_k`` /
    workload-shape keys (informational).

    Knobs: ``ZK_BENCH_SPEC_K`` (default 10 — on the CPU reference box
    the win is dispatch-count amortization, so the default leans on a
    wide window; the §18 cost model picks smaller k at lower
    acceptance), ``ZK_BENCH_SPEC_LAYERS`` (teacher depth, default 6),
    ``ZK_BENCH_SPEC_DRAFT_LAYERS`` (default 1),
    ``ZK_BENCH_SPEC_REQUESTS``/``_SLOTS``/``_NEW_TOKENS``/``_PROMPT``
    (default 16/4/55/16 — the budget is window-aligned, 55 = 5 full
    k+1 windows, and generations are long relative to prefill so the
    gated ratio measures the DECODE loop rather than the prefill cost
    both modes share), ``_DMODEL``/``_HEADS`` (default 256/4)."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import TransformerLM
    from zookeeper_tpu.serving.decode import (
        DecodeEngine,
        DecodeScheduler,
        SpeculativeDecoding,
    )

    env = os.environ if env is None else env
    k = int(env.get("ZK_BENCH_SPEC_K", "10"))
    layers = int(env.get("ZK_BENCH_SPEC_LAYERS", "6"))
    draft_layers = int(env.get("ZK_BENCH_SPEC_DRAFT_LAYERS", "1"))
    n_requests = int(env.get("ZK_BENCH_SPEC_REQUESTS", "16"))
    slots = int(env.get("ZK_BENCH_SPEC_SLOTS", "4"))
    new_tokens = int(env.get("ZK_BENCH_SPEC_NEW_TOKENS", "55"))
    max_prompt = int(env.get("ZK_BENCH_SPEC_PROMPT", "16"))
    d_model = int(env.get("ZK_BENCH_SPEC_DMODEL", "256"))
    num_heads = int(env.get("ZK_BENCH_SPEC_HEADS", "4"))
    vocab = 512
    seq_len = max(128, 2 * (max_prompt + new_tokens))
    if not 0 < draft_layers < layers:
        raise ValueError(
            f"need 0 < draft_layers ({draft_layers}) < layers ({layers})."
        )

    def build(n_layers, name):
        model = TransformerLM()
        configure(
            model,
            {
                "num_layers": n_layers,
                "d_model": d_model,
                "num_heads": num_heads,
                "max_seq_len": seq_len,
                "attention": "dense",  # short prefills, off-TPU safe
            },
            name=name,
        )
        module = model.build((seq_len,), vocab)
        params, state = model.initialize(module, (seq_len,), seed=0)
        return module, params, state

    import jax.numpy as jnp

    t_module, t_params, t_state = build(layers, "spec_bench_teacher")
    t_params = dict(t_params)
    for i in range(draft_layers, layers):
        block = {**t_params[f"block{i}"]}
        block["proj"] = {"kernel": jnp.zeros_like(block["proj"]["kernel"])}
        block["down"] = {"kernel": jnp.zeros_like(block["down"]["kernel"])}
        t_params[f"block{i}"] = block
    d_module, d_params, d_state = build(draft_layers, "spec_bench_draft")
    d_params = {key: t_params[key] for key in d_params}

    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots,
            "seq_buckets": (max_prompt,),
            "kv_capacity": seq_len,
        },
        name="spec_bench_engine",
    )
    engine.bind(t_module, t_params, t_state)
    engine.warmup()

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, vocab, size=int(rng.integers(1, max_prompt + 1)))
        .astype(np.int32)
        for _ in range(n_requests)
    ]

    def serve(spec):
        sched = DecodeScheduler()
        configure(
            sched,
            {"max_new_tokens": new_tokens},
            name="spec_bench_sched_"
            + ("spec" if spec is not None else "plain"),
        )
        sched.bind(engine, speculative=spec)
        warm = engine.compile_count
        dwarm = spec.draft_engine.compile_count if spec else 0
        t0 = time.perf_counter()
        streams = [sched.submit(p) for p in prompts]
        sched.drain()
        dt = time.perf_counter() - t0
        outputs = [s.result() for s in streams]
        if engine.compile_count != warm or (
            spec and spec.draft_engine.compile_count != dwarm
        ):
            raise RuntimeError(
                "speculative bench leg recompiled mid-traffic; the "
                "throughput numbers are invalid."
            )
        return sum(int(o.shape[0]) for o in outputs) / dt, outputs

    # Plain first (its scheduler never sees the draft), then the
    # speculative binding warms the verify widths + draft grid before
    # ITS traffic — one engine, two modes, identical prompts.
    plain_tps, plain_out = serve(None)
    spec_cfg = SpeculativeDecoding()
    configure(spec_cfg, {"enabled": True, "k": k}, name="spec_bench_spec")
    spec_cfg.bind(engine, d_module, d_params, d_state)
    spec_tps, spec_out = serve(spec_cfg)
    mismatch = sum(
        1 for a, b in zip(plain_out, spec_out) if not np.array_equal(a, b)
    )
    if mismatch:
        raise RuntimeError(
            f"speculative A/B: {mismatch}/{len(plain_out)} streams "
            "differ between plain and speculative greedy — the "
            "losslessness contract is broken; the speedup is "
            "meaningless."
        )
    mesh = engine._partitioner.mesh
    n_chips = int(mesh.size) if mesh is not None else 1
    return {
        "spec_tokens_per_sec_per_chip": round(spec_tps / n_chips, 1),
        "spec_plain_tokens_per_sec_per_chip": round(
            plain_tps / n_chips, 1
        ),
        "spec_speedup": round(spec_tps / plain_tps, 3)
        if plain_tps > 0
        else -1.0,
        "spec_acceptance_rate": round(spec_cfg.acceptance_rate, 4),
        # Workload shape (informational — config, not perf).
        "spec_k": k,
        "spec_teacher_layers": layers,
        "spec_draft_layers": draft_layers,
        "spec_requests": n_requests,
        "spec_slots": slots,
        "spec_new_tokens": new_tokens,
    }


def measure_disagg_throughput(env=None):
    """``ZK_BENCH_DISAGG=1`` leg: disaggregated-vs-single-mesh A/B on
    the SAME weights and prompt set (docs/DESIGN.md §22).

    Baseline first: a single-mesh paged DecodeEngine serves the full
    workload (prefill and decode interleaved on one role — every
    prefill dispatch lands between active streams' decode dispatches).
    Then the disaggregated stack — prefill lanes on one role engine,
    decode slots on another, each completed prefill's KV pages moved
    across by PageTransfer — serves the identical prompts. Streams are
    asserted TOKEN-IDENTICAL between the topologies (the bench re-pins
    the §22 certification on every run) and BOTH legs are asserted
    compile-free after warmup on every engine involved.

    On the 1-device CPU reference box the roles overlap on the same
    device, so the gated throughput measures the protocol's overhead
    floor (transfer cost with nothing bought back); on a multi-slice
    host the prefill role stops stealing the decode role's dispatch
    slots and the TTFT tail is the headline. Emits
    ``disagg_tokens_per_sec_per_chip`` / ``disagg_ttft_p50_ms`` /
    ``disagg_ttft_p99_ms`` and the single-mesh counterparts
    (``disagg_baseline_*``), ``transfer_ms_p50`` (per-handoff median
    wall cost) plus informational workload-shape / transfer-volume
    keys.

    Knobs: ``ZK_BENCH_DISAGG_REQUESTS`` (default 32),
    ``ZK_BENCH_DISAGG_SLOTS`` (decode role, default 8),
    ``ZK_BENCH_DISAGG_LANES`` (prefill role, default 4),
    ``ZK_BENCH_DISAGG_NEW_TOKENS`` (default 32),
    ``ZK_BENCH_DISAGG_PROMPT`` (default 32),
    ``ZK_BENCH_DISAGG_HOST_BOUNCE=1`` (force the portable host path),
    ``ZK_BENCH_DECODE_LAYERS``/``_DMODEL``/``_HEADS`` (model geometry,
    shared with the decode leg)."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import TransformerLM
    from zookeeper_tpu.serving import DisaggScheduler, PageTransfer
    from zookeeper_tpu.serving.decode import (
        DecodeEngine,
        DecodeMetrics,
        DecodeScheduler,
    )

    env = os.environ if env is None else env
    n_requests = int(env.get("ZK_BENCH_DISAGG_REQUESTS", "32"))
    slots = int(env.get("ZK_BENCH_DISAGG_SLOTS", "8"))
    lanes = int(env.get("ZK_BENCH_DISAGG_LANES", "4"))
    new_tokens = int(env.get("ZK_BENCH_DISAGG_NEW_TOKENS", "32"))
    max_prompt = int(env.get("ZK_BENCH_DISAGG_PROMPT", "32"))
    host_bounce = _env_flag(env, "ZK_BENCH_DISAGG_HOST_BOUNCE")
    num_layers = int(env.get("ZK_BENCH_DECODE_LAYERS", "4"))
    d_model = int(env.get("ZK_BENCH_DECODE_DMODEL", "256"))
    num_heads = int(env.get("ZK_BENCH_DECODE_HEADS", "4"))
    vocab = 512
    seq_len = max(128, 2 * (max_prompt + new_tokens))

    model = TransformerLM()
    configure(
        model,
        {
            "num_layers": num_layers,
            "d_model": d_model,
            "num_heads": num_heads,
            "max_seq_len": seq_len,
            "attention": "dense",  # short prefills, off-TPU safe
        },
        name="disagg_bench_model",
    )
    module = model.build((seq_len,), vocab)
    params, model_state = model.initialize(module, (seq_len,), seed=0)

    def role(name, n_slots, **conf):
        engine = DecodeEngine()
        configure(
            engine,
            {
                "slots": n_slots,
                "seq_buckets": (max_prompt,),
                "kv_capacity": seq_len,
                "kv_layout": "paged",
                **conf,
            },
            name=f"disagg_bench_{name}",
        )
        engine.bind(module, params, model_state)
        engine.warmup()
        return engine

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, vocab, size=int(rng.integers(1, max_prompt + 1)))
        .astype(np.int32)
        for _ in range(n_requests)
    ]

    def serve(scheduler):
        t0 = time.perf_counter()
        streams = [scheduler.submit(p) for p in prompts]
        scheduler.drain()
        dt = time.perf_counter() - t0
        outputs = [s.result() for s in streams]
        return outputs, sum(int(o.shape[0]) for o in outputs), dt

    # -- baseline: everything on one role -------------------------------
    single = role("single", slots)
    warm_single = single.compile_count
    base_metrics = DecodeMetrics()
    configure(base_metrics, {}, name="disagg_bench_base_metrics")
    base_sched = DecodeScheduler()
    configure(
        base_sched,
        {"max_new_tokens": new_tokens},
        name="disagg_bench_base_sched",
    )
    base_sched.bind(single, metrics=base_metrics)
    base_out, base_tokens, base_dt = serve(base_sched)
    base_snap = base_metrics.snapshot()
    if single.compile_count != warm_single:
        raise RuntimeError(
            "disagg baseline recompiled mid-traffic "
            f"({warm_single} -> {single.compile_count}); the A/B is "
            "invalid."
        )
    mesh = single._partitioner.mesh
    n_chips = int(mesh.size) if mesh is not None else 1
    # Release the baseline's KV + weights before the two role engines
    # bind (three live caches would inflate the footprint of a leg
    # whose point is the topology, not the memory).
    base_sched.close()
    single = None

    # -- disaggregated: prefill role + decode role + page handoff -------
    # Prefill batches as wide as the lane count allows (a bucket can
    # never admit more sequences than there are lanes).
    pre_buckets = tuple(b for b in (1, 2, 4) if b <= lanes) or (1,)
    pre = role("prefill", lanes, prefill_buckets=pre_buckets)
    dec = role("decode", slots, prefill_buckets=(1,), prefix_cache=False)
    pre.warmup_transfer()
    dec.warmup_transfer()
    warm_pre, warm_dec = pre.compile_count, dec.compile_count
    transfer = PageTransfer()
    configure(
        transfer, {"host_bounce": host_bounce}, name="disagg_bench_transfer"
    )
    dis_metrics = DecodeMetrics()
    configure(dis_metrics, {}, name="disagg_bench_metrics")
    transfer.bind(pre, dec, metrics=dis_metrics)
    sched = DisaggScheduler()
    configure(
        sched, {"max_new_tokens": new_tokens}, name="disagg_bench_sched"
    )
    sched.bind(pre, dec, transfer, metrics=dis_metrics)
    dis_out, dis_tokens, dis_dt = serve(sched)
    dis_snap = dis_metrics.snapshot()
    if pre.compile_count != warm_pre or dec.compile_count != warm_dec:
        raise RuntimeError(
            "disagg leg recompiled mid-traffic (prefill "
            f"{warm_pre} -> {pre.compile_count}, decode "
            f"{warm_dec} -> {dec.compile_count}); the A/B is invalid."
        )
    mismatch = sum(
        1 for a, b in zip(base_out, dis_out) if not np.array_equal(a, b)
    )
    if mismatch:
        raise RuntimeError(
            f"disagg A/B: {mismatch}/{len(base_out)} streams differ "
            "between the single-mesh and disaggregated topologies — "
            "the §22 token-identity contract is broken; the "
            "throughput comparison is meaningless."
        )
    ts = transfer.status()
    return {
        # Gated (direction-aware in tools/bench_diff.py).
        "disagg_tokens_per_sec_per_chip": round(
            dis_tokens / dis_dt / n_chips, 1
        ),
        "disagg_baseline_tokens_per_sec_per_chip": round(
            base_tokens / base_dt / n_chips, 1
        ),
        "disagg_ttft_p50_ms": round(dis_snap.get("ttft_p50_ms", -1.0), 3),
        "disagg_ttft_p99_ms": round(dis_snap.get("ttft_p99_ms", -1.0), 3),
        "disagg_baseline_ttft_p50_ms": round(
            base_snap.get("ttft_p50_ms", -1.0), 3
        ),
        "disagg_baseline_ttft_p99_ms": round(
            base_snap.get("ttft_p99_ms", -1.0), 3
        ),
        "transfer_ms_p50": round(ts["transfer_ms_p50"], 3),
        # Workload shape + transfer volume (informational — config and
        # workload-determined tallies, not perf directions).
        "disagg_requests": n_requests,
        "disagg_slots": slots,
        "disagg_lanes": lanes,
        "disagg_new_tokens": new_tokens,
        "disagg_transfer_handoffs": int(ts["handoffs_total"]),
        "disagg_transfer_pages": int(ts["pages_total"]),
        "disagg_transfer_bytes": int(ts["bytes_total"]),
        "disagg_host_bounces": int(ts["host_bounces"]),
        "disagg_generated_tokens": dis_tokens,
    }


def measure_fleet_throughput(env=None):
    """``ZK_BENCH_FLEET=1`` leg: prefix-affinity-vs-round-robin A/B
    over a REAL fleet — a :class:`FleetRouter` fronting N worker
    PROCESSES (each a paged-KV ``LMServingConfig`` spawned by
    ``zookeeper_tpu.testing.spawn_fleet_workers``), docs/DESIGN.md §23.

    The workload is multi-turn: S sessions x T turns, each turn's
    prompt extending the last (the history-grows shape). The affinity
    pass routes with session pinning (turn 2+ re-enters its replica's
    radix cache and prefills only the un-cached suffix); the
    round-robin pass — FRESH workers, same seed — sprays the same
    token-identical stream across replicas, so turn-2 history re-
    prefills cold on whichever box it lands on. Streams are asserted
    TOKEN-IDENTICAL between the passes (routing is a latency policy,
    never a correctness input), and every affinity turn-2+ must report
    worker-side warm ``shared_tokens`` — a silent cold fleet would
    gate, not just dip.

    Headline: ``fleet_warm_ttft_p50_ms`` (affinity turn-2+) vs
    ``fleet_rr_ttft_p50_ms`` (round-robin turn-2+) and their ratio
    ``fleet_affinity_ttft_speedup`` — the §20 warm-prefill win scaled
    FLEET-wide, which pure load balancing destroys. TTFTs are the
    workers' own scheduler-measured numbers, so the comparison is the
    prefill path, not HTTP plumbing.

    Knobs: ``ZK_BENCH_FLEET_REPLICAS`` (default 2),
    ``ZK_BENCH_FLEET_SESSIONS`` (default 3 — odd, so round-robin
    turn-2 genuinely lands cold with 2 replicas),
    ``ZK_BENCH_FLEET_TURNS`` (default 3), ``ZK_BENCH_FLEET_SHARED``
    (turn-1 prompt tokens, default 192 — long enough history that
    re-prefilling it cold dominates TTFT), ``ZK_BENCH_FLEET_TAIL``
    (new tokens per later turn, default 8),
    ``ZK_BENCH_FLEET_NEW_TOKENS`` (generation budget, default 8),
    ``ZK_BENCH_FLEET_LAYERS``/``_DMODEL``/``_HEADS`` (worker model
    geometry, defaults 4/256/4 — the decode leg's class)."""
    import shutil
    import tempfile

    import numpy as np

    from zookeeper_tpu.serving import FleetRouter, ReplicaHandle
    from zookeeper_tpu.testing import (
        spawn_fleet_workers,
        stop_fleet_workers,
    )

    env = os.environ if env is None else env
    n_replicas = int(env.get("ZK_BENCH_FLEET_REPLICAS", "2"))
    n_sessions = int(env.get("ZK_BENCH_FLEET_SESSIONS", "3"))
    turns = int(env.get("ZK_BENCH_FLEET_TURNS", "3"))
    shared = int(env.get("ZK_BENCH_FLEET_SHARED", "192"))
    tail = int(env.get("ZK_BENCH_FLEET_TAIL", "8"))
    new_tokens = int(env.get("ZK_BENCH_FLEET_NEW_TOKENS", "8"))
    num_layers = int(env.get("ZK_BENCH_FLEET_LAYERS", "4"))
    d_model = int(env.get("ZK_BENCH_FLEET_DMODEL", "256"))
    num_heads = int(env.get("ZK_BENCH_FLEET_HEADS", "4"))
    if turns < 2:
        raise RuntimeError(
            f"ZK_BENCH_FLEET_TURNS={turns}: the leg measures turn-2+ "
            "warm TTFT, so it needs at least 2 turns."
        )
    page_size = 16
    vocab = 512
    max_prompt = shared + (turns - 1) * tail
    seq_len = max(256, 2 * (max_prompt + new_tokens))
    # (16, max_prompt): warm turn-2+ suffixes (tail + partial chunk)
    # ride the small bucket; cold full-history prefills pay the big
    # one — exactly the asymmetry affinity routing protects.
    conf = {
        "model.num_layers": num_layers,
        "model.d_model": d_model,
        "model.num_heads": num_heads,
        "model.max_seq_len": seq_len,
        "model.attention": "dense",
        "seq_len": seq_len,
        "vocab_size": vocab,
        "seed": 0,
        "engine.kv_layout": "paged",
        "engine.page_size": page_size,
        "engine.slots": 4,
        "engine.seq_buckets": (16, max_prompt),
        "engine.prefill_buckets": (1,),
        "requests": 0,
        "verbose": False,
    }
    rng = np.random.default_rng(11)
    session_ids = [f"s{i}" for i in range(n_sessions)]
    prompts = {}
    for sid in session_ids:
        base = rng.integers(1, vocab, size=shared).tolist()
        turn_prompts = [list(base)]
        for _ in range(turns - 1):
            base = base + rng.integers(1, vocab, size=tail).tolist()
            turn_prompts.append(list(base))
        prompts[sid] = turn_prompts

    def run_pass(policy):
        workdir = tempfile.mkdtemp(prefix=f"zk_fleet_bench_{policy}_")
        workers = spawn_fleet_workers(
            workdir, num_workers=n_replicas, config=conf
        )
        router = None
        try:
            router = FleetRouter(
                [ReplicaHandle.from_worker(w) for w in workers],
                page_size=page_size,
                policy=policy,
            )
            outputs = {}
            ttft_by_turn = {t: [] for t in range(turns)}
            shared_by_turn = {t: [] for t in range(turns)}
            generated = 0
            t0 = time.perf_counter()
            # Turn-major: every session's turn t lands before any
            # turn t+1, the arrival order a live fleet would see.
            for turn in range(turns):
                for sid in session_ids:
                    resp = router.submit(
                        prompts[sid][turn],
                        # Round-robin is the no-affinity baseline:
                        # no pinning, pure rotation.
                        session=sid if policy == "affinity" else None,
                        max_new_tokens=new_tokens,
                    )
                    outputs[(sid, turn)] = resp.tokens.tolist()
                    ttft_by_turn[turn].append(float(resp.ttft_ms))
                    shared_by_turn[turn].append(resp.shared_tokens)
                    generated += int(resp.tokens.shape[0])
            dt = time.perf_counter() - t0
            route_snap = router.metrics.snapshot()
            return outputs, ttft_by_turn, shared_by_turn, generated, \
                dt, route_snap
        finally:
            if router is not None:
                router.close()
            stop_fleet_workers(workers)
            shutil.rmtree(workdir, ignore_errors=True)

    aff_out, aff_ttft, aff_shared, aff_tokens, aff_dt, route_snap = (
        run_pass("affinity")
    )
    rr_out, rr_ttft, rr_shared, rr_tokens, rr_dt, _ = run_pass(
        "round_robin"
    )
    if aff_out != rr_out:
        diff = sum(1 for k in aff_out if aff_out[k] != rr_out[k])
        raise RuntimeError(
            f"fleet A/B: {diff}/{len(aff_out)} streams differ between "
            "affinity and round-robin routing — the §23 token-identity "
            "contract is broken; the TTFT comparison is meaningless."
        )
    warm = [s for t in range(1, turns) for s in aff_shared[t]]
    if not all(s > 0 for s in warm):
        raise RuntimeError(
            "fleet affinity pass has COLD turn-2+ requests "
            f"(shared_tokens per turn>=2: {warm}) — session pinning "
            "or the radix warm path is broken; the warm TTFT below "
            "would be a lie."
        )
    warm_ttfts = [x for t in range(1, turns) for x in aff_ttft[t]]
    rr_ttfts = [x for t in range(1, turns) for x in rr_ttft[t]]
    warm_p50 = float(np.percentile(warm_ttfts, 50))
    rr_p50 = float(np.percentile(rr_ttfts, 50))
    hits = sum(1 for s in warm if s > 0)
    return {
        # Gated (direction-aware in tools/bench_diff.py).
        "fleet_tokens_per_sec": round(aff_tokens / aff_dt, 1),
        "fleet_rr_tokens_per_sec": round(rr_tokens / rr_dt, 1),
        "fleet_warm_ttft_p50_ms": round(warm_p50, 3),
        "fleet_rr_ttft_p50_ms": round(rr_p50, 3),
        "fleet_cold_ttft_p50_ms": round(
            float(np.percentile(aff_ttft[0], 50)), 3
        ),
        "fleet_affinity_ttft_speedup": round(
            rr_p50 / warm_p50 if warm_p50 > 0 else -1.0, 2
        ),
        "fleet_route_ms_p50": round(
            route_snap.get("fleet_route_ms_p50", -1.0), 4
        ),
        # Workload shape + affinity effectiveness (informational: the
        # synthetic workload DETERMINES the hit rate — 1.0 or bust,
        # and "bust" already raised above).
        "fleet_replicas": n_replicas,
        "fleet_sessions": n_sessions,
        "fleet_turns": turns,
        "fleet_shared_tokens": shared,
        "fleet_tail_tokens": tail,
        "fleet_new_tokens": new_tokens,
        "fleet_affinity_hit_rate": round(hits / max(1, len(warm)), 3),
        "fleet_generated_tokens": aff_tokens,
    }


def measure_trace_slo(env=None):
    """``ZK_BENCH_TRACE=1`` leg: overload-guardrails A/B under a
    pinned trace-driven burst — docs/DESIGN.md §24's acceptance
    numbers.

    One seed-keyed ``poisson_burst`` trace (every request carrying a
    deadline) is replayed open-loop against TWO fresh sync decode
    stacks built from the same config: pass A with the
    :class:`OverloadGuard` off (the baseline — doomed requests ride
    the queue until ``DeadlineExpiredError`` fires, wasting queue
    residency and mid-decode work), pass B with predicted-miss
    admission on (doomed requests shed at submit). Both passes get an
    identical no-deadline warmup block first, so pass B's EWMA
    estimator is warmed the way a live service's would be and neither
    pass pays compile time inside the measurement.

    Headline (gated, direction-aware in tools/bench_diff.py):

    - ``trace_goodput_tokens_per_sec`` — guardrails-on goodput
      (ok-request tokens / wall). Shedding the doomed tail must not
      cost throughput of the admitted body.
    - ``trace_admitted_ttft_p99_ms`` — p99 TTFT over ADMITTED (ok)
      requests with guardrails on; the §24 acceptance bound is <= the
      baseline's (``trace_baseline_admitted_ttft_p99_ms``,
      informational), because the queue no longer carries corpses.
    - ``trace_shed_precision`` — of the requests pass B shed, the
      fraction that pass A actually failed (deadline-expired): sheds
      should hit the doomed, not the viable.

    Knobs: ``ZK_BENCH_TRACE_SEED`` (default 23),
    ``ZK_BENCH_TRACE_DEADLINE_MS`` (default 300),
    ``ZK_BENCH_TRACE_BURST_RPS`` (default 900),
    ``ZK_BENCH_TRACE_NEW_TOKENS`` (max output budget, default 12),
    ``ZK_BENCH_TRACE_WARMUP`` (warmup requests, default 6)."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.loadgen import poisson_burst, replay
    from zookeeper_tpu.serving import LMServingConfig

    env = os.environ if env is None else env
    seed = int(env.get("ZK_BENCH_TRACE_SEED", "23"))
    deadline_ms = float(env.get("ZK_BENCH_TRACE_DEADLINE_MS", "300"))
    burst_rps = float(env.get("ZK_BENCH_TRACE_BURST_RPS", "900"))
    new_tokens = int(env.get("ZK_BENCH_TRACE_NEW_TOKENS", "12"))
    warmup = int(env.get("ZK_BENCH_TRACE_WARMUP", "6"))

    vocab = 61
    conf = {
        "model.num_layers": 2,
        "model.d_model": 64,
        "model.num_heads": 4,
        "model.max_seq_len": 128,
        "model.attention": "dense",
        "seq_len": 128,
        "vocab_size": vocab,
        "seed": 0,
        "engine.kv_layout": "paged",
        "engine.page_size": 16,
        "engine.slots": 4,
        "engine.seq_buckets": (32, 128),
        "engine.prefill_buckets": (1,),
        "requests": 0,
        "verbose": False,
        "metrics_port": -1,
    }
    trace = poisson_burst(
        seed,
        base_rate_rps=40.0,
        burst_rate_rps=burst_rps,
        base_s=0.3,
        burst_s=0.3,
        cooldown_s=0.15,
        vocab=vocab,
        prompt_len=4,
        max_prompt_len=24,
        new_tokens=4,
        max_new_tokens=new_tokens,
        deadline_ms=deadline_ms,
    )
    warm_rng = np.random.default_rng(7)
    warm_prompts = [
        warm_rng.integers(1, vocab, size=8).astype(np.int32)
        for _ in range(warmup)
    ]

    def run_pass(guard_on):
        svc = LMServingConfig()
        c = dict(conf)
        if guard_on:
            c["guard.enabled"] = True
            c["guard.min_samples"] = 4
        configure(
            svc, c, name="trace_slo_" + ("on" if guard_on else "off")
        )
        _, scheduler = svc.build_service()
        try:
            # Identical warmup both passes: compiles out of the clock,
            # and (pass B) the EWMA estimator fed like a live service.
            for p in warm_prompts:
                scheduler.submit(p, max_new_tokens=4).result(
                    timeout=300.0
                )
            return replay(trace, scheduler)
        finally:
            svc._teardown_service(suppress=True)

    base = run_pass(False)
    guarded = run_pass(True)

    def admitted_ttft_p99(report):
        ttfts = [
            o.ttft_ms
            for o in report.results
            if o.outcome == "ok" and o.ttft_ms is not None
        ]
        return float(np.percentile(ttfts, 99)) if ttfts else -1.0

    # Shed precision: B's sheds scored against what ACTUALLY failed in
    # the unguarded baseline (deadline-expired or statically shed).
    missed_base = {
        o.index for o in base.results if o.outcome != "ok"
    }
    shed = {o.index for o in guarded.results if o.outcome == "shed"}
    precision = (
        len(shed & missed_base) / len(shed) if shed else 1.0
    )
    return {
        # Gated (direction-aware in tools/bench_diff.py).
        "trace_goodput_tokens_per_sec": round(
            guarded.goodput_tokens_per_sec, 1
        ),
        "trace_admitted_ttft_p99_ms": round(
            admitted_ttft_p99(guarded), 3
        ),
        "trace_shed_precision": round(precision, 3),
        # Baseline pass (informational: context for the gated B side).
        "trace_baseline_goodput_tokens_per_sec": round(
            base.goodput_tokens_per_sec, 1
        ),
        "trace_baseline_admitted_ttft_p99_ms": round(
            admitted_ttft_p99(base), 3
        ),
        "trace_baseline_deadline_expired": base.outcomes.get(
            "deadline_expired", 0
        ),
        "trace_baseline_ok": base.outcomes.get("ok", 0),
        # Workload shape + outcome tallies (informational).
        "trace_requests": len(trace.requests),
        "trace_deadline_ms": deadline_ms,
        "trace_shed_total": len(shed),
        "trace_ok_total": guarded.outcomes.get("ok", 0),
        "trace_deadline_expired": guarded.outcomes.get(
            "deadline_expired", 0
        ),
    }


def measure_chunked_interference(env=None):
    """``ZK_BENCH_CHUNKED=1`` leg: chunked-prefill A/B under long-prompt
    interference — docs/DESIGN.md §25's acceptance number.

    One pinned ``poisson_burst`` trace (no deadlines — every request
    runs to completion) gets a few of its mid-trace requests rewritten
    into LONG prompts (near the top sequence bucket, far above the
    short-prompt body). The trace is submitted open-loop against TWO
    fresh sync decode stacks built from the same paged config: pass A
    with ``engine.prefill_chunk_tokens`` set (the token-budget planner
    interleaves prefill chunks between decode iterations), pass B
    monolithic (each long prefill is one dispatch that stalls every
    active decode slot for its full duration). Both passes replay the
    identical request sequence and must produce token-identical
    streams — the A/B moves WHEN prefill compute runs, never what it
    computes — with zero post-warmup compiles on either side.

    Inter-token latency is measured client-side: each stream's token
    emissions are timestamped at delivery, and the gap population
    (consecutive emissions within one stream, TTFT excluded) is
    aggregated across all streams. The long prefills land while other
    slots are mid-decode, so the monolithic pass's gap tail IS the
    prefill stall; chunking bounds it at one chunk's dispatch.

    Headline (gated, direction-aware in tools/bench_diff.py):

    - ``chunked_itl_p99_ms`` — p99 inter-token gap with chunking on.
      The §25 acceptance bound is <= 0.5x the monolithic pass's
      (``chunked_baseline_itl_p99_ms``, informational).
    - ``chunked_itl_improvement`` — baseline p99 / chunked p99
      (higher is better; the CI gate asserts >= 2.0).
    - ``chunked_ttft_p99_ms`` — p99 TTFT with chunking on: the cost
      side of the tradeoff (chunked prefill finishes a long prompt
      LATER than one monolithic dispatch would — §25 bounds the
      regression rather than pretending there isn't one).

    The shape matters: chunking trades EXTRA dispatches for BOUNDED
    stalls, so it only pays when one monolithic prefill costs far more
    than one dispatch — the long-context regime it exists for. The
    defaults put the leg there honestly (2048-token window, ~1900-token
    long prompts: one monolithic prefill is ~15-70x a chunk dispatch on
    CPU); shrink ``ZK_BENCH_CHUNKED_LONG`` below the dispatch-overhead
    floor and chunking rightly loses.

    Knobs: ``ZK_BENCH_CHUNKED_SEED`` (default 29),
    ``ZK_BENCH_CHUNKED_CHUNK`` (chunk size, default 256),
    ``ZK_BENCH_CHUNKED_LONG`` (long-prompt length, default 1900),
    ``ZK_BENCH_CHUNKED_LONGS`` (long arrivals, default 3),
    ``ZK_BENCH_CHUNKED_LAYERS``/``_DMODEL``/``_HEADS`` (model shape,
    defaults 4/128/4)."""
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.loadgen import poisson_burst
    from zookeeper_tpu.serving import LMServingConfig

    env = os.environ if env is None else env
    seed = int(env.get("ZK_BENCH_CHUNKED_SEED", "29"))
    chunk = int(env.get("ZK_BENCH_CHUNKED_CHUNK", "256"))
    long_len = int(env.get("ZK_BENCH_CHUNKED_LONG", "1900"))
    n_long = int(env.get("ZK_BENCH_CHUNKED_LONGS", "3"))
    num_layers = int(env.get("ZK_BENCH_CHUNKED_LAYERS", "4"))
    d_model = int(env.get("ZK_BENCH_CHUNKED_DMODEL", "128"))
    num_heads = int(env.get("ZK_BENCH_CHUNKED_HEADS", "4"))

    vocab = 61
    conf = {
        "model.num_layers": num_layers,
        "model.d_model": d_model,
        "model.num_heads": num_heads,
        "model.max_seq_len": 2048,
        "model.attention": "dense",
        "seq_len": 2048,
        "vocab_size": vocab,
        "seed": 0,
        "engine.kv_layout": "paged",
        "engine.page_size": 16,
        "engine.slots": 4,
        "engine.seq_buckets": (256, 2048),
        "engine.prefill_buckets": (1, 2, 4),
        "requests": 0,
        "verbose": False,
        "metrics_port": -1,
    }
    # The pinned workload: a short-prompt body (decode traffic) with
    # n_long LONG prompts spread through the middle — each arrives
    # while other slots are mid-decode, which is the interference
    # under test.
    trace = poisson_burst(
        seed,
        base_rate_rps=40.0,
        burst_rate_rps=120.0,
        base_s=0.3,
        burst_s=0.2,
        cooldown_s=0.1,
        vocab=vocab,
        prompt_len=4,
        max_prompt_len=24,
        new_tokens=6,
        max_new_tokens=16,
        deadline_ms=None,
    )
    reqs = trace.requests
    long_rng = np.random.default_rng(seed + 1)
    long_at = sorted(
        {
            max(1, int(len(reqs) * frac))
            for frac in np.linspace(0.3, 0.8, max(1, n_long))
        }
    )
    for idx in long_at:
        reqs[idx].prompt = long_rng.integers(
            1, vocab, size=long_len
        ).astype(np.int32)
        reqs[idx].max_new_tokens = 4
    warm_rng = np.random.default_rng(7)
    warm_prompts = [
        warm_rng.integers(1, vocab, size=8).astype(np.int32)
        for _ in range(4)
    ]
    # One long warm prompt: the monolithic pass's top-bucket prefill
    # program and BOTH passes' top-bucket decode program compile here,
    # outside the measurement.
    warm_prompts.append(
        warm_rng.integers(1, vocab, size=long_len).astype(np.int32)
    )

    def run_pass(chunk_tokens):
        svc = LMServingConfig()
        c = dict(conf)
        c["engine.prefill_chunk_tokens"] = int(chunk_tokens)
        configure(
            svc,
            c,
            name="chunked_itl_"
            + ("on" if chunk_tokens else "off"),
        )
        engine, scheduler = svc.build_service()
        try:
            for p in warm_prompts:
                scheduler.submit(p, max_new_tokens=4).result(
                    timeout=600.0
                )
            warm_compiles = engine.compile_count
            emits = [[] for _ in reqs]

            def tap(stream, sink):
                orig = stream._deliver

                def wrapped(token):
                    sink.append((time.perf_counter(), int(token)))
                    orig(token)

                stream._deliver = wrapped

            # Open-loop: submit the whole trace in arrival order, then
            # resolve — arrival ORDER (not wall-clock spacing) is what
            # puts the long prefills mid-decode, exactly like
            # loadgen.replay's deterministic time_scale=0 mode.
            t0 = time.perf_counter()
            streams = []
            for i, r in enumerate(reqs):
                s = scheduler.submit(
                    r.prompt, max_new_tokens=r.max_new_tokens
                )
                tap(s, emits[i])
                streams.append(s)
            outs = [s.result(timeout=600.0) for s in streams]
            wall = time.perf_counter() - t0
            if engine.compile_count != warm_compiles:
                raise RuntimeError(
                    f"post-warmup compiles: {warm_compiles} -> "
                    f"{engine.compile_count} "
                    f"(chunk_tokens={chunk_tokens})"
                )
            gaps = [
                (b[0] - a[0]) * 1e3
                for sink in emits
                for a, b in zip(sink, sink[1:])
            ]
            ttfts = [
                s.ttft_ms for s in streams if s.ttft_ms is not None
            ]
            return {
                "tokens": [tuple(int(t) for t in o) for o in outs],
                "gaps": gaps,
                "ttfts": ttfts,
                "wall": wall,
            }
        finally:
            svc._teardown_service(suppress=True)

    chunked = run_pass(chunk)
    base = run_pass(0)

    # Token identity: chunking moves prefill compute, never changes it.
    for i, (a, b) in enumerate(zip(chunked["tokens"], base["tokens"])):
        if a != b:
            raise AssertionError(
                f"request {i}: chunked {a} != monolithic {b}"
            )
    total_tokens = sum(len(t) for t in chunked["tokens"])

    def p99(values):
        return (
            float(np.percentile(np.asarray(values, np.float64), 99))
            if values
            else -1.0
        )

    chunked_p99 = p99(chunked["gaps"])
    base_p99 = p99(base["gaps"])
    improvement = base_p99 / chunked_p99 if chunked_p99 > 0 else -1.0
    return {
        # Gated (direction-aware in tools/bench_diff.py).
        "chunked_itl_p99_ms": round(chunked_p99, 3),
        "chunked_itl_improvement": round(improvement, 3),
        "chunked_ttft_p99_ms": round(p99(chunked["ttfts"]), 3),
        # Baseline pass (informational: context for the gated A side).
        "chunked_baseline_itl_p99_ms": round(base_p99, 3),
        "chunked_baseline_ttft_p99_ms": round(p99(base["ttfts"]), 3),
        "chunked_baseline_goodput_tokens_per_sec": round(
            total_tokens / max(base["wall"], 1e-9), 1
        ),
        # Workload shape + goodput (informational: token identity makes
        # the two passes' goodput the same WORK — only pacing differs).
        "chunked_goodput_tokens_per_sec": round(
            total_tokens / max(chunked["wall"], 1e-9), 1
        ),
        "chunked_chunk_tokens": chunk,
        "chunked_long_prompt_len": long_len,
        "chunked_long_arrivals": len(long_at),
        "chunked_requests": len(reqs),
        "chunked_generated_tokens": total_tokens,
    }


def measure_trace_overhead(env=None):
    """``ZK_BENCH_OBS=1`` leg: the host-tracing cost on the step-time
    anchor — the observability layer's acceptance number
    (docs/DESIGN.md §13 budgets it at <= 2%).

    Two measurements:

    - **Component cost** (the gated number,
      ``obs_trace_overhead_frac``): per-span enabled cost and per-call
      disabled (no-op) cost from a tight host loop — microsecond-scale
      quantities measured directly, stable on any box — scaled by the
      fused loop's spans-per-step (data_wait + dispatch) and divided by
      the measured step-time floor. This is the traced-vs-untraced
      difference computed from its parts instead of as the difference
      of two large noisy chain times: on a shared/noisy host, A/B
      chain timing of a multi-ms step cannot resolve 2% (observed
      ±20% min-to-min on the dev box), while the component numbers
      resolve it with orders of magnitude to spare.
    - **End-to-end A/B** (informational, ``obs_ab_overhead_frac``):
      interleaved traced/untraced chains of the real jitted step,
      min-per-mode ratio. On a quiet box this agrees with the
      component number; on a noisy one its scatter is visible next to
      the stable gated value.

    Knobs: ``ZK_BENCH_OBS_HIDDEN`` (Mlp width, default 256),
    ``ZK_BENCH_OBS_STEPS`` (chain length, default 30),
    ``ZK_BENCH_OBS_ROUNDS`` (A/B rounds, default 5)."""
    import jax
    import numpy as np
    import optax

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models.simple import Mlp
    from zookeeper_tpu.observability import trace
    from zookeeper_tpu.training import TrainState, make_train_step

    env = os.environ if env is None else env
    hidden = int(env.get("ZK_BENCH_OBS_HIDDEN", "256"))
    steps = int(env.get("ZK_BENCH_OBS_STEPS", "30"))
    rounds = int(env.get("ZK_BENCH_OBS_ROUNDS", "5"))

    model = Mlp()
    configure(
        model, {"hidden_units": (hidden, hidden)}, name="obs_bench_model"
    )
    module = model.build((28, 28, 1), 10)
    params, model_state = model.initialize(module, (28, 28, 1))
    state = TrainState.create(
        apply_fn=module.apply,
        params=params,
        model_state=model_state,
        tx=optax.adam(1e-3),
    )
    rng = np.random.default_rng(0)
    batch = {
        "input": rng.normal(size=(64, 28, 28, 1)).astype(np.float32),
        "target": rng.integers(0, 10, 64),
    }
    step = jax.jit(make_train_step())

    def chain(state):
        t0 = time.perf_counter()
        m = None
        for i in range(steps):
            with trace.span("data_wait", step=i):
                pass
            with trace.span("dispatch", step=i):
                state, m = step(state, batch)
        with trace.span("readback", step=steps):
            float(jax.device_get(m["loss"]))
        return time.perf_counter() - t0, state

    def span_cost_us(iters: int = 20000, reps: int = 5) -> float:
        """Per-call cost of ``with span(...): pass`` in the CURRENT
        tracing state: min over reps of a tight loop — pure host
        arithmetic, stable to sub-microsecond even on a noisy box."""
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for i in range(iters):
                with trace.span("obs_probe", step=i):
                    pass
            best = min(best, time.perf_counter() - t0)
        return best / iters * 1e6

    def call_cost_us(fn, iters: int = 20000, reps: int = 5) -> float:
        """Min-over-reps per-call cost of ``fn()`` — the same
        component-measurement protocol as span_cost_us."""
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best / iters * 1e6

    # Ledger-era per-step observability costs (docs/DESIGN.md §14):
    # the step-time watchdog's observe() and a gauge set() ride EVERY
    # step/dispatch; both are measured as components and included in
    # the gated budget. The zk-device-probe HBM poll is interval-
    # driven (default 10s), never per-step — its one-poll cost rides
    # along informationally.
    from zookeeper_tpu.observability.device import DeviceProbe
    from zookeeper_tpu.observability.registry import MetricsRegistry
    from zookeeper_tpu.observability.requests import RequestLog, next_rid
    from zookeeper_tpu.observability.watchdog import StepTimeWatchdog

    obs_reg = MetricsRegistry()
    probe_dog = StepTimeWatchdog("obs_bench_probe", registry=obs_reg)
    watchdog_us = call_cost_us(lambda: probe_dog.observe(1e-3))
    probe_gauge = obs_reg.gauge("obs_bench_probe_gauge")
    gauge_us = call_cost_us(lambda: probe_gauge.set(1.0))
    probe = DeviceProbe(registry=obs_reg)
    t0 = time.perf_counter()
    for _ in range(20):
        probe.poll_once()
    hbm_poll_us = (time.perf_counter() - t0) / 20 * 1e6
    # Request-tracing era (docs/DESIGN.md §16): rid minting and the
    # RequestLog terminal-summary append ride the serving request path
    # (submit + completion), so their component costs join the gated
    # sum — conservatively one mint + one append per step-equivalent
    # (a real step serves at most one request's bookkeeping per
    # dispatch slot; coalescing only amortizes it further).
    rid_mint_us = call_cost_us(next_rid)
    probe_log = RequestLog("obs_bench_probe", capacity=4096)
    requestlog_us = call_cost_us(
        lambda: probe_log.append(
            1,
            "ok",
            enqueue_ns=0,
            dispatch_ns=1,
            complete_ns=2,
            rows=1,
            bucket=8,
            weights_step=-1,
        )
    )

    prior_tracer = trace.get_tracer()
    state, m = step(state, batch)  # compile outside every timed window
    jax.block_until_ready(m["loss"])
    untraced_best = traced_best = float("inf")
    try:
        # Component costs: the disabled path (flag check + shared
        # no-op) and the enabled path (span object + two clock reads +
        # ring append).
        trace.disable()
        noop_us = span_cost_us()
        trace.enable()
        enabled_us = span_cost_us()
        # End-to-end A/B chains (informational; see docstring).
        for _ in range(rounds):
            trace.disable()
            dt_u, state = chain(state)
            trace.enable()
            dt_t, state = chain(state)
            untraced_best = min(untraced_best, dt_u)
            traced_best = min(traced_best, dt_t)
    finally:
        # Leave the process's tracing state as found — the ORIGINAL
        # tracer object with its ring, not a fresh one: enable() after
        # disable() would install an empty ring and orphan references
        # an outer session holds (the first-enable-wins contract).
        trace.install(prior_tracer)
    # The fused loop records two spans per step (data_wait +
    # dispatch); readback/checkpoint spans amortize over a slab or an
    # epoch and only lower the real per-step count below this. The
    # ledger era adds one watchdog observe (the inter-dispatch stream)
    # and one gauge set (EWMA mirror) per step; the sync-point MFU
    # gauges amortize over log_every and only lower the real count.
    spans_per_step = 2
    step_floor_ms = min(untraced_best, traced_best) / steps * 1e3
    overhead_frac = (
        (enabled_us - noop_us) * spans_per_step
        + watchdog_us
        + gauge_us
        + rid_mint_us
        + requestlog_us
    ) / 1e3 / step_floor_ms
    return {
        "obs_span_cost_us": round(enabled_us, 4),
        "obs_span_noop_cost_us": round(noop_us, 4),
        "obs_watchdog_cost_us": round(watchdog_us, 4),
        "obs_gauge_cost_us": round(gauge_us, 4),
        "obs_rid_mint_cost_us": round(rid_mint_us, 4),
        "obs_requestlog_append_cost_us": round(requestlog_us, 4),
        "obs_hbm_poll_us": round(hbm_poll_us, 3),
        "obs_spans_per_step": spans_per_step,
        "obs_step_time_ms_untraced": round(
            untraced_best / steps * 1e3, 4
        ),
        "obs_step_time_ms_traced": round(traced_best / steps * 1e3, 4),
        "obs_trace_overhead_frac": round(max(0.0, overhead_frac), 6),
        "obs_ab_overhead_frac": round(
            traced_best / untraced_best - 1.0, 4
        ),
        "obs_steps_per_round": steps,
        "obs_rounds": rounds,
    }


def measure_binary_throughput(env=None):
    """``ZK_BENCH_BINARY=1`` leg: Pallas-kernel-vs-reference A/B on the
    pinned packed popcount deployment forward (docs/DESIGN.md §21).

    Builds the ``ZK_BENCH_BINARY_MODEL`` (default QuickNetLarge — the
    north-star family) with ``binary_compute="xnor_popcount"`` and
    ``packed_weights=True`` (the LCE-converter deployment artifact: sign
    words + folded per-channel scales), then times the SAME packed
    forward twice — ``binary_flavor="pallas"`` (the fused §21 kernels)
    vs ``binary_flavor="reference"`` (the unfused popcount composition)
    — on identical params and inputs. Logits are asserted BIT-IDENTICAL
    between the passes (the bench re-pins the §21 exact-integer
    contract on every run) and both jits are asserted compile-free
    after warmup, so the speedup compares two certified-equal programs.

    Off-TPU the kernels run in interpret mode (a numerics vehicle, not
    a perf claim — the speedup is only meaningful on TPU, where the
    driver runs this leg; interpret-mode numbers still pin the A/B
    harness itself). Emits ``binary_kernel_images_per_sec_per_chip`` /
    ``binary_reference_images_per_sec_per_chip`` /
    ``binary_kernel_speedup`` (kernel/reference — the headline) plus
    ``binary_mfu_vs_measured_int8_peak`` (kernel-pass XLA-counted
    FLOPs over the measured int8 MXU ceiling — the honest denominator
    for binary compute, which the MXU never exceeds; -1 when cost
    analysis is unavailable) and the informational workload shape.

    Knobs: ``ZK_BENCH_BINARY_BATCH`` (default 8),
    ``ZK_BENCH_BINARY_IMAGE`` (square image side, default 64),
    ``ZK_BENCH_BINARY_ITERS`` (timed iterations, default 10),
    ``ZK_BENCH_BINARY_MODEL`` (default QuickNetLarge)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from zookeeper_tpu import models as zoo
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import Model
    from zookeeper_tpu.ops.packed import pack_quantconv_params

    env = os.environ if env is None else env
    batch_size = int(env.get("ZK_BENCH_BINARY_BATCH", "8"))
    image = int(env.get("ZK_BENCH_BINARY_IMAGE", "64"))
    iters = int(env.get("ZK_BENCH_BINARY_ITERS", "10"))
    model_name = env.get("ZK_BENCH_BINARY_MODEL", "QuickNetLarge")
    model_cls = getattr(zoo, model_name, None)
    if not (isinstance(model_cls, type) and issubclass(model_cls, Model)):
        raise ValueError(
            f"ZK_BENCH_BINARY_MODEL={model_name!r} is not in the zoo."
        )
    required = {"binary_compute", "packed_weights", "binary_flavor"}
    missing = required - set(model_cls.__component_fields__)
    if missing:
        raise ValueError(
            f"ZK_BENCH_BINARY_MODEL={model_name!r} has no packed binary "
            f"deployment path (missing {sorted(missing)})."
        )
    on_tpu = jax.default_backend() == "tpu"

    def build(packed, flavor):
        model = model_cls()
        configure(
            model,
            {
                "binary_compute": "xnor_popcount",
                "packed_weights": packed,
                # Interpret mode is the off-TPU numerics vehicle only;
                # on TPU the compiled Mosaic kernels run.
                "pallas_interpret": not on_tpu,
                "binary_flavor": flavor,
            },
            name="binary_bench_model",
        )
        return model.build((image, image, 3), num_classes=1000)

    x = jnp.asarray(
        np.random.default_rng(0).normal(
            size=(batch_size, image, image, 3)
        ),
        jnp.float32,
    )
    # Train-float params -> packed deployment params, exactly the
    # LCE-converter path the zoo round-trip test certifies.
    float_module = build(packed=False, flavor="reference")
    variables = float_module.init(jax.random.PRNGKey(0), x, training=False)
    packed_vars = {
        **variables,
        "params": pack_quantconv_params(variables["params"]),
    }

    def timed_forward(flavor):
        module = build(packed=True, flavor=flavor)
        fwd = jax.jit(
            lambda v, xb: module.apply(v, xb, training=False)
        )
        y = jax.block_until_ready(fwd(packed_vars, x))  # warmup compile
        start = time.perf_counter()
        for _ in range(iters):
            y = jax.block_until_ready(fwd(packed_vars, x))
        elapsed = (time.perf_counter() - start) / iters
        if fwd._cache_size() != 1:
            raise RuntimeError(
                f"binary leg ({flavor}) recompiled mid-loop "
                f"(cache size {fwd._cache_size()}); the timing is invalid."
            )
        flops = cost_flops(fwd.lower(packed_vars, x).compile())
        return np.asarray(y), elapsed, flops

    y_kernel, t_kernel, kernel_flops = timed_forward("pallas")
    y_reference, t_reference, _ = timed_forward("reference")
    if not np.array_equal(y_kernel, y_reference):
        raise RuntimeError(
            "binary leg: kernel and reference logits differ — the §21 "
            "bit-identity contract is broken; the A/B is meaningless."
        )
    n_chips = 1  # single-device forward: jit places it on one chip
    int8_peak, int8_source = resolve_int8_peak(env)
    mfu_int8 = (
        round(kernel_flops / t_kernel / int8_peak, 4)
        if kernel_flops is not None and int8_peak is not None
        else -1.0
    )
    return {
        "binary_kernel_images_per_sec_per_chip": round(
            batch_size / t_kernel / n_chips, 1
        ),
        "binary_reference_images_per_sec_per_chip": round(
            batch_size / t_reference / n_chips, 1
        ),
        "binary_kernel_speedup": round(t_reference / t_kernel, 3)
        if t_kernel > 0
        else -1.0,
        "binary_mfu_vs_measured_int8_peak": mfu_int8,
        "binary_int8_peak_source": int8_source,
        # Informational workload shape + execution vehicle.
        "binary_model": model_name,
        "binary_batch": batch_size,
        "binary_image": image,
        "binary_kernel_flavor": "pallas" if on_tpu else "pallas_interpret",
    }


# The LM perf leg's pinned workload: the configuration behind
# BASELINE.md's 187k tokens/s claim (TransformerLM 4L/d512/h8, flash
# attention, s=8192, b=4, vocab 1024, bf16) — pinned so the number is
# comparable round over round and a flash auto-block regression moves
# it visibly. ZK_BENCH_LM_SEQ / ZK_BENCH_LM_BATCH override for sweeps.
LM_BENCH_CONFIG = {
    "num_layers": 4,
    "d_model": 512,
    "num_heads": 8,
    "vocab": 1024,
    "seq": 8192,
    "batch": 4,
}


def lm_bench_flash_blocks(seq, d_model=None, num_heads=None, itemsize=2):
    """The flash auto-block sizes the LM leg's pinned config selects
    (bf16 operands by default) — recorded in the bench JSON so a
    flash-policy regression (a changed default demoting the measured
    sweep winner) becomes driver-visible as a moved number, not just a
    slower step time."""
    from zookeeper_tpu.ops.attention import _default_flash_blocks

    d_model = LM_BENCH_CONFIG["d_model"] if d_model is None else d_model
    num_heads = LM_BENCH_CONFIG["num_heads"] if num_heads is None else num_heads
    return _default_flash_blocks(
        seq, None, None, head_dim=d_model // num_heads, itemsize=itemsize
    )


def measure_lm_throughput(peak_flops=None, env=None):
    """``ZK_BENCH_LM=1`` leg: tokens/s/chip of the full jitted LM train
    step (fwd + bwd through the flash-attention custom_vjp + Adam) at
    the pinned config above, with the bench's standard two-chain-length
    marginal timing and the roofline plausibility floor (when XLA cost
    analysis and a peak anchor are available). Returns the metrics dict
    or raises — the caller treats failure as omit-and-warn, never as
    losing the primary metric."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import TransformerLM
    from zookeeper_tpu.parallel import DataParallelPartitioner
    from zookeeper_tpu.training import TrainState, make_train_step
    from zookeeper_tpu.training.benchmark import time_marginal

    env = os.environ if env is None else env
    seq = int(env.get("ZK_BENCH_LM_SEQ", str(LM_BENCH_CONFIG["seq"])))
    batch_size = int(
        env.get("ZK_BENCH_LM_BATCH", str(LM_BENCH_CONFIG["batch"]))
    )
    vocab = LM_BENCH_CONFIG["vocab"]

    model = TransformerLM()
    configure(
        model,
        {
            "num_layers": LM_BENCH_CONFIG["num_layers"],
            "d_model": LM_BENCH_CONFIG["d_model"],
            "num_heads": LM_BENCH_CONFIG["num_heads"],
            "max_seq_len": seq,
            "compute_dtype": "bfloat16",
        },
        name="lm_model",
    )
    module = model.build((seq,), num_classes=vocab)
    params, model_state = model.initialize(module, (seq,))
    state = TrainState.create(
        apply_fn=module.apply,
        params=params,
        model_state=model_state,
        tx=optax.adam(1e-3),
    )
    partitioner = DataParallelPartitioner()
    configure(partitioner, {}, name="lm_partitioner")
    partitioner.setup()
    state = partitioner.shard_state(state)
    jit_step = partitioner.compile_step(make_train_step(), state)

    rng = np.random.default_rng(0)
    lm_batch = jax.device_put(
        {
            "input": jnp.asarray(
                rng.integers(0, vocab, (batch_size, seq)), jnp.int32
            ),
            "target": jnp.asarray(
                rng.integers(0, vocab, (batch_size, seq)), jnp.int32
            ),
        },
        partitioner.batch_sharding(),
    )
    lowered = jit_step.lower(state, lm_batch)
    compiled = lowered.compile()
    lm_cost = cost_flops(compiled)  # shared wrapper; None when absent

    def run_chain(k):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(k):
            state, metrics = compiled(state, lm_batch)
        float(jax.device_get(metrics["loss"]))
        return time.perf_counter() - t0

    run_chain(2)  # Warmup.
    min_plausible = (
        lm_cost / (4.0 * peak_flops)
        if lm_cost is not None and peak_flops is not None
        else 1e-5
    )
    step_time = -1.0
    for n1, n2, rounds in ((4, 12, 6), (8, 32, 8)):
        step_time = time_marginal(run_chain, n1, n2, rounds=rounds)
        if step_time > min_plausible:
            break
    if step_time <= min_plausible:
        raise RuntimeError(
            f"LM marginal {step_time * 1e3:.3f} ms/step below the "
            f"{min_plausible * 1e3:.3f} ms roofline floor at all chain "
            "lengths (host jitter)"
        )
    n_chips = jax.device_count()
    lm_block_q, lm_block_k = lm_bench_flash_blocks(seq)
    metrics = {
        "lm_tokens_per_sec_per_chip": round(
            batch_size * seq / step_time / max(1, n_chips), 1
        ),
        "lm_step_time_ms": round(step_time * 1e3, 2),
        "lm_seq_len": seq,
        "lm_batch_size": batch_size,
        "lm_model": "transformer_lm_{num_layers}l_d{d_model}_h{num_heads}".format(
            **LM_BENCH_CONFIG
        ),
        "lm_attention": "flash",
        # Flash-policy + parallelism visibility: the auto-selected
        # block sizes this run compiled with, and the sequence-parallel
        # degree (1 on the single-chip leg; the dp x sp leg reports its
        # own sp_* metrics).
        "lm_flash_block_q": int(lm_block_q),
        "lm_flash_block_k": int(lm_block_k),
        "lm_sp_degree": 1,
    }
    if lm_cost is not None:
        metrics["lm_per_chip_step_tflops"] = round(lm_cost / 1e12, 2)
    return metrics


def measure_sp_ring_throughput(env=None):
    """``ZK_BENCH_SP=1`` leg: tokens/s of one fwd+bwd ring-attention
    step at long sequence on a sequence-parallel mesh, measured for
    BOTH ring schedules — ``sp_tokens_per_sec_overlap`` (the
    double-buffered prefetch default) vs ``sp_tokens_per_sec_sequential``
    (permutes issued after the block compute) — so a scheduling
    regression in either direction is a moved number. The op is timed
    directly (not the full LM step): the schedules differ ONLY inside
    the ring loop, and the surrounding transformer would dilute the
    comparison with identical work.

    Knobs: ZK_BENCH_SP_SEQ (default 8192), ZK_BENCH_SP_DEGREE (default
    min(8, devices)), ZK_BENCH_SP_FLAVOR ("ring" = dense block compute,
    compiles on every backend; "ring_flash" for real chips — interpret-
    mode Pallas would dominate the timing off-TPU), ZK_BENCH_SP_BATCH,
    ZK_BENCH_SP_HEADS."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from zookeeper_tpu.ops import ring_attention, ring_flash_attention
    from zookeeper_tpu.training.benchmark import time_marginal

    env = os.environ if env is None else env
    seq = int(env.get("ZK_BENCH_SP_SEQ", "8192"))
    sp = int(env.get("ZK_BENCH_SP_DEGREE", str(min(8, jax.device_count()))))
    flavor = env.get("ZK_BENCH_SP_FLAVOR", "ring")
    batch = int(env.get("ZK_BENCH_SP_BATCH", "1"))
    heads = int(env.get("ZK_BENCH_SP_HEADS", "4"))
    head_dim = 64
    if flavor not in ("ring", "ring_flash"):
        raise ValueError(
            f"ZK_BENCH_SP_FLAVOR={flavor!r}: expected ring/ring_flash."
        )
    if not 1 <= sp <= jax.device_count():
        # A silently-truncated ring would report tokens/s against a
        # misstated sp_degree; fail the leg loudly instead.
        raise ValueError(
            f"ZK_BENCH_SP_DEGREE={sp}: need 1 <= degree <= device "
            f"count ({jax.device_count()})."
        )
    fn = ring_flash_attention if flavor == "ring_flash" else ring_attention
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(
            jnp.asarray(
                rng.normal(size=(batch, seq, heads, head_dim)).astype(
                    np.float32
                )
                * 0.02
            ),
            NamedSharding(mesh, P(None, "sp")),
        )
        for _ in range(3)
    )

    metrics = {
        "sp_seq_len": seq,
        "sp_degree": sp,
        "sp_flavor": flavor,
        "sp_batch_size": batch,
    }
    for name, overlap in (("overlap", True), ("sequential", False)):
        # fwd + bwd (the training shape): grads w.r.t. q/k/v all ride
        # the ring, so both the forward and the inverse rotations of
        # the schedule under test are in the timed program.
        step = jax.jit(
            jax.grad(
                lambda q, k, v, _ov=overlap: fn(
                    q, k, v, mesh=mesh, seq_axis="sp", causal=True,
                    overlap=_ov,
                )
                .astype(jnp.float32)
                .sum(),
                argnums=(0, 1, 2),
            )
        )

        def run_chain(n):
            t0 = time.perf_counter()
            g = None
            for _ in range(n):
                g = step(q, k, v)
            jax.block_until_ready(g)
            return time.perf_counter() - t0

        run_chain(1)  # Warmup (compile).
        step_time = time_marginal(run_chain, 1, 3, rounds=3)
        if step_time <= 0:
            raise RuntimeError(
                f"non-positive SP marginal {step_time:.6f}s (jitter)"
            )
        metrics[f"sp_tokens_per_sec_{name}"] = round(
            batch * seq / step_time, 1
        )
        metrics[f"sp_step_time_ms_{name}"] = round(step_time * 1e3, 2)
    return metrics


def check_device_reachable() -> None:
    """Refuse to bench a CPU that nobody asked for: the platform is
    ``tpu`` unless ``JAX_PLATFORMS`` (or the ``jax_platforms`` config)
    asks for the CPU first. A TPU that failed to initialise would
    otherwise turn the bench into a multi-hour CPU run whose numbers
    read as the chip's."""
    import jax

    backend = jax.default_backend()
    requested_cpu = str(
        jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    ).startswith("cpu")
    if backend != "tpu" and not requested_cpu:
        raise RuntimeError(
            f"jax's default backend is {backend!r}, not 'tpu', and "
            "JAX_PLATFORMS=cpu was not requested — the accelerator "
            "backend failed to initialise. Refusing to run the bench "
            "on a fallback device."
        )


def parse_args(argv=None):
    """Bench CLI: ``--compare PREV.json`` gates this run against a
    previous BENCH/MULTICHIP artifact via ``tools.bench_diff`` (exit 3
    on regression); everything else stays env-var-driven (ZK_BENCH_*)
    so the driver contract is unchanged."""
    import argparse

    parser = argparse.ArgumentParser(description="north-star bench")
    parser.add_argument(
        "--compare",
        default=None,
        metavar="PREV_JSON",
        help="previous bench JSON (raw line or driver wrapper) to diff "
        "against; regressions beyond per-metric tolerance exit 3",
    )
    parser.add_argument(
        "--compare-out",
        default=None,
        metavar="DIFF_JSON",
        help="write the full diff JSON here (CI artifact)",
    )
    return parser.parse_args(argv)


def main(argv=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    args = parse_args(argv)
    check_device_reachable()
    from zookeeper_tpu.parallel.distributed import enable_compile_cache

    enable_compile_cache()
    # Legs that raised: each is reported on stderr where it failed, the
    # result line still prints, and the process then exits non-zero.
    failed_legs = []
    # Resolve early: a malformed ZK_BENCH_COMPILER_OPTIONS must fail
    # before the (minutes-long) model build + lower, not at compile.
    compiler_options = resolve_compiler_options()

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.parallel import DataParallelPartitioner
    from zookeeper_tpu.training import TrainState, make_train_step

    input_shape = (224, 224, 3)
    num_classes = 1000
    (
        model,
        model_name,
        batch_size,
        binary_compute,
        pack_residuals,
    ) = resolve_bench_config()
    module = model.build(input_shape, num_classes=num_classes)
    params, model_state = model.initialize(module, input_shape)
    # Snapshot the weights for the serving anchor NOW: the donated train
    # step below consumes its input state's buffers, and on some
    # device_put/sharding combinations those can alias these arrays —
    # binding deleted arrays later would silently drop the serve_*
    # metrics (the except guard would eat the error).
    serve_weights = None
    if _env_flag(os.environ, "ZK_BENCH_SERVE"):
        serve_weights = jax.device_get((params, model_state))
    state = TrainState.create(
        apply_fn=module.apply,
        params=params,
        model_state=model_state,
        tx=optax.adam(1e-3),
    )

    # Use every local chip (data-parallel): throughput/chip stays honest
    # on multi-chip hosts instead of dividing one chip's work by N.
    partitioner = DataParallelPartitioner()
    configure(partitioner, {}, name="partitioner")
    partitioner.setup()
    state = partitioner.shard_state(state)
    jit_step = partitioner.compile_step(make_train_step(), state)
    batch_sharding = partitioner.batch_sharding()

    rng = np.random.default_rng(0)
    batch = jax.device_put(
        {
            "input": jnp.asarray(
                rng.normal(size=(batch_size, *input_shape)), jnp.bfloat16
            ),
            "target": jnp.asarray(rng.integers(0, num_classes, batch_size)),
        },
        batch_sharding,
    )

    # AOT-compile ONCE: the same executable serves the timed runs and the
    # FLOPs cost analysis (a second trace/compile of this graph costs
    # minutes at ImageNet shapes).
    lowered = jit_step.lower(state, batch)
    if compiler_options is None:
        compiled_step = lowered.compile()
    else:
        compiled_step = lowered.compile(compiler_options=compiler_options)

    # Model FLOPs from XLA's cost analysis of the compiled train step
    # (includes fwd + bwd + optimizer as actually executed). NOTE: for an
    # SPMD executable this is already the PER-DEVICE partitioned module's
    # FLOPs — do not divide by n_chips again. Computed before timing: it
    # also sets the plausibility floor for the measured step time. Goes
    # through the shared cost-analysis wrapper (None/[dict]/missing-key
    # tolerant) the ledger and summary use.
    cost = cost_flops(compiled_step)

    # Resolve the MFU anchor BEFORE timing: the plausibility floor below
    # must scale with the chip actually under test (deriving it from the
    # v5e's recorded peak would reject legitimate marginals on any chip
    # >4x a v5e), and resolving it here also keeps the peak measurement's own
    # traffic out of the timed window. With no cost analysis there is no
    # floor and no MFU — skip the (expensive, on-chip) measurement
    # entirely rather than burning matmul chains on a number nothing
    # reads.
    if cost is not None:
        peak_flops, peak_source = resolve_peak_flops()
        # Second anchor when the binary convs run on the int8 MXU path:
        # the bf16-anchored MFU is conservative by convention (the int8
        # ceiling is ~2x higher), so the dual-anchor output states the
        # step's position against BOTH rooflines.
        int8_peak = int8_source = None
        if binary_compute == "int8":
            int8_peak, int8_source = resolve_int8_peak()

    def run_chain(n):
        """n chained steps ended by ``block_until_ready`` on the last
        step's loss."""
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = compiled_step(state, batch)
        jax.block_until_ready(metrics["loss"])
        return time.perf_counter() - t0

    run_chain(2)  # Warmup.

    # Each chain pays one fixed sync latency; the shared
    # two-chain-length marginal (time_marginal docstring) cancels it.
    # More rounds = better minima vs host jitter. Jitter varies by
    # SESSION (BASELINE.md round 5 observed inverted marginals on chains
    # that were ample in earlier rounds), so an implausible marginal —
    # non-positive, or faster than 4x the hardware roofline for this
    # step's own FLOPs — escalates to longer chains, and if even the
    # longest chains stay implausible the bench FAILS instead of
    # reporting garbage throughput.
    min_plausible = (
        cost / (4.0 * peak_flops)
        if cost is not None and peak_flops is not None
        else 1e-5
    )
    # First tier starts at 60 marginal steps (~1.3 s of work on the
    # north star): at the (5, 25) chains rounds 2-4 used, a noisy
    # session's jitter is a few percent of the marginal; these lengths
    # keep the relative error well under 1% for ~90 s of extra timing.
    tiers = ((15, 75, 8), (40, 200, 10))
    step_time = -1.0
    for i, (n1, n2, rounds) in enumerate(tiers):
        step_time = time_marginal(run_chain, n1, n2, rounds=rounds)
        if step_time > min_plausible:
            break
        print(
            f"marginal {step_time * 1e3:.3f} ms/step from chains "
            f"({n1}, {n2}) is implausible (< {min_plausible * 1e3:.3f} ms"
            " roofline floor; host jitter)"
            + ("; escalating chain lengths..." if i + 1 < len(tiers) else ""),
            file=sys.stderr,
            flush=True,
        )
    if step_time <= min_plausible:
        raise RuntimeError(
            f"Bench could not obtain a plausible step time (last marginal "
            f"{step_time * 1e3:.3f} ms <= floor {min_plausible * 1e3:.3f} "
            "ms) even at the longest chain lengths — host too unstable; "
            "rerun on a quieter host."
        )

    # Steady-state END-TO-END loop time through the fused multi-step
    # engine (training.step.build_multi_step): ``unroll`` copies of the
    # batch resident as one HBM slab, chains of back-to-back slab
    # dispatches with deferred readback. step_time_ms stays the
    # compute-only anchor; loop_time_ms includes per-slab Python
    # dispatch + host bookkeeping amortized over unroll steps — the
    # overhead the engine exists to remove, now visible in the BENCH
    # trajectory. ZK_BENCH_UNROLL overrides (<= 1 skips).
    unroll = int(os.environ.get("ZK_BENCH_UNROLL", "8"))
    loop_time = None
    if unroll > 1:
        try:
            from zookeeper_tpu.training import build_multi_step
            from zookeeper_tpu.training.benchmark import (
                measure_fused_loop_time,
            )

            slab = jax.device_put(
                jax.tree.map(lambda x: jnp.stack([x] * unroll), batch),
                partitioner.slab_sharding(),
            )
            multi_step = partitioner.compile_multi_step(
                build_multi_step(make_train_step()),
                state,
                donate_state=True,
                donate_slab=False,  # the slab is re-driven every chain
            )
            # The fused loop CONTAINS the full step compute, so a
            # marginal below ~0.8x the measured step time is jitter,
            # not speed — escalate chain lengths, then discard.
            loop_floor = 0.8 * step_time
            for ln1, ln2, lrounds in ((4, 12, 6), (8, 40, 8)):
                loop_time, state = measure_fused_loop_time(
                    multi_step, state, slab,
                    rounds=lrounds, n1=ln1, n2=ln2,
                )
                if loop_time > loop_floor:
                    break
            if loop_time <= loop_floor:
                print(
                    f"fused-loop marginal {loop_time * 1e3:.3f} ms/step "
                    f"below the {loop_floor * 1e3:.3f} ms plausibility "
                    "floor at all chain lengths; omitting loop_time_ms",
                    file=sys.stderr,
                    flush=True,
                )
                loop_time = None
        except Exception as e:  # never lose the primary metric
            failed_legs.append("loop_time")
            print(
                f"fused-loop measurement failed ({e}); omitting "
                "loop_time_ms",
                file=sys.stderr,
                flush=True,
            )
            loop_time = None

    n_chips = jax.device_count()
    images_per_sec_per_chip = batch_size / step_time / max(1, n_chips)

    # Serving-side anchors (env-gated: the serving engine compiles its
    # own forward, minutes at ImageNet shapes): steady-state latency and
    # throughput of the REAL inference path — zookeeper_tpu.serving's
    # bucketed, pre-compiled, padded engine dispatch, host input
    # staging included (requests arrive on host). serve_qps_per_chip
    # uses the shared two-chain-length marginal (time_marginal) like
    # every other anchor; the p50/p99 percentiles come from repeated
    # SHORT chains (per-dispatch = chain/length), which amortize the
    # fixed sync latency the same way while preserving dispatch-to-
    # dispatch spread. ZK_BENCH_SERVE_BUCKET overrides the bucket (32
    # default — the batcher's steady-state micro-batch).
    serve_metrics = None
    if serve_weights is not None:
        try:
            from zookeeper_tpu.serving import InferenceEngine
            from zookeeper_tpu.training.benchmark import (
                measure_serving_latency,
            )

            serve_bucket = int(os.environ.get("ZK_BENCH_SERVE_BUCKET", "32"))
            engine = InferenceEngine()
            configure(
                engine,
                {"batch_buckets": (serve_bucket,)},
                name="serve_engine",
            )
            engine.bind(
                module.apply,
                serve_weights[0],
                serve_weights[1],
                input_shape,
                dtype=jnp.bfloat16,
                partitioner=partitioner,
            )
            engine.warmup()  # compile outside the timed window
            xs = np.asarray(
                rng.normal(size=(serve_bucket, *input_shape)),
                np.dtype(jnp.bfloat16),
            )
            mean_s, p50_s, p99_s = measure_serving_latency(engine, xs)
            if mean_s <= 0:
                raise RuntimeError(
                    f"non-positive serve marginal {mean_s:.6f}s "
                    "(host jitter)"
                )
            serve_metrics = {
                "serve_bucket": serve_bucket,
                "serve_p50_ms": round(p50_s * 1e3, 3),
                "serve_p99_ms": round(p99_s * 1e3, 3),
                "serve_qps_per_chip": round(
                    serve_bucket / mean_s / max(1, n_chips), 1
                ),
            }
        except Exception as e:  # never lose the primary metric
            failed_legs.append("serve")
            print(
                f"serving measurement failed ({e}); omitting serve_*",
                file=sys.stderr,
                flush=True,
            )
            serve_metrics = None

    # LM perf leg (env-gated: a second multi-minute compile at s=8192).
    lm_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_LM"):
        try:
            lm_metrics = measure_lm_throughput(
                peak_flops=peak_flops if cost is not None else None
            )
        except Exception as e:  # never lose the primary metric
            failed_legs.append("lm")
            print(
                f"LM bench leg failed ({e}); omitting lm_*",
                file=sys.stderr,
                flush=True,
            )
            lm_metrics = None

    # Sequence-parallel ring schedule A/B leg (env-gated: a long-
    # sequence multi-device compile): overlapped vs sequential ring
    # tokens/s, so ring-schedule regressions are driver-visible.
    sp_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_SP"):
        try:
            sp_metrics = measure_sp_ring_throughput()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("sp")
            print(
                f"SP ring leg failed ({e}); omitting sp_*",
                file=sys.stderr,
                flush=True,
            )
            sp_metrics = None

    # Host input-pipeline leg (CPU-only, seconds): the augmented batch-
    # assembly rate the driver machine-checks round over round — the
    # one stage where the framework's own host code was the measured
    # bottleneck.
    host_metrics = None
    try:
        host_metrics = measure_host_aug_throughput()
    except Exception as e:  # never lose the primary metric
        failed_legs.append("host_aug")
        print(
            f"host pipeline leg failed ({e}); omitting host_aug_*",
            file=sys.stderr,
            flush=True,
        )
        host_metrics = None

    # Recovery leg (always-on, seconds): supervisor-restart ->
    # first-post-resume-step latency through the real kill/save/restore
    # path (docs/DESIGN.md §10 recovery-time budget).
    recovery_metrics = None
    try:
        recovery_metrics = measure_recovery_leg()
    except Exception as e:  # never lose the primary metric
        failed_legs.append("recovery")
        print(
            f"recovery leg failed ({e}); omitting recovery_*",
            file=sys.stderr,
            flush=True,
        )
        recovery_metrics = None

    # Load-shedding leg (env-gated: spins a worker thread + a few
    # hundred dispatches): shed rate + latency percentiles under
    # deliberate overload through the MicroBatcher.
    shed_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_SHED"):
        try:
            shed_metrics = measure_shed_overload()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("shed")
            print(
                f"shed leg failed ({e}); omitting shed_*",
                file=sys.stderr,
                flush=True,
            )
            shed_metrics = None

    # Checkpoint-stall leg (env-gated: several real orbax saves):
    # sync vs async training-thread save stall + steps overlapped per
    # async save — the async checkpointer's acceptance number.
    ckpt_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_CKPT"):
        try:
            ckpt_metrics = measure_checkpoint_stall()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("ckpt")
            print(
                f"checkpoint stall leg failed ({e}); omitting ckpt_*",
                file=sys.stderr,
                flush=True,
            )
            ckpt_metrics = None

    # Decode-serving leg (env-gated: a full continuous-batching serve of
    # ZK_BENCH_DECODE_REQUESTS streams): tokens/s/chip + TTFT p99 under
    # mixed prefill/decode traffic, compile-free-after-warmup asserted.
    decode_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_DECODE"):
        try:
            decode_metrics = measure_decode_throughput()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("decode")
            print(
                f"decode leg failed ({e}); omitting decode_*",
                file=sys.stderr,
                flush=True,
            )
            decode_metrics = None

    # Shared-prefix reuse leg (env-gated: warm-vs-cold TTFT A/B on the
    # paged-KV engine at the shared-system-prompt workload): streams
    # asserted token-identical, prefix_ttft_speedup is the headline.
    prefix_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_PREFIX"):
        try:
            prefix_metrics = measure_prefix_reuse()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("prefix")
            print(
                f"prefix leg failed ({e}); omitting prefix_*",
                file=sys.stderr,
                flush=True,
            )
            prefix_metrics = None

    # Speculative-decode leg (env-gated: spec-vs-plain A/B on one
    # engine at the pinned zero-tail high-acceptance workload): streams
    # asserted token-identical, spec_speedup is the headline.
    spec_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_SPEC"):
        try:
            spec_metrics = measure_speculative_throughput()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("spec")
            print(
                f"speculative leg failed ({e}); omitting spec_*",
                file=sys.stderr,
                flush=True,
            )
            spec_metrics = None

    # Disaggregated-serving leg (env-gated: the same prompt set through
    # the single-mesh baseline and the prefill/decode split with KV
    # page handoff): streams asserted token-identical between the
    # topologies, both legs compile-free; transfer_ms_p50 prices the
    # handoff.
    disagg_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_DISAGG"):
        try:
            disagg_metrics = measure_disagg_throughput()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("disagg")
            print(
                f"disagg leg failed ({e}); omitting disagg_*",
                file=sys.stderr,
                flush=True,
            )
            disagg_metrics = None

    # Fleet-serving leg (env-gated: spawns 2 x n_replicas REAL worker
    # processes across the two passes): prefix-affinity routing vs
    # round-robin on a token-identical multi-turn stream — the §20
    # warm-prefill TTFT win preserved (or destroyed) fleet-wide.
    fleet_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_FLEET"):
        try:
            fleet_metrics = measure_fleet_throughput()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("fleet")
            print(
                f"fleet leg failed ({e}); omitting fleet_*",
                file=sys.stderr,
                flush=True,
            )
            fleet_metrics = None

    # Trace-SLO leg (env-gated: two fresh sync decode stacks replay a
    # pinned deadline-carrying burst): overload guardrails on vs off —
    # goodput held, admitted-tail TTFT improved, sheds precise
    # (docs/DESIGN.md §24).
    trace_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_TRACE"):
        try:
            trace_metrics = measure_trace_slo()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("trace")
            print(
                f"trace SLO leg failed ({e}); omitting trace_*",
                file=sys.stderr,
                flush=True,
            )
            trace_metrics = None

    # Chunked-prefill leg (env-gated: two fresh sync decode stacks
    # replay a pinned long-prompt-interference trace): chunked vs
    # monolithic prefill — token-identical streams, decode ITL tail
    # halved or better (docs/DESIGN.md §25).
    chunked_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_CHUNKED"):
        try:
            chunked_metrics = measure_chunked_interference()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("chunked")
            print(
                f"chunked prefill leg failed ({e}); omitting chunked_*",
                file=sys.stderr,
                flush=True,
            )
            chunked_metrics = None

    # Observability-overhead leg (env-gated: interleaved traced/untraced
    # step chains): host-span tracing cost on the step-time anchor —
    # the <= 2% budget docs/DESIGN.md §13 commits to.
    obs_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_OBS"):
        try:
            obs_metrics = measure_trace_overhead()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("obs")
            print(
                f"trace overhead leg failed ({e}); omitting obs_*",
                file=sys.stderr,
                flush=True,
            )
            obs_metrics = None

    # Binary-kernel A/B leg (env-gated: a second full model compile x2
    # plus the packed-param conversion): fused §21 Pallas kernels vs
    # the unfused popcount reference on the pinned packed deployment
    # forward, logits asserted bit-identical between the passes.
    binary_metrics = None
    if _env_flag(os.environ, "ZK_BENCH_BINARY"):
        try:
            binary_metrics = measure_binary_throughput()
        except Exception as e:  # never lose the primary metric
            failed_legs.append("binary")
            print(
                f"binary kernel leg failed ({e}); omitting binary_*",
                file=sys.stderr,
                flush=True,
            )
            binary_metrics = None

    extras = {
        "model": model_name,
        "batch_size": batch_size,
        "binary_compute": binary_compute,
        "pack_residuals": pack_residuals,
        "step_time_ms": round(step_time * 1e3, 2),
        "n_chips": n_chips,
        # Provenance stamp (git sha, jax version, device kind, schema
        # version): the JSON line is self-describing without the driver
        # log around it.
        **bench_metadata(device_kind=jax.devices()[0].device_kind),
    }
    if lm_metrics is not None:
        extras.update(lm_metrics)
    if sp_metrics is not None:
        extras.update(sp_metrics)
    if host_metrics is not None:
        extras.update(host_metrics)
    if recovery_metrics is not None:
        extras.update(recovery_metrics)
    if shed_metrics is not None:
        extras.update(shed_metrics)
    if ckpt_metrics is not None:
        extras.update(ckpt_metrics)
    if decode_metrics is not None:
        extras.update(decode_metrics)
    if prefix_metrics is not None:
        extras.update(prefix_metrics)
    if spec_metrics is not None:
        extras.update(spec_metrics)
    if disagg_metrics is not None:
        extras.update(disagg_metrics)
    if fleet_metrics is not None:
        extras.update(fleet_metrics)
    if trace_metrics is not None:
        extras.update(trace_metrics)
    if chunked_metrics is not None:
        extras.update(chunked_metrics)
    if obs_metrics is not None:
        extras.update(obs_metrics)
    if binary_metrics is not None:
        extras.update(binary_metrics)
    if loop_time is not None:
        extras["unroll"] = unroll
        extras["loop_time_ms"] = round(loop_time * 1e3, 2)
        extras["loop_images_per_sec_per_chip"] = round(
            batch_size / loop_time / max(1, n_chips), 1
        )
    if serve_metrics is not None:
        extras.update(serve_metrics)
    if compiler_options is not None:
        extras["compiler_options"] = compiler_options
    if cost is not None and peak_flops is not None:
        mfu = cost / step_time / peak_flops
        extras["per_chip_step_tflops"] = round(cost / 1e12, 2)
        vs_baseline = round(mfu, 4)
        extras["mfu_vs_measured_bf16_peak"] = vs_baseline
        extras["bf16_peak_tflops"] = round(peak_flops / 1e12, 1)
        extras["bf16_peak_source"] = peak_source
        if int8_peak is not None:
            extras["mfu_vs_measured_int8_peak"] = round(
                cost / step_time / int8_peak, 4
            )
            extras["int8_peak_tops"] = round(int8_peak / 1e12, 1)
            extras["int8_peak_source"] = int8_source
    else:
        vs_baseline = -1.0  # cost analysis or peak anchor unknown

    # Stable name for the default north-star run (continuity across
    # BENCH_r*.json); other models get a lowercased variant.
    metric_model = {
        "QuickNetLarge": "quicknet_large",
        "QuickNet": "quicknet",
        "ResNet50": "resnet50",
        "BinaryAlexNet": "binary_alexnet",
    }.get(model_name, model_name.lower())
    result = {
        "metric": f"{metric_model}_train_images_per_sec_per_chip",
        "value": round(images_per_sec_per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": vs_baseline,
        **extras,
    }
    print(json.dumps(result))
    if failed_legs:
        print(
            f"bench legs failed: {', '.join(failed_legs)}",
            file=sys.stderr,
            flush=True,
        )
        sys.exit(1)

    if args.compare:
        # Regression gate (tools/bench_diff.py): diff this run against
        # the previous artifact AFTER the result line printed — the
        # measurement must never be lost to a failed gate.
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tools"))
        import bench_diff

        previous = bench_diff.load_bench_json(args.compare)
        diff = bench_diff.compare(result, previous)
        print(
            f"--compare vs {args.compare}:\n{diff.report()}",
            file=sys.stderr,
            flush=True,
        )
        if args.compare_out:
            with open(args.compare_out, "w") as f:
                json.dump(diff.as_dict(), f, indent=1)
        if not diff.ok:
            raise SystemExit(3)


if __name__ == "__main__":
    main()
