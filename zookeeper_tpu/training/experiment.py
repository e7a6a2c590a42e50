"""Experiment components: the configurable training loop.

Reference contract (SURVEY.md §2.2/§3.3): ``Experiment`` is an abstract
``@task``-style component whose ``run()`` owns training. The canonical
``TrainingExperiment`` here replaces the Keras compile/fit path with:

    loader.batches() ──prefetch──► device memory (sharded)
    state = TrainState(params, opt_state, batch_stats)
    step  = partitioner.compile_step(make_train_step(...))   # jit/pjit
    for epoch: for batch: state, metrics = step(state, batch)

Throughput (examples/sec) is measured natively since images/sec/chip is the
north-star metric (BASELINE.md).
"""

import json
import os
import time
from typing import Any, Dict, List, Optional

from zookeeper_tpu.core import ComponentField, Field, component, pretty_print
from zookeeper_tpu.data.pipeline import DataLoader
from zookeeper_tpu.models.base import Model
from zookeeper_tpu.observability import trace as _obs_trace
from zookeeper_tpu.observability.device import device_summary
from zookeeper_tpu.observability.registry import MetricsRegistry
from zookeeper_tpu.parallel.distributed import DistributedRuntime
from zookeeper_tpu.parallel.partitioner import Partitioner, SingleDevicePartitioner
from zookeeper_tpu.resilience import faults as _faults
from zookeeper_tpu.resilience.faults import NonFiniteLossError, Preempted
from zookeeper_tpu.resilience.guard import PreemptionGuard
from zookeeper_tpu.training.checkpoint import Checkpointer
from zookeeper_tpu.training.metrics import CompositeMetricsWriter, MetricsWriter
from zookeeper_tpu.training.optimizer import Adam, Optimizer
from zookeeper_tpu.training.state import TrainState
from zookeeper_tpu.training.step import (
    make_eval_step,
    make_train_step,
    smoothed_softmax_cross_entropy,
)


@component
class Experiment:
    """Abstract experiment: subclasses implement run()."""

    def run(self) -> Any:
        raise NotImplementedError("Experiment subclasses must implement run().")


def _data_wait_iter(iterable, name="data_wait"):
    """Wrap a batch/slab iterator so each ``next()`` is a ``data_wait``
    host span: the time the training thread spent BLOCKED on the input
    pipeline (prefetch queue empty = data-bound loop; near-zero spans =
    compute-bound). One flag check + a generator hop per slab when
    tracing is off."""
    it = iter(iterable)
    while True:
        with _obs_trace.span(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def _host_memory() -> Dict[str, int]:
    """Bytes of machine memory in use (``MemTotal - MemAvailable`` of
    ``/proc/meminfo``) and of this process's resident set; an empty
    dict where ``/proc`` is not readable. Called only while tracing is
    on (the ``host_memory`` event)."""
    out: Dict[str, int] = {}
    try:
        with open("/proc/meminfo") as f:
            kb = {
                line.split(":")[0]: int(line.split()[1])
                for line in f
                if line.startswith(("MemTotal:", "MemAvailable:"))
            }
        out["in_use_bytes"] = (kb["MemTotal"] - kb["MemAvailable"]) * 1024
        with open("/proc/self/statm") as f:
            out["rss_bytes"] = int(f.read().split()[1]) * os.sysconf(
                "SC_PAGE_SIZE"
            )
    except (OSError, ValueError, KeyError, IndexError):
        pass
    return out


def run_weighted_eval(loader, split, eval_step, state, sharding, epoch=0):
    """Shared eval loop: accumulate per-batch metric MEANS weighted by
    batch example count, ON DEVICE (one multiply-add per batch, a single
    device_get at the end), so a partial final batch does not skew the
    reported score. Returns {} when the split yields no batches."""
    import jax
    import jax.numpy as jnp

    accum = None
    examples = 0
    for batch in loader.batches(
        split, epoch=epoch, sharding=sharding, training=False
    ):
        n = int(batch["target"].shape[0])
        m = eval_step(state, batch)
        weighted = jax.tree.map(lambda v: v * n, m)
        accum = (
            weighted
            if accum is None
            else jax.tree.map(jnp.add, accum, weighted)
        )
        examples += n
    if not examples:
        return {}
    return {k: float(v) / examples for k, v in jax.device_get(accum).items()}


@component
class TrainingExperiment(Experiment):
    """Supervised-classification training loop.

    ``batch_size`` declared here is inherited by the loader through scoped
    field inheritance (the reference's signature config-reuse mechanism):
    set it once on the experiment.
    """

    loader: DataLoader = ComponentField(DataLoader)
    model: Model = ComponentField()
    optimizer: Optimizer = ComponentField(Adam)
    partitioner: Partitioner = ComponentField(SingleDevicePartitioner)
    checkpointer: Checkpointer = ComponentField(Checkpointer)
    runtime: DistributedRuntime = ComponentField(DistributedRuntime)
    #: Pluggable metrics sink (SURVEY §5): no-op until a leg is configured,
    #: e.g. ``writer.tensorboard.log_dir=/tmp/tb writer.jsonl.path=m.jsonl``.
    writer: MetricsWriter = ComponentField(CompositeMetricsWriter)
    #: Preemption safety (docs/DESIGN.md §10): while training runs,
    #: SIGTERM/SIGINT set a flag checked at step/slab boundaries; the
    #: loop then saves ONE synchronous checkpoint (exact-resume state)
    #: and exits with the distinguished ``Preempted`` status that
    #: ``resilience.run_with_recovery`` resumes from. ``guard.enabled=
    #: False`` restores raw signal behavior.
    guard: PreemptionGuard = ComponentField(PreemptionGuard)

    epochs: int = Field(1)
    batch_size: int = Field(32)
    seed: int = Field(0)
    #: Fused multi-step execution: batches are stacked into device-
    #: resident SLABS of ``unroll`` consecutive batches and the train
    #: step runs ``unroll`` times inside ONE ``lax.scan`` program
    #: (``training.step.build_multi_step``), so per-step Python
    #: dispatch, host bookkeeping, and the forced device->host metrics
    #: sync are paid once per slab instead of once per step. Metrics
    #: stay on device as ``[unroll]``-stacked arrays (deferred
    #: readback: the host reads them only at ``log_every`` boundaries
    #: and at epoch end, one ``device_get`` each). Same steps, same
    #: RNG folding, same example order as the eager loop — bit-exact
    #: for the dense stack, conv backwards within XLA reduction-order
    #: ULPs (see ``build_multi_step``); 1 = today's eager loop. Costs
    #: ``unroll x batch`` of input HBM per slab (x2 while the prefetch
    #: double-buffer holds the next slab) and quantizes step-cadence
    #: checkpoints and ``log_every`` readbacks to slab boundaries.
    unroll: int = Field(1)
    #: Cap on steps per epoch (smoke tests / benchmarking); -1 = full epoch.
    steps_per_epoch: int = Field(-1)
    validate: bool = Field(True)
    #: Epochs between validations (Keras ``validation_freq`` capability):
    #: validation runs on epochs where ``(epoch + 1) % validate_every ==
    #: 0``. On skipped epochs nothing validation-derived happens: no
    #: val_* records/scalars, no best-checkpoint rank-save, no early-stop
    #: patience tick — stale metrics are never re-emitted or re-scored
    #: (early-stop patience therefore counts VALIDATED epochs).
    validate_every: int = Field(1)
    log_every: int = Field(0)  # Steps between progress lines; 0 = epoch only.
    verbose: bool = Field(True)
    #: Legacy epoch-record JSONL (``{"epoch": N, ..., "val_*": ...}``).
    #: Prefer ``writer.jsonl.path`` (step-keyed, shared schema with the
    #: other sinks); this field is kept for config back-compat.
    metrics_file: Optional[str] = Field(None)
    #: Capture a jax.profiler trace of a few steady-state steps when set.
    profile_dir: Optional[str] = Field(None)
    #: Host-side span tracing (docs/DESIGN.md §13): when set, the run
    #: records data_wait/dispatch/readback/checkpoint spans (plus every
    #: background subsystem's spans/events) and writes Chrome
    #: trace-event JSON here at teardown — open it in Perfetto next to
    #: the ``profile_dir`` device trace. None = tracing stays disabled
    #: (zero-cost: one flag check per would-be span).
    trace_export: Optional[str] = Field(None)
    #: Live observability endpoint: port for a stdlib HTTP server
    #: serving ``/metrics`` (Prometheus text), ``/statusz`` (JSON
    #: status) and ``/trace`` while the run is alive. -1 = off
    #: (default); 0 = bind an ephemeral port (logged, and readable via
    #: ``self.obs_server.port``).
    metrics_port: int = Field(-1)
    #: Flight recorder (docs/DESIGN.md §16): when set, a
    #: ``FlightRecorder`` writing to this directory is installed for
    #: the run, so watchdog anomalies, NaN-halts, fault injections and
    #: supervisor recoveries each dump a rate-limited debug bundle
    #: (trace ring + /metrics text + program ledger + statusz +
    #: manifest). None = off. Under ``run_with_recovery`` the recorder
    #: persists across restarts (same experiment object, same Field),
    #: so every recovery writes its bundle.
    flight_recorder_dir: Optional[str] = Field(None)
    #: Minimum seconds between flight-recorder bundles (manual
    #: ``/debugz`` triggers bypass it).
    flight_recorder_interval_s: float = Field(30.0)
    #: Report the per-step sign-flip fraction of binary kernels
    #: (larq FlipRatio capability) in the train metrics.
    track_flip_ratio: bool = Field(False)
    #: Label smoothing for the training loss (standard ImageNet recipe
    #: regularizer; 0 = off). Validation uses the SAME smoothed loss
    #: (Keras semantics: the compiled loss scores both splits) — accuracy
    #: metrics are unaffected.
    label_smoothing: float = Field(0.0)
    #: Also report top-5 accuracy in validation metrics (the ImageNet
    #: companion metric; requires >= 5 classes).
    track_top5: bool = Field(False)
    #: Save a model-only checkpoint (params + batch stats, no optimizer
    #: state) here after training: the deployment/teacher export format
    #: (see training.checkpoint.save_model / DistillationExperiment).
    #: Exports the EMA weights when ema_decay is on (they are the ship
    #: artifact).
    export_model_to: Optional[str] = Field(None)
    #: Exponential-moving-average of params (0 = off). When on, the train
    #: step maintains the average, validation evaluates it, and
    #: export_model_to ships it. Standard for long binary-net recipes:
    #: late sign flips make raw weights oscillate; the average does not.
    #: Downstream consumers pick EMA vs raw with the shared weights
    #: Field (``ServingConfig.weights`` / ``EvalExperiment.weights`` —
    #: ``training.checkpoint.select_inference_weights``): "auto" serves
    #: the EMA shadow whenever this knob produced one.
    ema_decay: float = Field(0.0)
    #: Non-finite-loss policy (``training.step.make_train_step``):
    #: "ignore" (default, zero-cost), "skip" (a non-finite step keeps
    #: the pre-step params/opt/EMA state on device — no host sync —
    #: and the epoch metrics report a summed ``skipped_steps`` count),
    #: or "halt" (skip on device, then raise ``NonFiniteLossError`` at
    #: the next metrics readback boundary so a supervisor restores
    #: from checkpoint).
    nan_policy: str = Field("ignore")
    #: Group-mode drain margin in STEPS (docs/DESIGN.md §19): the gap
    #: between a preemption flag's publish boundary and the agreed
    #: whole-group exit. Must exceed the worst cross-host boundary
    #: skew PLUS the shared storage's flag-visibility lag; 0 = auto
    #: (4 x unroll — right for strongly-consistent storage like local
    #: disk/GCS). Raise it on storage with cached directory listings
    #: (NFS attribute caching) where a flag may take longer to become
    #: visible to peers.
    group_drain_margin_steps: int = Field(0)
    #: Rematerialization policy ("none"/"dots"/"full"/"quant"): trade
    #: backward recompute for activation HBM (see make_train_step —
    #: "quant" saves only the tagged binarized activations; measured
    #: guidance in BASELINE.md says remat="none" for the conv zoo).
    remat: str = Field("none")
    #: Keras ``EarlyStopping`` capability: stop when this metric (scored
    #: on validation metrics when a split exists, else train epoch
    #: metrics — the keep_best_metric convention) fails to improve by
    #: ``early_stop_min_delta`` for ``early_stop_patience`` consecutive
    #: epochs. None disables.
    early_stop_metric: Optional[str] = Field(None)
    early_stop_patience: int = Field(3)
    early_stop_min_delta: float = Field(0.0)
    #: "auto" infers direction from the name ("loss" -> min, else max);
    #: or explicit "min"/"max".
    early_stop_mode: str = Field("auto")
    #: Print the quantization-aware parameter summary (per-layer bits,
    #: deployment memory — models.summary) before training.
    print_model_summary: bool = Field(False)

    @Field
    def num_classes(self) -> int:
        # Works for every dataset type: prefers a declared num_classes
        # field, else the dataset infers (TFDS metadata / label scan).
        return int(self.loader.dataset.resolved_num_classes())

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(msg, flush=True)

    def _log_profile_breakdown(self, steps: int) -> None:
        """Best-effort per-op attribution of the captured trace (the
        BASELINE.md bottleneck-naming analysis, in the loop). Quiet on
        failure: CPU traces carry no device planes, and the xplane proto
        lives in the optional tensorflow dependency."""
        if not self.verbose:
            return
        try:
            from zookeeper_tpu.training.profiling import (
                format_breakdown,
                op_time_breakdown,
            )

            self._log(
                format_breakdown(
                    op_time_breakdown(
                        self.profile_dir, steps=max(1, steps)
                    )
                )
            )
        except Exception as e:  # pragma: no cover - env-dependent
            import logging

            logging.getLogger(__name__).debug(
                "trace breakdown unavailable: %s", e
            )

    # -- observability (docs/DESIGN.md §13) ------------------------------

    @property
    def obs_registry(self) -> MetricsRegistry:
        """This experiment's typed instrument registry (derived rates
        published per epoch); rendered at ``/metrics`` when
        ``metrics_port`` is set."""
        reg = getattr(self, "_obs_registry", None)
        if reg is None:
            reg = MetricsRegistry()
            self._obs_registry = reg
        return reg

    def _publish_epoch_observability(
        self, epoch, steps_trained, epoch_metrics, vmetrics
    ) -> None:
        """Mirror the epoch's derived rates into typed instruments so a
        live scrape sees them without waiting for the writer sinks.
        Rides the epoch boundary — zero cost on the step path. Never
        raises: a pathological metric NAME (one colliding with a
        differently-typed instrument) loses its mirror with a log line,
        not the training run — observability is strictly an observer
        here."""
        import logging

        reg = self.obs_registry
        try:
            # _total suffix keeps the counter clear of the zk_train_<k>
            # gauge namespace (an epoch metric literally named
            # "steps_total" would still collide; the except covers it).
            reg.counter(
                "zk_train_steps_total",
                help="train steps completed this run",
            ).inc(steps_trained)
            reg.gauge("zk_train_epoch", help="last completed epoch").set(
                epoch + 1
            )
            for k, v in epoch_metrics.items():
                reg.gauge(f"zk_train_{k}").set(v)
            for k, v in (vmetrics or {}).items():
                reg.gauge(f"zk_val_{k}").set(v)
        except Exception as e:
            logging.getLogger(__name__).warning(
                "epoch observability mirror skipped: %s", e
            )

    def _obs_status(self) -> Dict[str, Any]:
        """The ``/statusz`` section for this run."""
        return {
            "model": type(self.model).__name__,
            "epochs": int(self.epochs),
            "batch_size": int(self.batch_size),
            "unroll": int(self.unroll),
        }

    def _setup_observability(self) -> None:
        if self.trace_export:
            # Remember whether WE turned tracing on: an externally-
            # enabled tracer (nested runs, tests) must survive teardown.
            self._trace_enabled_here = not _obs_trace.enabled()
            _obs_trace.enable()
        if self.metrics_port >= 0:
            from zookeeper_tpu.observability import (
                DeviceProbe,
                ObservabilityServer,
            )
            from zookeeper_tpu.observability.registry import default_registry

            server = ObservabilityServer(
                [default_registry(), self.obs_registry],
                port=self.metrics_port,
                status_providers={"training": self._obs_status},
            )
            server.start()
            self.obs_server = server
            # Live HBM gauges ride the endpoint's lifetime: an eager
            # first poll so zk_hbm_* exists from the first scrape, then
            # the zk-device-probe daemon keeps it fresh. Allocator
            # counters only — the probe never dispatches device work.
            probe = DeviceProbe()
            probe.poll_once()
            probe.start()
            self.obs_probe = probe
            self._log(f"observability endpoint: {server.url}/metrics")
        if self.flight_recorder_dir:
            from zookeeper_tpu.observability import recorder as _obs_recorder
            from zookeeper_tpu.observability.registry import default_registry

            rec = getattr(self, "flight_recorder", None)
            if rec is None or rec.directory != self.flight_recorder_dir:
                rec = _obs_recorder.arm(
                    self.flight_recorder_dir,
                    registries=[default_registry(), self.obs_registry],
                    status_providers={"training": self._obs_status},
                    min_interval_s=self.flight_recorder_interval_s,
                )
                self.flight_recorder = rec
            # Installed for the PROCESS, not the run: run() teardown
            # deliberately leaves it in place, because the supervisor's
            # bundle-per-recovery trigger fires AFTER run() has exited
            # with the recoverable status (docs/DESIGN.md §16). The
            # same experiment object re-runs under run_with_recovery
            # and reuses this recorder (re-install covers a replacement
            # installed by an interleaved service in the meantime).
            _obs_recorder.install(rec)

    def _finish_host_trace(self) -> None:
        """Teardown: write the Chrome trace-event JSON and restore the
        pre-run tracing state."""
        if self.trace_export and _obs_trace.enabled():
            n = _obs_trace.export_chrome_trace(self.trace_export)
            self._log(
                f"host trace: {n} events -> {self.trace_export} "
                "(open in Perfetto)"
            )
            if self.profile_dir is not None:
                # The docs §13 merge recipe, automated: this teardown
                # already closed any open device capture window
                # (_abort_jax_trace runs first), so both halves of the
                # timeline are final and PAIRED here — no hand-merging,
                # one log line says exactly what to open side by side.
                self._log(
                    "paired trace artifacts: host spans "
                    f"{self.trace_export} (Chrome JSON) + device xplane "
                    f"{self.profile_dir} — load both in Perfetto and "
                    "align on wall time (docs/DESIGN.md §13)"
                )
            if getattr(self, "_trace_enabled_here", False):
                _obs_trace.disable()

    def _stop_obs_server(self) -> None:
        server = getattr(self, "obs_server", None)
        if server is not None:
            self.obs_server = None
            server.stop()
        probe = getattr(self, "obs_probe", None)
        if probe is not None:
            self.obs_probe = None
            probe.stop()

    # -- step-time watchdog + live MFU (docs/DESIGN.md §14) --------------

    def _watchdog(self, stream: str):
        """Per-stream anomaly watchdog, lazily created, counters in
        this experiment's registry."""
        dogs = getattr(self, "_watchdogs", None)
        if dogs is None:
            dogs = {}
            self._watchdogs = dogs
        dog = dogs.get(stream)
        if dog is None:
            from zookeeper_tpu.observability.watchdog import StepTimeWatchdog

            # 5ms excess floor: a flagged straggler must be worth a
            # human's attention on any backend — sub-ms host jitter on
            # fast CPU steps never is (docs/DESIGN.md §14 policy).
            dog = StepTimeWatchdog(
                stream, min_excess_s=0.005, registry=self.obs_registry
            )
            dogs[stream] = dog
        return dog

    def _obs_reset_timers(self) -> None:
        """Start-of-run timer state (one dict, not Fields: pure
        runtime)."""
        self._obs_timer = {
            "iter_t": None,
            "iter_dirty": False,
            "sync_t": None,
            "sync_step": None,
            "sync_dirty": False,
        }

    def _obs_mark_stall(self, sync: bool = True) -> None:
        """Mark the current timing intervals polluted by a known
        non-step phase (checkpoint save, profiler window open/close,
        epoch boundary with validation): the watchdogs must not read a
        deliberate stall as a straggler — the false-positive policy of
        docs/DESIGN.md §14. ``sync=False`` marks only the
        inter-dispatch stream (a metrics readback inflates the
        iteration it rides in, but IS the sync stream's clean
        boundary)."""
        timer = getattr(self, "_obs_timer", None)
        if timer is not None:
            timer["iter_dirty"] = True
            if sync:
                timer["sync_dirty"] = True

    def _obs_iteration_end(self, k: int, global_step: int) -> None:
        """End of one train-loop iteration (k steps dispatched): feed
        the host-side inter-dispatch duration stream. This wall time is
        data wait + dispatch Python — an INPUT/HOST straggler signal
        (the device runs behind asynchronously; honest device-throttled
        timing comes from the sync points below)."""
        timer = self._obs_timer
        t = time.perf_counter()
        prev = timer["iter_t"]
        timer["iter_t"] = t
        if timer["iter_dirty"]:
            timer["iter_dirty"] = False
            return
        if prev is not None:
            self._watchdog("train_dispatch").observe(
                (t - prev) / max(1, k), step=global_step
            )

    def _obs_sync_point(self, global_step: int) -> None:
        """A metrics readback just completed — a true completion
        barrier for every step up to ``global_step``. The interval
        since the previous barrier is honest device-throttled time:
        feed the step-time watchdog and publish the live
        ``zk_train_step_time_ms`` gauge. While tracing is on, each
        sync point also records one ``host_memory`` event (the machine's
        memory in use and this process's RSS): what the loop leaves
        behind per step can lie outside the process, where nothing
        else the program prints sees it."""
        if _obs_trace.enabled():
            _obs_trace.event(
                "host_memory", step=global_step, attrs=_host_memory()
            )
        timer = getattr(self, "_obs_timer", None)
        if timer is None:
            return
        t = time.perf_counter()
        prev_t, prev_step = timer["sync_t"], timer["sync_step"]
        timer["sync_t"], timer["sync_step"] = t, global_step
        if timer["sync_dirty"]:
            timer["sync_dirty"] = False
            return
        if prev_t is None or prev_step is None or global_step <= prev_step:
            return
        per_step = (t - prev_t) / (global_step - prev_step)
        self._watchdog("train_step").observe(per_step, step=global_step)
        self.obs_registry.gauge(
            "zk_train_step_time_ms",
            help="measured steady-state seconds/step (readback-bounded)",
        ).set(per_step * 1e3)

    # -- jax profiler window (device trace) ------------------------------

    def _start_jax_trace(self) -> None:
        import jax

        jax.profiler.start_trace(self.profile_dir)
        self._jax_trace_active = True

    def _stop_jax_trace(self) -> None:
        import jax

        # Clear the flag BEFORE stopping: a stop that raises must not
        # be retried by the teardown abort (stop_trace on a stopped
        # profiler raises).
        self._jax_trace_active = False
        jax.profiler.stop_trace()

    def _abort_jax_trace(self) -> None:
        """Teardown half of the profiling-window contract: an exception
        raised mid-capture (preemption, NaN halt, a crash) must not
        leave ``jax.profiler.start_trace`` open — a dangling capture
        poisons the next run's ``start_trace`` and holds the trace
        buffers. No-op when no window is open."""
        if getattr(self, "_jax_trace_active", False):
            self._stop_jax_trace()

    def build_state(self) -> TrainState:
        """Build module + optimizer and initialize the TrainState."""
        input_shape = self.loader.preprocessing.input_shape
        # Mesh-owning partitioners wire themselves into the model here
        # (e.g. SequenceParallelPartitioner injecting its attention
        # callable) — the config-first seam; a no-op for the rest.
        self.partitioner.prepare_model(self.model)
        module = self.model.build(input_shape, self.num_classes)
        params, model_state = self.model.initialize(
            module, input_shape, seed=self.seed
        )
        spe = self._steps_per_epoch()
        tx = self.optimizer.build(total_steps=max(1, spe * self.epochs))
        return TrainState.create(
            apply_fn=module.apply,
            params=params,
            model_state=model_state,
            tx=tx,
            ema=self.ema_decay > 0,
        )

    def _steps_per_epoch(self) -> int:
        spe = self.loader.steps_per_epoch("train")
        if self.steps_per_epoch > 0:
            spe = min(spe, self.steps_per_epoch)
        return spe

    def _train_step_kwargs(self) -> Dict[str, Any]:
        """The make_train_step wiring, exposed so subclasses extend it
        (add kwargs) without re-deriving the base options."""
        from zookeeper_tpu.training.optimizer import BINARY_KERNEL_PATTERN

        return {
            "loss_fn": smoothed_softmax_cross_entropy(self.label_smoothing),
            "rng_seed": self.seed,
            "flip_ratio_pattern": (
                BINARY_KERNEL_PATTERN if self.track_flip_ratio else None
            ),
            "ema_decay": self.ema_decay if self.ema_decay > 0 else None,
            "remat": self.remat,
            "nan_policy": self.nan_policy,
        }

    def _train_step_fn(self):
        """The pure step the loop compiles — the subclass hook (e.g.
        DistillationExperiment adds a teacher term)."""
        return make_train_step(**self._train_step_kwargs())

    def _step_save_due(self, epoch: int, step_idx: int, spe: int) -> bool:
        """Whether the step-cadence checkpoint fires after this step.

        An epoch-boundary step defers to the save_every_epochs path
        ONLY when that path will actually fire this epoch (a double
        save of one step would collide in orbax); otherwise the step
        cadence must still hold — that's the "loss bounded to N steps"
        promise (0 = cadence disabled, both knobs).
        """
        ck = self.checkpointer
        if not (ck.enabled and ck.save_every_steps > 0):
            return False
        if (epoch * spe + step_idx + 1) % ck.save_every_steps != 0:
            return False
        epoch_save_fires = (
            ck.save_every_epochs > 0
            and (epoch + 1) % ck.save_every_epochs == 0
        )
        return step_idx + 1 < spe or not epoch_save_fires

    def _log_step_scalars(self, epoch, step_idx, spe, row):
        """Per-step progress line + ``train/`` writer scalars — ONE
        formatting path shared by the eager and fused loops so the two
        modes can never log divergent output."""
        self._log(
            f"  step {step_idx + 1}/{spe} "
            f"loss={row['loss']:.4f} acc={row['accuracy']:.4f}"
        )
        self.writer.write_scalars(
            epoch * spe + step_idx + 1,
            {f"train/{k}": v for k, v in row.items()},
        )

    def _mark_first_step(self, metrics, global_step: int = 0) -> None:
        """Timestamp the completion of THIS RUN's first train step (one
        deliberate device sync, once per run): the supervisor reads it
        to report restore latency (restart -> first post-resume step).
        The same barrier seeds the step-time stream's baseline — the
        first honest post-compile sync, so a ``log_every=0`` run can
        still publish ``zk_train_step_time_ms`` from its epoch-end
        readback."""
        if getattr(self, "first_step_at", None) is None:
            import jax

            jax.block_until_ready(metrics["loss"])
            self.first_step_at = time.perf_counter()
            timer = getattr(self, "_obs_timer", None)
            if timer is not None:
                timer["sync_t"] = self.first_step_at
                timer["sync_step"] = int(global_step)
                timer["sync_dirty"] = False

    def _group_process_index(self) -> int:
        """This host's index for logical fault keying: the group
        coordinator's when one is wired, else the live jax runtime's."""
        coord = getattr(self, "group_coordinator", None)
        if coord is not None:
            return int(coord.process_index)
        import jax

        return int(jax.process_index())

    def _group_drain_margin(self) -> int:
        """Steps between a drain flag's publish boundary and the
        agreed group exit. Must exceed the worst cross-host boundary
        skew (one slab, enforced by the group boundary's device sync)
        plus the storage's flag-visibility lag, so NO host can already
        be past the exit when the flag becomes visible — the
        no-deadlock argument of docs/DESIGN.md §19. Configurable via
        ``group_drain_margin_steps`` for slow-visibility storage."""
        if self.group_drain_margin_steps > 0:
            return int(self.group_drain_margin_steps)
        return 4 * max(1, int(self.unroll))

    def _group_stop_due(self, global_step: int) -> bool:
        """Group-mode boundary protocol (docs/DESIGN.md §19): a host
        whose guard tripped PUBLISHES a stop flag (only if no drain is
        already in progress) instead of exiting; every host sees the
        flag at a later boundary — publish-before-dispatch ordering
        plus the per-boundary device sync guarantee any host past the
        flag's step sees it — and the whole group exits at the first
        boundary at or past ``flag.step + margin``. One common grid,
        one deterministic stop step: all hosts save the SAME state and
        the per-host commit record can land. Non-blocking by design: a
        host never waits here (a peer mid-collective could be waiting
        on OUR next dispatch); it keeps training to the agreed
        boundary. Returns True when THIS boundary is the group exit."""
        coord = self.group_coordinator
        pid = int(coord.process_index)
        flags = coord.poll_flags("preempt")
        if (
            self.guard.preempted
            and not flags
            and getattr(self, "_group_flag_step", None) is None
        ):
            # This host originates the drain (SIGTERM / injected kill
            # here, and no drain already in progress).
            coord.publish_flag(
                "preempt",
                {
                    "origin": pid,
                    "step": int(global_step),
                    "signal": self.guard.received_signal,
                },
            )
            self._group_flag_step = int(global_step)
            self.guard.request_preemption(
                signum=self.guard.received_signal, origin=pid
            )
            flags = coord.poll_flags("preempt")
        if not flags:
            return False
        if self.guard.preemption_origin is None:
            # Join the drain (and record who started it for the
            # supervisor's flight-recorder manifest).
            first = min(flags, key=lambda f: int(f["origin"]))
            self.guard.request_preemption(
                signum=self.guard.received_signal or first.get("signal"),
                origin=int(first["origin"]),
            )
        stop_step = (
            max(int(f["step"]) for f in flags) + self._group_drain_margin()
        )
        return int(global_step) >= stop_step

    def _boundary_check(self, state, global_step: int) -> None:
        """Preemption check at a safe boundary (a step/slab end, where
        ``state`` is a valid exact-resume point). An active FaultPlan's
        ``kill_at_step`` / ``kill_process_at_step`` trips the same flag
        a real SIGTERM does, so the injected and production paths are
        one path. On preemption: one SYNCHRONOUS save of exactly this
        state, then the distinguished ``Preempted`` exit (teardown
        still runs via run()'s finally). With a group coordinator
        wired (``run_with_recovery(coordinator=...)``), the flag is
        first EXCHANGED across hosts so the whole process group drains
        and saves the same boundary together."""
        plan = _faults.active()
        if plan is not None and plan.kill_due(
            global_step,
            self._group_process_index()
            if (
                plan.kill_process_at_step is not None
                or getattr(self, "group_coordinator", None) is not None
            )
            else 0,
        ):
            self.guard.request_preemption()
        coord = getattr(self, "group_coordinator", None)
        if coord is not None and coord.process_count > 1:
            import jax

            # Bound cross-host boundary skew to ONE slab (the drain-
            # margin no-deadlock argument, docs/DESIGN.md §19): this
            # host passes the boundary only once every peer has
            # dispatched the slab that produced this state.
            jax.block_until_ready(state.step)
            if not self._group_stop_due(global_step):
                return
        elif not self.guard.preempted:
            return
        # The guard owns the drain-then-sync-save policy (async mode
        # first lands or supersedes the in-flight background write);
        # the time spent waiting on that write is surfaced per attempt
        # by run_with_recovery as save_wait_ms.
        saved, self.save_wait_ms = self.guard.preemption_save(
            self.checkpointer, state, global_step
        )
        self._log(
            f"preemption requested "
            f"(signal {self.guard.received_signal or 'injected/manual'}); "
            f"exiting at step {global_step} "
            f"({'checkpoint saved' if saved else 'NO checkpoint'})"
        )
        raise Preempted(global_step, saved, self.guard.received_signal)

    def _check_halt(self, host_metrics, global_step: int) -> None:
        """``nan_policy="halt"``: raise at a readback boundary when any
        step in the freshly-pulled host metrics was skipped for a
        non-finite loss/grad. ``host_metrics`` is one step's scalar
        dict, one slab's [k]-stacked dict, or a list of either."""
        if self.nan_policy != "halt":
            return
        import numpy as np

        rows = host_metrics if isinstance(host_metrics, list) else [host_metrics]
        skipped = sum(
            float(np.sum(np.asarray(m["skipped_steps"])))
            for m in rows
            if "skipped_steps" in m
        )
        if skipped > 0:
            # Flight-recorder trigger (docs/DESIGN.md §16): the trace
            # ring around the NaN step is the forensic record — bundle
            # it before the supervisor's restore discards the run.
            from zookeeper_tpu.observability import recorder as _obs_recorder

            _obs_recorder.notify(
                "nan_halt",
                step=global_step,
                attrs={"skipped_steps": int(skipped)},
            )
            raise NonFiniteLossError(global_step, int(skipped))

    def _run_fused_epoch(
        self, multi_step, state, accum, epoch, spe, start_b,
        profiling, p_start, p_stop,
    ):
        """One epoch of the fused multi-step engine (``unroll > 1``).

        Drives device-resident slabs of ``unroll`` stacked batches
        through the compiled ``lax.scan`` multi-step with DEFERRED
        metrics readback: each dispatch appends the slab's
        ``[k]``-stacked per-step metrics to ``accum`` still on device,
        and the host only reads back (one ``device_get`` per occasion)
        at ``log_every`` step boundaries — so with logging off, the
        loop dispatches slab N+1 without ever blocking on slab N's
        results, and host time disappears under device time.

        Semantics match the eager loop step-for-step: the slab
        iterator preserves example order and ``start_batch`` resume
        (a resume point mid-slab just becomes the first slab's first
        step), the step counter advances inside the scan, and
        ``log_every`` scalars carry the SAME per-step values the eager
        path logs. Two quantizations are inherent: step-cadence
        checkpoints fire at the end of the slab containing the due
        step (the saved state is a valid, exactly-resumable state a
        few steps later), and the profiler trace window widens to
        whole slabs. Returns ``(state, steps_trained)``.
        """
        import jax

        from zookeeper_tpu.training.profiling import slab_annotation

        step_idx = start_b
        tracing = False
        trace_first = start_b
        for slab_idx, slab in enumerate(
            _data_wait_iter(
                self.loader.batches(
                    "train",
                    epoch=epoch,
                    sharding=self.partitioner.slab_sharding(),
                    start_batch=start_b,
                    unroll=self.unroll,
                    max_batches=spe - start_b,
                )
            )
        ):
            k = int(next(iter(slab.values())).shape[0])
            # Trace from the first SLAB BOUNDARY at/after p_start so
            # the scan compile + warmup slabs stay OUT of the window
            # (the eager path's warmup-exclusion contract); a
            # single-slab epoch has no later boundary, so its one
            # dispatch is traced, compile included — the only capture
            # possible there.
            if profiling and not tracing and (
                step_idx >= p_start or step_idx + k >= spe
            ):
                self._start_jax_trace()
                self._obs_mark_stall()
                tracing, trace_first = True, step_idx
            with slab_annotation(slab_idx, num_steps=k), _obs_trace.span(
                "dispatch", step=epoch * spe + step_idx, slab=slab_idx
            ):
                state, metrics = multi_step(state, slab)
            entry = getattr(multi_step, "ledger_entry", None)
            if entry is not None and "steps" not in entry.attrs:
                # The first dispatch is the one that compiled the
                # recorded program, so THIS slab's size is the FLOPs
                # divisor — the configured unroll is wrong when the
                # first slab is partial (mid-epoch resume, spe<unroll).
                entry.attrs["steps"] = k
            accum.append(metrics)
            self._mark_first_step(metrics, epoch * spe + step_idx + k)
            if tracing and step_idx + k > p_stop:
                jax.block_until_ready(metrics["loss"])
                self._stop_jax_trace()
                self._obs_mark_stall()
                profiling = tracing = False
                self._log_profile_breakdown(step_idx + k - trace_first)
            if any(
                self._step_save_due(epoch, s, spe)
                for s in range(step_idx, step_idx + k)
            ):
                with _obs_trace.span(
                    "checkpoint", step=epoch * spe + step_idx + k,
                    slab=slab_idx,
                ):
                    self.checkpointer.save(state)
                self._obs_mark_stall()
            if self.log_every:
                bounds = [
                    s
                    for s in range(step_idx, step_idx + k)
                    if (s + 1) % self.log_every == 0
                ]
                if bounds:
                    # ONE readback for the whole slab; per-step values
                    # are identical to what the eager loop would log.
                    with _obs_trace.span(
                        "readback", step=epoch * spe + step_idx + k,
                        slab=slab_idx,
                    ):
                        hm = jax.device_get(metrics)
                    # The readback is the step-time stream's honest
                    # completion barrier (and it pollutes the current
                    # inter-dispatch interval, which is why the
                    # dispatch stream skips this iteration).
                    self._obs_mark_stall(sync=False)
                    self._obs_sync_point(epoch * spe + step_idx + k)
                    self._check_halt(hm, epoch * spe + step_idx + k)
                    for s in bounds:
                        self._log_step_scalars(
                            epoch, s, spe,
                            {
                                kk: float(v[s - step_idx])
                                for kk, v in hm.items()
                            },
                        )
            step_idx += k
            # Slab ends are the fused loop's safe boundaries: the state
            # here is a valid exact-resume point (same quantization as
            # step-cadence checkpoints).
            self._boundary_check(state, epoch * spe + step_idx)
            self._obs_iteration_end(k, epoch * spe + step_idx)
        return state, step_idx - start_b

    def run(self) -> Dict[str, List[Dict[str, float]]]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        if not 0.0 <= self.ema_decay < 1.0:
            raise ValueError(
                f"ema_decay={self.ema_decay} is outside [0, 1): 0 disables "
                "EMA; 1.0 would freeze the average at initialization "
                "forever (common typo for 0.999)."
            )
        if self.remat not in ("none", "dots", "full", "quant"):
            # Pure config: fail before device setup / checkpoint restore.
            raise ValueError(
                f"remat={self.remat!r} unknown; choose none/dots/full/quant."
            )
        if self.unroll < 1:
            raise ValueError(
                f"unroll={self.unroll} must be >= 1 (1 = eager per-step "
                "loop; N fuses N steps per dispatch)."
            )
        if self.nan_policy not in ("ignore", "skip", "halt"):
            # Pure config: fail before device setup / compilation.
            raise ValueError(
                f"nan_policy={self.nan_policy!r} unknown; "
                "choose ignore/skip/halt."
            )
        if self.early_stop_mode not in ("auto", "min", "max"):
            raise ValueError(
                f"early_stop_mode={self.early_stop_mode!r} unknown; "
                "choose auto/min/max."
            )
        if (
            self.checkpointer.save_every_epochs < 0
            or self.checkpointer.save_every_steps < 0
        ):
            raise ValueError(
                "checkpointer.save_every_epochs/save_every_steps must be "
                ">= 0 (0 disables that cadence)."
            )
        # Pure config (mode/queue_policy/durable tier): fail before
        # device setup / checkpoint restore.
        self.checkpointer._validate_mode()
        if (
            self.checkpointer.save_every_steps > 0
            and self.checkpointer.keep_best_metric is not None
        ):
            # Pure config: fail before device setup / compilation.
            raise ValueError(
                "checkpointer.save_every_steps is incompatible with "
                "keep_best_metric: mid-epoch saves carry no fresh "
                "rankable metrics (best-ranking pins every save to a "
                "metric). Use one or the other."
            )
        if self.group_drain_margin_steps < 0:
            raise ValueError(
                f"group_drain_margin_steps={self.group_drain_margin_steps}"
                " must be >= 0 (0 = auto: 4 x unroll)."
            )
        if self.validate_every < 1:
            # Fail fast rather than guess: 0 commonly means "disable" in
            # every-N conventions, but validate=False is the explicit
            # switch for that here.
            raise ValueError(
                f"validate_every={self.validate_every} must be >= 1; "
                "set validate=False to disable validation."
            )
        if not 0.0 <= self.label_smoothing < 1.0:
            raise ValueError(
                f"label_smoothing={self.label_smoothing} outside [0, 1)."
            )
        if self.track_top5 and self.num_classes < 5:
            raise ValueError(
                f"track_top5=True needs >= 5 classes "
                f"(dataset has {self.num_classes})."
            )
        self._log(pretty_print(self))
        if self.print_model_summary:
            from zookeeper_tpu.models.summary import model_summary

            input_shape = self.loader.preprocessing.input_shape
            self._log(
                str(
                    model_summary(
                        self.model.build(input_shape, self.num_classes),
                        input_shape,
                        # The pipeline knows the real input dtype (token
                        # ids vs pixels); None falls back to summary's
                        # documented rank heuristic.
                        input_dtype=self.loader.preprocessing.input_dtype,
                    )
                )
            )
        try:
            # Opt-in observability (trace ring + /metrics endpoint) comes
            # up BEFORE device setup so compile/restore phases are
            # scrapeable — inside the protected region so a half-failed
            # setup (tracer enabled, then the HTTP bind raises
            # EADDRINUSE) is still torn down by the finally below.
            self._setup_observability()
            self.runtime.initialize()  # Multi-host bootstrap; no-op single host.
            if self.checkpointer.enabled and self.checkpointer.sharded_per_host:
                # Construct (and stale-purge) the restore-agreement
                # coordinator NOW, behind the cluster-formation
                # rendezvous — not lazily at first restore, where a
                # slow peer's stale files could still be visible
                # (coordination.FileCoordinator docstring).
                self.checkpointer._coordinator()
            partitioner = self.partitioner
            partitioner.setup()
            self._log(f"devices: {json.dumps(device_summary())}")
            state = partitioner.shard_state(self.build_state())
            state = self.checkpointer.restore_state(state)
            if self.unroll > 1:
                from zookeeper_tpu.training.step import build_multi_step

                multi_step = partitioner.compile_multi_step(
                    build_multi_step(self._train_step_fn()), state
                )
                train_step = None
            else:
                multi_step = None
                train_step = partitioner.compile_step(
                    self._train_step_fn(), state
                )
            eval_step = partitioner.compile_eval(
                make_eval_step(
                    smoothed_softmax_cross_entropy(self.label_smoothing),
                    use_ema=self.ema_decay > 0,
                    top5=self.track_top5,
                ),
                state,
            )
            batch_sharding = partitioner.batch_sharding()

            spe = self._steps_per_epoch()
            start_step = int(jax.device_get(state.step))
            start_epoch = start_step // max(1, spe)
            # Steps already trained within the resumed epoch (nonzero only
            # for step-granular checkpoints): the epoch's permutation is
            # (seed, epoch)-fixed, so skipping the first k batches resumes
            # EXACTLY where the crashed run left off.
            resume_step = start_step % max(1, spe)
            if start_step > 0:
                self._log(
                    f"resumed from checkpoint at step {start_step} "
                    f"(epoch {start_epoch}"
                    + (f", step {resume_step} within it" if resume_step else "")
                    + ")"
                )
            history: Dict[str, List[Dict[str, float]]] = {"train": [], "validation": []}
            # One presence probe, not one per epoch: dataset.validation()
            # may construct a real source (e.g. a TFDS reader).
            has_val_split = self.validate and (
                self.loader.dataset.validation() is not None
            )
            es_best: Optional[float] = None
            es_stale = 0
            es_minimize = self.early_stop_mode == "min" or (
                self.early_stop_mode == "auto"
                and self.early_stop_metric is not None
                and "loss" in self.early_stop_metric
            )
            # Per-run restore-latency probe (read by run_with_recovery).
            self.first_step_at = None
            # Per-run group-drain flag marker (the group boundary
            # protocol publishes at most one stop flag per run).
            self._group_flag_step = None
            # Step-time watchdog + live-MFU timer state (docs §14).
            self._obs_reset_timers()
            # Per-run preemption-save wait probe (ms spent draining the
            # in-flight async checkpoint write before the final sync save;
            # 0.0 in sync mode — also read by run_with_recovery).
            self.save_wait_ms = None
            # From here until teardown, SIGTERM/SIGINT mean "save and exit
            # at the next step/slab boundary", not "die mid-write".
            self.guard.install()
            for epoch in range(start_epoch, self.epochs):
                t0 = time.perf_counter()
                accum: List[Any] = []
                # Mid-epoch resume: skip the already-trained prefix of
                # the FIRST epoch only; step_idx stays epoch-absolute so
                # logging/writer steps and the spe cutoff are unchanged.
                start_b = resume_step if epoch == start_epoch else 0
                profiling = self.profile_dir is not None and epoch == start_epoch
                # Trace window, anchored at the first step this run
                # actually executes (warmup steps excluded).
                p_start = min(start_b + 4, spe - 1)
                p_stop = min(start_b + 14, spe - 1)
                if multi_step is not None:
                    state, steps_trained = self._run_fused_epoch(
                        multi_step, state, accum, epoch, spe, start_b,
                        profiling, p_start, p_stop,
                    )
                else:
                    for step_idx, batch in enumerate(
                        _data_wait_iter(
                            self.loader.batches(
                                "train",
                                epoch=epoch,
                                sharding=batch_sharding,
                                start_batch=start_b,
                            )
                        ),
                        start=start_b,
                    ):
                        if step_idx >= spe:
                            break
                        if profiling and step_idx == p_start:
                            self._start_jax_trace()
                            self._obs_mark_stall()
                        with _obs_trace.span(
                            "dispatch", step=epoch * spe + step_idx
                        ):
                            state, metrics = train_step(state, batch)
                        accum.append(metrics)
                        self._mark_first_step(
                            metrics, epoch * spe + step_idx + 1
                        )
                        if profiling and step_idx == p_stop:
                            jax.block_until_ready(metrics["loss"])
                            self._stop_jax_trace()
                            self._obs_mark_stall()
                            profiling = False
                            # Steps p_start..p_stop run INSIDE the trace
                            # window, inclusive on both ends.
                            self._log_profile_breakdown(p_stop - p_start + 1)
                        if self._step_save_due(epoch, step_idx, spe):
                            with _obs_trace.span(
                                "checkpoint",
                                step=epoch * spe + step_idx + 1,
                            ):
                                self.checkpointer.save(state)
                            self._obs_mark_stall()
                        if self.log_every and (step_idx + 1) % self.log_every == 0:
                            # Per-step scalars ride the host pull that log_every
                            # already paid for — finer than epoch granularity at
                            # zero extra device syncs.
                            with _obs_trace.span(
                                "readback", step=epoch * spe + step_idx + 1
                            ):
                                hm = jax.device_get(metrics)
                            self._obs_mark_stall(sync=False)
                            self._obs_sync_point(
                                epoch * spe + step_idx + 1
                            )
                            self._check_halt(hm, epoch * spe + step_idx + 1)
                            self._log_step_scalars(
                                epoch, step_idx, spe,
                                {k: float(v) for k, v in hm.items()},
                            )
                        self._boundary_check(
                            state, epoch * spe + step_idx + 1
                        )
                        self._obs_iteration_end(
                            1, epoch * spe + step_idx + 1
                        )
                    steps_trained = len(accum)
                # One host sync per epoch: pull all accumulated device scalars
                # in a single device_get (each separate transfer pays a full
                # host<->device round trip).
                # Fused slabs land as [k]-stacked per-step arrays; eager
                # steps as scalars — atleast_1d + concatenate makes the
                # epoch mean a plain per-step mean in both modes.
                with _obs_trace.span(
                    "readback", step=epoch * spe + start_b + steps_trained
                ):
                    host_accum = jax.device_get(accum)
                self._obs_mark_stall(sync=False)
                self._obs_sync_point(
                    epoch * spe + start_b + steps_trained
                )
                self._check_halt(
                    host_accum, epoch * spe + start_b + steps_trained
                )
                epoch_metrics = {
                    # skipped_steps is a COUNTER (how many steps this
                    # epoch hit the nan_policy guard), not a mean.
                    k: float(
                        (np.sum if k == "skipped_steps" else np.mean)(
                            np.concatenate(
                                [
                                    np.atleast_1d(np.asarray(m[k]))
                                    for m in host_accum
                                ]
                            )
                        )
                    )
                    for k in (host_accum[0] if host_accum else {})
                }
                dt = time.perf_counter() - t0
                examples = steps_trained * self.loader.batch_size
                epoch_metrics["examples_per_sec"] = examples / dt if dt > 0 else 0.0
                # A mid-epoch resume trains only steps start_b..spe-1 of
                # its first epoch: its train aggregates describe a PARTIAL
                # epoch and must not be compared against full ones.
                partial_epoch = epoch == start_epoch and start_b > 0
                history["train"].append(epoch_metrics)
                line = (
                    f"epoch {epoch + 1}/{self.epochs} "
                    f"loss={epoch_metrics.get('loss', float('nan')):.4f} "
                    f"acc={epoch_metrics.get('accuracy', float('nan')):.4f} "
                    f"({epoch_metrics['examples_per_sec']:.0f} ex/s)"
                )
                if partial_epoch:
                    line += f" [partial: resumed at step {start_b}]"

                # vmetrics is non-None only when validation RAN this
                # epoch (and produced batches): val_* records/scalars,
                # best-checkpoint ranking, and early stopping all key off
                # fresh measurements — stale values are never re-emitted
                # or re-scored.
                vmetrics = None
                if has_val_split and (epoch + 1) % self.validate_every == 0:
                    vmetrics = run_weighted_eval(
                        self.loader, "validation", eval_step, state,
                        batch_sharding, epoch=epoch,
                    ) or None
                    # Validation is a deliberate pause, not step time.
                    self._obs_mark_stall()
                    if vmetrics is not None:
                        history["validation"].append(vmetrics)
                        line += (
                            f" | val_loss={vmetrics.get('loss', float('nan')):.4f} "
                            f"val_acc={vmetrics.get('accuracy', float('nan')):.4f}"
                        )
                self._log(line)

                if self.metrics_file:
                    record = {"epoch": epoch, **epoch_metrics}
                    if partial_epoch:
                        record["partial_epoch"] = True
                    if vmetrics is not None:
                        record.update(
                            {f"val_{k}": v for k, v in vmetrics.items()}
                        )
                    with open(self.metrics_file, "a") as f:
                        f.write(json.dumps(record) + "\n")

                # Epoch aggregates use a distinct prefix so they never collide
                # with the per-step train/ tags at the same global step (two
                # different values on one TensorBoard tag renders as a zigzag).
                scalars = {f"train_epoch/{k}": v for k, v in epoch_metrics.items()}
                if vmetrics is not None:
                    scalars.update({f"val/{k}": v for k, v in vmetrics.items()})
                self.writer.write_scalars((epoch + 1) * spe, scalars)
                self._publish_epoch_observability(
                    epoch, steps_trained, epoch_metrics, vmetrics
                )

                # The epoch's scored metrics: fresh validation when it
                # ran; train metrics only when the run HAS no validation
                # (never mixed — train and val values are not on one
                # scale). None = nothing scoreable this epoch. A partial
                # epoch's train aggregates are not comparable to full
                # epochs' (fewer, later-in-permutation steps), so they
                # are excluded from best-ranking and early stopping;
                # validation metrics always cover the full split and
                # stay scoreable.
                scored = vmetrics if has_val_split else epoch_metrics
                if partial_epoch and not has_val_split:
                    scored = None

                if (
                    self.checkpointer.enabled
                    and self.checkpointer.save_every_epochs > 0
                    and (epoch + 1) % self.checkpointer.save_every_epochs == 0
                ):
                    if (
                        self.checkpointer.keep_best_metric is not None
                        and scored is None
                    ):
                        # Best-ranking needs fresh comparable metrics:
                        # rank-saves happen on validated epochs only.
                        pass
                    else:
                        with _obs_trace.span(
                            "checkpoint", step=(epoch + 1) * spe
                        ):
                            self.checkpointer.save(state, metrics=scored)
                        self._obs_mark_stall()

                if self.early_stop_metric is not None and scored is not None:
                    if self.early_stop_metric not in scored:
                        raise ValueError(
                            f"early_stop_metric={self.early_stop_metric!r} "
                            f"not in epoch metrics {sorted(scored)}."
                        )
                    current = float(scored[self.early_stop_metric])
                    improved = es_best is None or (
                        es_best - current > self.early_stop_min_delta
                        if es_minimize
                        else current - es_best > self.early_stop_min_delta
                    )
                    if improved:
                        es_best, es_stale = current, 0
                    else:
                        es_stale += 1
                        if es_stale >= self.early_stop_patience:
                            self._log(
                                f"early stop at epoch {epoch + 1}: "
                                f"{self.early_stop_metric} has not improved "
                                f"for {es_stale} scored epoch(s) "
                                f"(best {es_best:.6g})"
                            )
                            break

        finally:
            # Crash-safe teardown: pending async checkpoint saves
            # complete and buffered metrics (TensorBoard events) become
            # durable even when an epoch raises mid-run. flush, not
            # close: the writer is a long-lived component and run() may
            # be called again on the same experiment. A teardown step
            # that ITSELF raises while an exception is already in
            # flight must not mask it (the original traceback is the
            # one that says what actually went wrong) — it is logged
            # and suppressed; with no exception in flight the first
            # teardown failure propagates after every step has run.
            import sys

            self.guard.uninstall()
            pending = sys.exc_info()[1]
            teardown_err: Optional[BaseException] = None
            for what, fn in (
                # First: close any open jax.profiler capture window — an
                # exception mid-capture must not leave start_trace open
                # (the next run's start_trace would fail and the trace
                # buffers leak).
                ("profiler.stop_trace", self._abort_jax_trace),
                ("checkpointer.wait", self.checkpointer.wait),
                ("writer.flush", self.writer.flush),
                ("trace.export", self._finish_host_trace),
                ("obs_server.stop", self._stop_obs_server),
            ):
                try:
                    fn()
                except Exception as e:
                    if pending is not None or teardown_err is not None:
                        import logging

                        logging.getLogger(__name__).warning(
                            "teardown %s failed (%s); suppressed so the "
                            "original exception propagates",
                            what,
                            e,
                        )
                    else:
                        teardown_err = e
            if teardown_err is not None:
                raise teardown_err
        if self.export_model_to:
            from zookeeper_tpu.training.checkpoint import save_model

            export_params = (
                state.ema_params
                if self.ema_decay > 0 and state.ema_params is not None
                else state.params
            )
            save_model(self.export_model_to, export_params, state.model_state)
        self.final_state = state
        return history


@component
class EvalExperiment(Experiment):
    """Evaluate an exported model checkpoint on a dataset split — the
    standard load-and-score workflow pairing with ``export_model_to``
    (and with ``ConvertPacked`` output when the model component is built
    with ``packed_weights=True``).

    The loader defaults to ``drop_remainder=False`` so the headline score
    covers EVERY example of the split (weighted partial final batch);
    multi-host eval should set ``loader.drop_remainder=True`` to keep
    collectives in lockstep. ``split="train"`` iterates the training data
    in eval mode (no shuffle/augmentation)."""

    loader: DataLoader = ComponentField(DataLoader, drop_remainder=False)
    model: Model = ComponentField()
    partitioner: Partitioner = ComponentField(SingleDevicePartitioner)
    runtime: DistributedRuntime = ComponentField(DistributedRuntime)

    #: Model-only checkpoint (save_model format) OR a full
    #: ``Checkpointer`` directory (the latest step of a training run).
    checkpoint: str = Field()
    #: Which weights to score when the checkpoint carries both: "auto"
    #: (EMA when present — the ship artifact), "ema" (require the EMA
    #: shadow), or "raw" (the raw training params). Shares
    #: ``training.checkpoint.select_inference_weights`` with the serving
    #: loader, so eval scores exactly what serving ships.
    weights: str = Field("auto")
    split: str = Field("validation")
    batch_size: int = Field(32)
    seed: int = Field(0)
    verbose: bool = Field(True)
    #: Also report top-5 accuracy (ImageNet companion metric).
    track_top5: bool = Field(False)
    #: LM headline metrics: derive ``perplexity`` (e^CE) and
    #: ``bits_per_token`` (CE / ln 2) from the split's weighted-mean
    #: cross-entropy. Derived AFTER aggregation — ``exp`` is convex, so
    #: a per-batch perplexity mean would overstate the true
    #: whole-split perplexity; the weighted CE mean is the exact
    #: token-level mean (every position contributes one CE term and
    #: batches are example-weighted). The existing CE/accuracy already
    #: broadcast over positions (rank-general metrics), so this is
    #: pure arithmetic on the aggregate — no LM-specific eval step.
    track_lm_metrics: bool = Field(False)

    @Field
    def num_classes(self) -> int:
        return int(self.loader.dataset.resolved_num_classes())

    def run(self) -> Dict[str, float]:
        import jax

        from zookeeper_tpu.training.checkpoint import load_inference_model

        if self.weights not in ("auto", "ema", "raw"):
            raise ValueError(
                f"weights={self.weights!r} unknown; choose auto/ema/raw."
            )
        if self.split not in ("train", "validation"):
            # The loader maps any non-"train" name to the validation
            # split; scoring "test" against validation data silently
            # would misreport.
            raise ValueError(
                f"split={self.split!r} unknown; datasets here expose "
                "'train' and 'validation'."
            )
        if self.track_top5 and self.num_classes < 5:
            raise ValueError(
                f"track_top5=True needs >= 5 classes "
                f"(dataset has {self.num_classes})."
            )
        if self.verbose:
            print(pretty_print(self), flush=True)
        self.runtime.initialize()
        partitioner = self.partitioner
        partitioner.setup()

        input_shape = self.loader.preprocessing.input_shape
        # Same partitioner->model seam as training (the SP attention
        # callable must be injected before build for dp x sp eval).
        partitioner.prepare_model(self.model)
        module = self.model.build(input_shape, self.num_classes)
        # The unified inference loader (shared with the serving engine):
        # model-only export OR full Checkpointer directory, EMA-vs-raw
        # selected by the weights Field, structure validated against the
        # freshly-built model's abstract init.
        abstract = jax.eval_shape(
            lambda: self.model.initialize(
                module, input_shape, seed=self.seed
            )
        )
        params, model_state = load_inference_model(
            self.checkpoint,
            weights=self.weights,
            params_like=abstract[0],
            model_state_like=abstract[1],
        )
        state = TrainState.create(
            apply_fn=module.apply,
            params=params,
            model_state=model_state,
            tx=_eval_noop_tx(),
        )
        state = partitioner.shard_state(state)
        eval_step = partitioner.compile_eval(
            make_eval_step(top5=self.track_top5), state
        )
        metrics = run_weighted_eval(
            self.loader, self.split, eval_step, state,
            partitioner.batch_sharding(),
        )
        if not metrics:
            raise ValueError(f"Split {self.split!r} produced no batches.")
        if self.track_lm_metrics:
            import math

            ce = metrics["loss"]
            metrics["perplexity"] = math.exp(ce)
            metrics["bits_per_token"] = ce / math.log(2.0)
        if self.verbose:
            line = " ".join(f"{k}={v:.4f}" for k, v in sorted(metrics.items()))
            print(f"eval[{self.split}] {line}", flush=True)
        return metrics


def _eval_noop_tx():
    """A do-nothing optax transformation (EvalExperiment never updates)."""
    import optax

    return optax.identity()
