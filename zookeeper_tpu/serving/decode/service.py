"""The LM decode service config: checkpoint -> warmed decode engine.

The ``ServingConfig`` counterpart for token streaming: point it at a
``save_model`` export or ``Checkpointer`` directory of a
``TransformerLM`` run (EMA-vs-raw selection identical), and
``build_service()`` returns a warmed :class:`DecodeEngine` +
:class:`DecodeScheduler` pair. ``run()`` is the demo/bench driver: a
deterministic synthetic prompt stream through the continuous-batching
loop, one JSON result line (tokens/s, TTFT percentiles, refill count,
compile counts) through the same MetricsWriter sinks — so
``python examples/serve_lm.py ServeLM checkpoint=...`` is an
end-to-end smoke of the whole decode subsystem.

The decode-attention flavor threads through the engine component
(``engine.decode_attention=auto|pallas|reference`` on the CLI —
docs/DESIGN.md §17): "auto" serves with the length-aware Pallas pool
decode kernel on TPU and the reference einsum elsewhere; the result
line and ``/statusz`` report the RESOLVED flavor plus the
``decode_mbu`` memory-bandwidth roofline.
"""

import json
import logging
import time
from typing import Any, Dict, Optional

from zookeeper_tpu.core import ComponentField, Field, component, pretty_print
from zookeeper_tpu.models.base import Model
from zookeeper_tpu.models.transformer import TransformerLM
from zookeeper_tpu.observability.device import device_summary
from zookeeper_tpu.parallel.partitioner import (
    Partitioner,
    SingleDevicePartitioner,
)
from zookeeper_tpu.serving.decode.engine import DecodeEngine
from zookeeper_tpu.serving.decode.metrics import DecodeMetrics
from zookeeper_tpu.serving.decode.scheduler import DecodeScheduler
from zookeeper_tpu.serving.decode.speculative import SpeculativeDecoding
from zookeeper_tpu.serving.guardrails import OverloadGuard
from zookeeper_tpu.training.experiment import Experiment
from zookeeper_tpu.training.metrics import CompositeMetricsWriter, MetricsWriter

logger = logging.getLogger(__name__)

__all__ = ["LMServingConfig"]


@component
class LMServingConfig(Experiment):
    """Configurable token-streaming service over a causal LM.

    Subclass with ``@task`` for a CLI entry point — see
    ``examples/serve_lm.py``.
    """

    model: Model = ComponentField(TransformerLM)
    partitioner: Partitioner = ComponentField(SingleDevicePartitioner)
    engine: DecodeEngine = ComponentField(DecodeEngine)
    scheduler: DecodeScheduler = ComponentField(DecodeScheduler)
    metrics: DecodeMetrics = ComponentField(DecodeMetrics)
    writer: MetricsWriter = ComponentField(CompositeMetricsWriter)
    #: Speculative decoding (docs/DESIGN.md §18): ``speculative.
    #: enabled=True speculative.k=4 speculative.draft_checkpoint=...``
    #: serves the draft/verify schedule — token-identical to plain
    #: greedy decode, up to k+1 tokens per teacher dispatch. Resolved
    #: at bind; an unavailable draft (unreadable checkpoint,
    #: incompatible geometry) degrades LOUDLY to plain decode rather
    #: than failing the service.
    speculative: SpeculativeDecoding = ComponentField(SpeculativeDecoding)
    #: Overload guardrails (docs/DESIGN.md §24): ``guard.enabled=True``
    #: turns on predicted-miss admission (EWMA queue-wait + per-token
    #: service estimate vs each request's deadline ⇒ shed at submit
    #: with :class:`PredictedMissError`) and, with ``guard.
    #: brownout_after>0``, the brown-out degraded mode (capped
    #: ``max_new_tokens`` + speculation off, applied only at the
    #: drained-slot-array boundary). Off by default — zero behavior
    #: change unless asked for.
    guard: OverloadGuard = ComponentField(OverloadGuard)

    #: Deployment artifact: a ``save_model`` export or a full
    #: ``Checkpointer`` directory (latest step). None = fresh-init
    #: weights (compile/latency smoke without a training run).
    checkpoint: Optional[str] = Field(None)
    #: EMA-vs-raw weight selection (same contract as ServingConfig).
    weights: str = Field("auto")

    #: Model build geometry: the positional capacity the module is
    #: built with (prompt + generated tokens must fit) and the vocab.
    seq_len: int = Field(128)
    vocab_size: int = Field(256)
    seed: int = Field(0)

    #: Pre-compile the full prefill/decode program grid before traffic.
    warmup: bool = Field(True)
    #: Demo-driver knobs for ``run()``: request count, prompt-length
    #: range, and the per-request generation budget.
    requests: int = Field(32)
    max_prompt: int = Field(12)
    new_tokens: int = Field(16)
    verbose: bool = Field(True)
    #: Live observability endpoint: ``/metrics`` (every ``zk_decode_*``
    #: series) + ``/statusz`` decode section (active slots, queue
    #: depth, KV pages in use). -1 = off; 0 = ephemeral port.
    metrics_port: int = Field(-1)
    #: Flight recorder (docs/DESIGN.md §16): directory for rate-limited
    #: debug bundles on decode-worker crashes, recompiles, watchdog
    #: anomalies, fault injections and ``POST /debugz``. None = off.
    flight_recorder_dir: Optional[str] = Field(None)
    #: Minimum seconds between bundles (manual ``/debugz`` bypasses).
    flight_recorder_interval_s: float = Field(30.0)

    def build_service(self):
        """Load weights, bind + warm the engine, bind the scheduler.
        Returns ``(engine, scheduler)`` (also kept on self)."""
        if self.weights not in ("auto", "ema", "raw"):
            raise ValueError(
                f"weights={self.weights!r} unknown; choose auto/ema/raw."
            )
        if self.requests < 0 or self.max_prompt < 1 or self.new_tokens < 1:
            raise ValueError(
                f"requests={self.requests} must be >= 0, max_prompt="
                f"{self.max_prompt} and new_tokens={self.new_tokens} "
                ">= 1."
            )
        module, params, model_state = self._build_module_and_weights()
        self.partitioner.setup()
        self.engine.bind(
            module,
            params,
            model_state,
            partitioner=self.partitioner,
        )
        if self.warmup:
            self.engine.warmup()
        spec = self._resolve_speculative()
        self.guard.bind()
        self.scheduler.bind(
            self.engine,
            metrics=self.metrics,
            speculative=spec,
            guard=self.guard if self.guard.enabled else None,
        )
        if self.metrics_port >= 0 or self.flight_recorder_dir:
            try:
                if self.flight_recorder_dir:
                    self._start_flight_recorder()
                if self.metrics_port >= 0:
                    self._start_obs_server()
            except BaseException:
                self._teardown_service(suppress=True)
                raise
        return self.engine, self.scheduler

    def _build_module_and_weights(self):
        """Build the module and resolve its weights (checkpoint load or
        fresh init) — shared by this config and the disaggregated one,
        which binds the SAME weights into two role engines."""
        module = self.model.build((self.seq_len,), self.vocab_size)
        if self.checkpoint:
            import jax

            from zookeeper_tpu.training.checkpoint import (
                load_inference_model,
            )

            abstract = jax.eval_shape(
                lambda: self.model.initialize(
                    module, (self.seq_len,), seed=self.seed
                )
            )
            params, model_state = load_inference_model(
                self.checkpoint,
                weights=self.weights,
                params_like=abstract[0],
                model_state_like=abstract[1],
            )
        else:
            params, model_state = self.model.initialize(
                module, (self.seq_len,), seed=self.seed
            )
        return module, params, model_state

    def _resolve_speculative(self) -> Optional[SpeculativeDecoding]:
        """Resolve ``speculative`` at bind (docs/DESIGN.md §18): build
        the draft module from ``speculative.draft_model`` at the
        teacher's seq_len/vocab, load ``draft_checkpoint`` (EMA/raw per
        ``draft_weights``) or fresh-init when none is given (program-
        shape smoke — acceptance will be ~chance, flagged loudly), and
        bind the draft engine. An UNAVAILABLE draft — unreadable
        checkpoint, incompatible geometry — degrades LOUDLY to plain
        decode: the service stays up, the warning says why speculation
        is off. Returns the bound binding or None."""
        sp = self.speculative
        if not sp.enabled:
            return None
        draft_module = sp.draft_model.build((self.seq_len,), self.vocab_size)
        try:
            if sp.draft_checkpoint:
                import jax

                from zookeeper_tpu.training.checkpoint import (
                    load_inference_model,
                )

                abstract = jax.eval_shape(
                    lambda: sp.draft_model.initialize(
                        draft_module, (self.seq_len,), seed=self.seed
                    )
                )
                draft_params, draft_state = load_inference_model(
                    sp.draft_checkpoint,
                    weights=sp.draft_weights,
                    params_like=abstract[0],
                    model_state_like=abstract[1],
                )
            else:
                logger.warning(
                    "speculative.enabled with no draft_checkpoint: "
                    "serving a FRESH-INIT draft (program-shape smoke "
                    "only — acceptance will be ~chance; point "
                    "speculative.draft_checkpoint at the distilled "
                    "student for real speedup)"
                )
                draft_params, draft_state = sp.draft_model.initialize(
                    draft_module, (self.seq_len,), seed=self.seed
                )
            return sp.bind(
                self.engine,
                draft_module,
                draft_params,
                draft_state,
                partitioner=self.partitioner,
            )
        except (OSError, ValueError) as e:
            # Degrade loudly: a missing/unreadable/mismatched draft
            # must not take the TEACHER service down — but silent
            # plain-decode-with-spec-configured would misreport every
            # capacity plan built on the expected speedup.
            logger.warning(
                "speculative decoding DISABLED — draft unavailable "
                "(%s); serving plain greedy decode", e,
            )
            if self.verbose:
                print(
                    f"speculative decoding disabled: {e}", flush=True
                )
            return None

    def _request_log_status(self):
        """``/statusz`` + bundle section: the recent terminal-stream
        tail (rid, timestamps, outcome — docs/DESIGN.md §16)."""
        log = self.scheduler.request_log
        return log.as_status() if log is not None else {}

    def _status_providers(self):
        """Named ``/statusz`` (+ flight-recorder bundle) sections. The
        disaggregated config extends this with per-role sections."""
        return {
            "decode": self.scheduler.status,
            "requests": self._request_log_status,
            "guardrails": self.guard.status,
        }

    def _start_flight_recorder(self):
        from zookeeper_tpu.observability import recorder as _recorder
        from zookeeper_tpu.observability.registry import default_registry

        rec = _recorder.arm(
            self.flight_recorder_dir,
            registries=[
                default_registry(),
                self.metrics.registry,
                self.guard.registry,
            ],
            status_providers=self._status_providers(),
            request_logs={"decode": self.scheduler.request_log},
            min_interval_s=self.flight_recorder_interval_s,
        )
        object.__setattr__(self, "flight_recorder", rec)
        if self.verbose:
            print(
                f"flight recorder armed: {self.flight_recorder_dir}",
                flush=True,
            )
        return rec

    def _stop_flight_recorder(self):
        from zookeeper_tpu.observability import recorder as _recorder

        rec = getattr(self, "flight_recorder", None)
        if rec is not None:
            object.__setattr__(self, "flight_recorder", None)
            _recorder.disarm(rec)

    def _start_obs_server(self):
        from zookeeper_tpu.observability import (
            DeviceProbe,
            ObservabilityServer,
        )
        from zookeeper_tpu.observability.registry import default_registry

        server = ObservabilityServer(
            [
                default_registry(),
                self.metrics.registry,
                self.guard.registry,
            ],
            port=self.metrics_port,
            status_providers=self._status_providers(),
        )
        server.start()
        object.__setattr__(self, "obs_server", server)
        probe = DeviceProbe()
        probe.poll_once()
        probe.start()
        object.__setattr__(self, "obs_probe", probe)
        if self.verbose:
            print(
                f"observability endpoint: {server.url}/metrics",
                flush=True,
            )
        return server

    def _teardown_service(self, *, suppress: bool = False) -> None:
        """The ONE teardown sequence (endpoint port, device probe,
        scheduler worker, the engine's device state) shared by every
        exit path — the ``run_teardown_steps`` contract
        ``ServingConfig`` uses."""
        from zookeeper_tpu.serving.service import run_teardown_steps

        steps = []
        server = getattr(self, "obs_server", None)
        if server is not None:
            object.__setattr__(self, "obs_server", None)
            steps.append(server.stop)
        probe = getattr(self, "obs_probe", None)
        if probe is not None:
            object.__setattr__(self, "obs_probe", None)
            steps.append(probe.stop)
        steps.append(self._stop_flight_recorder)
        steps.append(self.scheduler.close)
        # Last: the weights and the page pool leave the device with the
        # service, not with the last reference to a stream.
        steps.append(self.engine.release)
        run_teardown_steps(steps, suppress=suppress)

    def finish_report(
        self,
        *,
        warm_compiles: int,
        n_requests: int,
        tokens: int,
        dt: float,
        writer_extra: Optional[Dict[str, float]] = None,
        result_extra: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """The one reporting path: metrics snapshot through the writer,
        one JSON result line, teardown."""
        tokens_per_sec = tokens / dt if dt > 0 else 0.0
        snapshot = self.metrics.emit(
            self.writer,
            step=0,
            extra={"tokens_per_sec": tokens_per_sec, **(writer_extra or {})},
        )
        self.writer.flush()
        result = {
            **{k: round(float(v), 4) for k, v in snapshot.items()},
            "model": type(self.model).__name__,
            "device": device_summary(),
            "weights": self.weights,
            "slots": int(self.engine.slots),
            "seq_buckets": [int(s) for s in self.engine.seq_buckets],
            "kv_capacity": self.engine.capacity,
            # The RESOLVED cache-attention flavor (docs/DESIGN.md §17):
            # "pallas" = the length-aware pool decode kernel,
            # "reference" = the oracle einsum (auto-selected off-TPU or
            # degraded on unsupported geometry).
            "decode_attention": self.engine.decode_attention_flavor,
            "decode_mbu": round(self.engine.decode_mbu, 4),
            # Page-pool vitals (docs/DESIGN.md §20): pool fill and
            # prefix-cache hit rate (``kv_layout`` is a constant that
            # CI still reads).
            "kv_layout": str(self.engine.kv_layout),
            "kv_pool_fill": round(
                self.engine.page_pool.used_pages
                / self.engine.page_pool.num_pages,
                4,
            ),
            "prefix_cache_hit_rate": round(
                self.engine.page_pool.prefix_hit_rate, 4
            ),
            # Speculative schedule (docs/DESIGN.md §18): the RESOLVED
            # state (config-enabled but draft-unavailable degrades to
            # False here — the result line reports what actually
            # served), k, and the live acceptance rate.
            "speculative": (
                getattr(self.scheduler, "_speculative", None) is not None
            ),
            "spec_k": (
                int(self.scheduler._speculative.k)
                if getattr(self.scheduler, "_speculative", None) is not None
                else 0
            ),
            # Unconditional when speculation serves (-1 = no window ran
            # yet); the snapshot merge above only carries it once a
            # window committed — scripts parsing the README'd key must
            # never find it absent on a speculative serve.
            **(
                {
                    "spec_acceptance_rate": round(
                        self.scheduler._speculative.acceptance_rate, 4
                    )
                }
                if getattr(self.scheduler, "_speculative", None) is not None
                else {}
            ),
            # Serving-role topology (docs/DESIGN.md §22): single-mesh
            # serves everything on the decode role with nothing to
            # transfer; the disaggregated config overrides all three
            # via result_extra. The keys are UNCONDITIONAL so scripts
            # parsing the result line never branch on topology.
            "role": "decode",
            "transfer_pages": 0,
            "transfer_ms_p50": -1.0,
            "compiles": self.engine.compile_count,
            "recompiles_after_warmup": (
                self.engine.compile_count - warm_compiles
            ),
            "requests": n_requests,
            "generated_tokens": tokens,
            "tokens_per_sec": round(tokens_per_sec, 1),
            **(result_extra or {}),
        }
        if self.verbose:
            print(json.dumps(result), flush=True)
        self._teardown_service()
        return result

    def run(self) -> Dict[str, Any]:
        """Serve a deterministic synthetic prompt stream and report."""
        import numpy as np

        if self.verbose:
            print(pretty_print(self), flush=True)
        engine, scheduler = self.build_service()
        try:
            warm_compiles = engine.compile_count
            rng = np.random.default_rng(self.seed)
            max_prompt = min(self.max_prompt, engine.max_prompt)
            t0 = time.perf_counter()
            streams = []
            for _ in range(self.requests):
                n = int(rng.integers(1, max_prompt + 1))
                prompt = rng.integers(
                    1, self.vocab_size, size=n
                ).astype(np.int32)
                streams.append(
                    scheduler.submit(
                        prompt, max_new_tokens=self.new_tokens
                    )
                )
            scheduler.drain()
            dt = time.perf_counter() - t0
            tokens = 0
            for stream in streams:
                out = stream.result()
                assert out.shape[0] >= 1, out.shape
                tokens += int(out.shape[0])
        except BaseException:
            self._teardown_service(suppress=True)
            raise
        return self.finish_report(
            warm_compiles=warm_compiles,
            n_requests=self.requests,
            tokens=tokens,
            dt=dt,
        )
