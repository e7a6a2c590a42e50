"""The disaggregated LM serving config: one checkpoint, two role
engines (docs/DESIGN.md §22).

``LMServingConfig`` with the topology split: the SAME weights bind
into a PREFILL engine (few lanes, wide ``prefill_buckets``, prefix
cache on — the compute-bound role) on one mesh slice and a DECODE
engine (the full slot array, ``prefill_buckets=(1,)`` — it never runs
prefill — prefix cache off, the memory-bound role) on another, joined
by a :class:`~zookeeper_tpu.serving.disagg.transfer.PageTransfer` and
scheduled by the :class:`~zookeeper_tpu.serving.disagg.scheduler.
DisaggScheduler`. Both engines run the paged KV layout — the handoff
unit is the page.

Everything else inherits: checkpoint/EMA selection, speculative
decoding (the draft lives with the DECODE role), the demo driver, the
observability endpoint (which gains ``prefill``/``transfer``/
``topology`` ``/statusz`` sections and the ``zk_transfer_*`` series),
and the one-JSON-line report (which gains ``role="disagg"`` and the
transfer keys).

CLI::

    python examples/serve_lm.py ServeLM --disagg checkpoint=/tmp/ckpt
    # role sizing:
    ... --disagg prefill_engine.slots=4 engine.slots=16 \\
        partitioner.prefill_devices=2 partitioner.decode_devices=6
"""

import logging
from typing import Any, Dict, Optional

from zookeeper_tpu.core import ComponentField, component
from zookeeper_tpu.parallel.partitioner import Partitioner
from zookeeper_tpu.serving.decode.engine import DecodeEngine
from zookeeper_tpu.serving.decode.scheduler import DecodeScheduler
from zookeeper_tpu.serving.decode.service import LMServingConfig
from zookeeper_tpu.serving.disagg.partition import DisaggPartitioner
from zookeeper_tpu.serving.disagg.scheduler import DisaggScheduler
from zookeeper_tpu.serving.disagg.transfer import PageTransfer

logger = logging.getLogger(__name__)

__all__ = ["DisaggServingConfig"]


@component
class DisaggServingConfig(LMServingConfig):
    """Disaggregated prefill/decode serving (see module docstring).
    Subclass with ``@task`` for a CLI entry point — ``examples/
    serve_lm.py --disagg``."""

    #: The role topology: two mesh slices (disjoint when the host has
    #: the devices; overlapping single-host fallback otherwise).
    partitioner: Partitioner = ComponentField(DisaggPartitioner)
    #: The DECODE role (the inherited ``engine`` slot, so every
    #: downstream report key keeps meaning "the serving engine"):
    #: prefill programs unused (admission
    #: arrives by page transfer), prefix cache off (adopted pages are
    #: private to their stream).
    engine: DecodeEngine = ComponentField(
        DecodeEngine, prefix_cache=False
    )
    #: The PREFILL role: few lanes batched wide, prefix cache on (warm
    #: prompts skip prefill BEFORE the transfer, so shared pages are
    #: computed once and shipped many times).
    prefill_engine: DecodeEngine = ComponentField(
        DecodeEngine, slots=4, prefill_buckets=(1, 2, 4)
    )
    #: The page mover (``transfer.host_bounce=True`` forces the
    #: portable host path for A/B).
    transfer: PageTransfer = ComponentField(PageTransfer)
    scheduler: DecodeScheduler = ComponentField(DisaggScheduler)

    # -- wiring ----------------------------------------------------------

    def _role_partitioners(self):
        """(prefill, decode) role partitioners: the DisaggPartitioner's
        slices, or the one configured partitioner for both roles when a
        user swapped in a non-role-aware one."""
        p = self.partitioner
        if hasattr(p, "prefill") and hasattr(p, "decode"):
            return p.prefill, p.decode
        return p, p

    def build_service(self):
        """Load weights ONCE, bind + warm both role engines, bind the
        transfer and the disaggregated scheduler. Returns ``(engine,
        scheduler)`` — the decode role, like the single-mesh config."""
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
        for role, eng in (
            ("prefill", self.prefill_engine),
            ("decode", self.engine),
        ):
            if int(eng.prefill_chunk_tokens) > 0:
                # Chunked prefill is the SINGLE-mesh answer to prefill/
                # decode interference (docs/DESIGN.md §25); disagg
                # already isolates the roles on separate slices, so
                # chunking would only fragment the prefill role's
                # dispatches. Warn-degrade, mirroring the §20 posture.
                logger.warning(
                    "prefill_chunk_tokens=%d ignored on the disagg %s "
                    "role: disaggregation already isolates prefill "
                    "from decode (docs/DESIGN.md §25) — running "
                    "monolithic prefill.",
                    int(eng.prefill_chunk_tokens),
                    role,
                )
                # Post-configure components are immutable; the degrade
                # writes the instance value store directly (the same
                # bypass the component runtime itself uses) BEFORE
                # bind() reads the field.
                object.__getattribute__(
                    eng, "__component_values__"
                )["prefill_chunk_tokens"] = 0
        module, params, model_state = self._build_module_and_weights()
        self.partitioner.setup()
        prefill_part, decode_part = self._role_partitioners()
        self.prefill_engine.bind(
            module, params, model_state, partitioner=prefill_part
        )
        self.engine.bind(
            module, params, model_state, partitioner=decode_part
        )
        if self.warmup:
            self.prefill_engine.warmup()
            self.engine.warmup()
            # The handoff programs compile with the grid: export on
            # the prefill role, import on the decode role (each role
            # warms both directions' own half).
            self.prefill_engine.warmup_transfer()
            self.engine.warmup_transfer()
        self.transfer.bind(
            self.prefill_engine, self.engine, metrics=self.metrics
        )
        spec = self._resolve_speculative()
        self.scheduler.bind(
            self.prefill_engine,
            self.engine,
            self.transfer,
            metrics=self.metrics,
            speculative=spec,
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

    # -- observability ----------------------------------------------------

    def _prefill_status(self) -> Dict[str, Any]:
        """``/statusz`` prefill-role section."""
        pe = self.prefill_engine
        sched = self.scheduler
        return {
            "lanes": int(pe.slots),
            "parked_handoffs": (
                sched.parked if hasattr(sched, "parked") else 0
            ),
            "compiles": pe.compile_count,
            "recompiles_detected": pe.recompiles_detected,
            "decode_attention": pe.decode_attention_flavor,
            "kv_pool": pe.pool_status(),
        }

    def _topology_status(self) -> Dict[str, Any]:
        p = self.partitioner
        return p.describe() if hasattr(p, "describe") else {}

    def _status_providers(self):
        out = super()._status_providers()
        out["prefill"] = self._prefill_status
        out["transfer"] = self.transfer.status
        out["topology"] = self._topology_status
        return out

    # -- reporting --------------------------------------------------------

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
        """The single-mesh result line with the §22 keys: ``role``
        flips to "disagg" and the transfer totals/latency land
        unconditionally."""
        ts = self.transfer.status()
        p = self.partitioner
        extra = {
            "role": "disagg",
            "prefill_lanes": int(self.prefill_engine.slots),
            "prefill_compiles": self.prefill_engine.compile_count,
            "disjoint_roles": bool(getattr(p, "disjoint", False)),
            "transfer_handoffs": int(ts["handoffs_total"]),
            "transfer_pages": int(ts["pages_total"]),
            "transfer_bytes": int(ts["bytes_total"]),
            "transfer_host_bounces": int(ts["host_bounces"]),
            "transfer_ms_p50": float(ts["transfer_ms_p50"]),
            **(result_extra or {}),
        }
        return super().finish_report(
            warm_compiles=warm_compiles,
            n_requests=n_requests,
            tokens=tokens,
            dt=dt,
            writer_extra=writer_extra,
            result_extra=extra,
        )
