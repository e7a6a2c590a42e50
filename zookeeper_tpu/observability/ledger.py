"""The program ledger: per-executable XLA cost accounting for a live
process.

The host side became observable in the spans/registry layer
(docs/DESIGN.md §13), but the DEVICE side stayed a black box outside
manual ``jax.profiler`` captures: nothing could answer "what is this
process's MFU right now", "which compiled program owns the HBM", or
"did a recompile just stall serving" from a live endpoint. This module
closes that gap at the one place every executable passes through — the
lower/compile seam:

- :func:`cost_analysis_dict` / :func:`cost_flops` — the ONE
  ``cost_analysis()`` wrapper (``models.summary``, ``bench.py``, the
  serving engine and the partitioner seams all call it), tolerant of
  backends that return ``None``, a ``[dict]`` list, or a dict missing
  keys (the CPU backend does all three across jax versions).
- :class:`ProgramLedger` — a process-global, thread-safe record of
  every compiled program: identity key, FLOPs/bytes from XLA's own
  cost analysis, lower/compile wall time, and the compiled memory
  analysis (argument/output/temp bytes — which program owns the HBM).
  Every record also bumps ``zk_compiles_total{kind=}`` /
  ``zk_compile_ms_total{kind=}`` counters in the default registry and
  renders as a ``/statusz`` section (``observability.export``).
- :class:`LedgeredExecutable` — the partitioner seams' wrapper: the
  first call per argument signature does the AOT ``lower()`` +
  ``compile()`` explicitly (timed, ledger-recorded — the same work
  ``jax.jit`` would have done lazily, now visible), and every later
  call dispatches the compiled executable directly (one attribute read
  of steady-state overhead). An argument-shape change falls back to
  the wrapped ``jit`` callable, which retraces exactly as an
  uninstrumented seam would.
- :func:`mfu` — FLOPs/time/peak with total guards; the gauge math for
  ``zk_serve_mfu`` (peaks from ``observability.peaks`` so the live
  gauge and bench.py divide by the same anchors).

Identity keys (docs/DESIGN.md §14): ``<kind>`` names the seam
(``train_step`` / ``multi_step`` / ``eval_step`` / ``serve_forward`` /
``summary_forward``), the key string appends the argument signature
(leaf count + a shape/dtype digest) and the mesh axis sizes — enough
to tell two programs apart in ``/statusz`` without dumping whole
pytree structures.
"""

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from zookeeper_tpu.observability import trace as _trace
from zookeeper_tpu.observability.registry import default_registry

__all__ = [
    "LedgeredExecutable",
    "ProgramLedger",
    "ProgramRecord",
    "cost_analysis_dict",
    "cost_bytes",
    "cost_flops",
    "default_ledger",
    "mbu",
    "mfu",
]


# -- the shared cost_analysis wrapper ------------------------------------


def cost_analysis_dict(program: Any) -> Dict[str, float]:
    """``program.cost_analysis()`` as a plain dict, or ``{}``.

    ``program`` is anything with a ``cost_analysis`` method (a jax
    ``Lowered`` or ``Compiled``). Every historical failure mode maps to
    ``{}`` instead of raising: backends that return ``None`` (CPU on
    some versions), the older ``[dict]`` list convention, a non-dict
    payload, or ``cost_analysis`` itself raising (interpret-mode
    Pallas, unsupported backends). Cost analysis is diagnostic — it
    must never be the reason a compile seam dies."""
    try:
        analysis = program.cost_analysis()
    except Exception:
        return {}
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return {}
    return analysis


def _scalar_from(analysis: Dict[str, Any], key: str) -> Optional[float]:
    value = analysis.get(key)
    try:
        value = float(value)
    except (TypeError, ValueError):
        return None
    # NaN/negative costs are backend noise, not information.
    return value if value == value and value >= 0 else None


def _analysis_scalar(program: Any, key: str) -> Optional[float]:
    return _scalar_from(cost_analysis_dict(program), key)


def cost_flops(program: Any) -> Optional[float]:
    """The executable's FLOP count per XLA's cost analysis, or None.
    For an SPMD executable this is the PER-DEVICE partitioned module's
    count (bench.py's long-standing convention — do not divide by the
    chip count again)."""
    return _analysis_scalar(program, "flops")


def cost_bytes(program: Any) -> Optional[float]:
    """Bytes accessed per XLA's cost analysis, or None."""
    return _analysis_scalar(program, "bytes accessed")


def memory_analysis_dict(compiled: Any) -> Dict[str, float]:
    """The compiled memory analysis as a plain dict (argument/output/
    temp/code bytes), or ``{}`` when the backend exposes none."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for name in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        value = getattr(mem, name, None)
        if isinstance(value, (int, float)):
            out[name] = float(value)
    return out


# -- the ledger ----------------------------------------------------------


@dataclass
class ProgramRecord:
    """One compiled program's ledger row."""

    kind: str
    key: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    lower_ms: Optional[float] = None
    compile_ms: Optional[float] = None
    memory: Dict[str, float] = field(default_factory=dict)
    attrs: Dict[str, Any] = field(default_factory=dict)
    #: Monotonic registration ordinal (process lifetime order).
    ordinal: int = 0
    #: Wall-clock registration time (time.time(); for /statusz only).
    recorded_at: float = 0.0
    dispatches: int = 0

    def as_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "kind": self.kind,
            "key": self.key,
            "ordinal": self.ordinal,
            "dispatches": self.dispatches,
        }
        if self.flops is not None:
            out["flops"] = self.flops
        if self.bytes_accessed is not None:
            out["bytes_accessed"] = self.bytes_accessed
        if self.lower_ms is not None:
            out["lower_ms"] = round(self.lower_ms, 3)
        if self.compile_ms is not None:
            out["compile_ms"] = round(self.compile_ms, 3)
        if self.memory:
            out["memory"] = dict(self.memory)
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        return out


class ProgramLedger:
    """Thread-safe, bounded record of every program this process
    compiled. Appends are cheap (compiles are rare by construction);
    readers snapshot under the lock. ``max_records`` bounds memory for
    pathological compile storms (the oldest rows are evicted — their
    counters survive in the registry totals)."""

    def __init__(self, max_records: int = 512, registry=None) -> None:
        self._lock = threading.Lock()
        self._records: List[ProgramRecord] = []
        self._max_records = int(max_records)
        self._ordinal = 0
        self._registry = registry

    def _reg(self):
        return self._registry if self._registry is not None else default_registry()

    def record(
        self,
        kind: str,
        key: str,
        *,
        lowered: Any = None,
        compiled: Any = None,
        lower_ms: Optional[float] = None,
        compile_ms: Optional[float] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> ProgramRecord:
        """Register one compiled program. FLOPs/bytes come from
        ``compiled`` when available (post-optimization numbers), else
        ``lowered``; memory analysis from ``compiled`` only. Never
        raises on analysis failure — the seam's compile must not."""
        source = compiled if compiled is not None else lowered
        # ONE cost pass per program: cost_analysis() re-runs XLA's HLO
        # cost analysis on every call, so extract both scalars from a
        # single invocation.
        analysis = cost_analysis_dict(source) if source is not None else {}
        rec = ProgramRecord(
            kind=str(kind),
            key=str(key),
            flops=_scalar_from(analysis, "flops"),
            bytes_accessed=_scalar_from(analysis, "bytes accessed"),
            lower_ms=lower_ms,
            compile_ms=compile_ms,
            memory=(
                memory_analysis_dict(compiled) if compiled is not None else {}
            ),
            attrs=dict(attrs or {}),
            recorded_at=time.time(),
        )
        with self._lock:
            self._ordinal += 1
            rec.ordinal = self._ordinal
            self._records.append(rec)
            if len(self._records) > self._max_records:
                del self._records[: len(self._records) - self._max_records]
        try:
            reg = self._reg()
            reg.counter(
                "zk_compiles_total",
                help="programs compiled (ledger-recorded), by seam kind",
                labels={"kind": rec.kind},
            ).inc()
            if compile_ms is not None:
                reg.counter(
                    "zk_compile_ms_total",
                    help="cumulative XLA compile wall time, by seam kind",
                    labels={"kind": rec.kind},
                ).inc(max(0.0, float(compile_ms)))
        except Exception:  # registry conflicts must not kill a compile
            pass
        if _trace.enabled():
            _trace.event(
                "program_compiled",
                attrs={
                    "kind": rec.kind,
                    "key": rec.key,
                    "compile_ms": (
                        round(compile_ms, 1) if compile_ms is not None else None
                    ),
                },
            )
        return rec

    def entries(self) -> List[ProgramRecord]:
        with self._lock:
            return list(self._records)

    def latest(
        self, kind: Optional[str] = None
    ) -> Optional[ProgramRecord]:
        """Newest record (of ``kind``, when given)."""
        with self._lock:
            for rec in reversed(self._records):
                if kind is None or rec.kind == kind:
                    return rec
        return None

    def total_compile_ms(self) -> float:
        with self._lock:
            return sum(r.compile_ms or 0.0 for r in self._records)

    def as_status(self) -> Dict[str, Any]:
        """The ``/statusz`` ledger section: per-program rows (newest
        first, capped) + totals."""
        with self._lock:
            records = list(self._records)
        return {
            "programs": [r.as_dict() for r in reversed(records)][:64],
            "count": len(records),
            "total_compile_ms": round(
                sum(r.compile_ms or 0.0 for r in records), 1
            ),
        }

    def clear(self) -> None:
        with self._lock:
            self._records.clear()


_DEFAULT = ProgramLedger()


def default_ledger() -> ProgramLedger:
    """The process-global ledger every seam records into (compiles are
    process-scarce events; one table is the point — ``/statusz``
    renders it whole)."""
    return _DEFAULT


# -- MFU gauge math ------------------------------------------------------


def mfu(
    flops: Optional[float],
    seconds: Optional[float],
    peak_flops: Optional[float],
) -> Optional[float]:
    """Model FLOPs utilization: ``flops / seconds / peak``. Returns
    None unless every input is a positive finite number — a gauge
    update must never raise, and a nonsense ratio (0-time, missing
    cost analysis) must render as "unknown" (the gauges publish -1),
    not as 0% or infinity."""
    try:
        flops, seconds, peak_flops = (
            float(flops),
            float(seconds),
            float(peak_flops),
        )
    except (TypeError, ValueError):
        return None
    if not (flops > 0 and seconds > 0 and peak_flops > 0):
        return None
    value = flops / seconds / peak_flops
    return value if value == value and value != float("inf") else None


def mbu(
    bytes_accessed: Optional[float],
    seconds: Optional[float],
    peak_bytes_per_sec: Optional[float],
) -> Optional[float]:
    """Memory-bandwidth utilization: ``bytes / seconds / bandwidth`` —
    the roofline lens for MEMORY-bound programs (decode_step reads the
    KV cache and weights every token; its MFU is meaninglessly low by
    construction). Same totality contract as :func:`mfu`: None unless
    every input is positive and finite, so the engine's ``decode_mbu``
    reads -1-unknown instead of raising or lying. NOTE the bytes side
    is XLA's STATIC cost analysis — with a length-aware kernel the true
    bytes read are lower, so the gauge is an upper bound
    (docs/DESIGN.md §17)."""
    return mfu(bytes_accessed, seconds, peak_bytes_per_sec)


# -- the compile-seam wrapper --------------------------------------------


def _signature(args) -> tuple:
    """Hashable (shape, dtype, sharding) signature of a call's
    arguments — the cache key deciding whether the AOT-compiled
    program fits. Sharding/placement is part of the signature because
    an AOT ``Compiled`` rejects re-placed arguments that a plain jit
    would silently reshard or retrace for."""
    import jax

    return tuple(
        (
            tuple(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", type(leaf).__name__)),
            str(getattr(leaf, "sharding", "")),
        )
        for leaf in jax.tree.leaves(args)
    )


class LedgeredExecutable:
    """Ledger-instrumented wrapper over a ``jax.jit`` callable.

    First call: ``lower()`` + ``compile()`` explicitly (both timed,
    recorded into the ledger with cost + memory analysis), then
    dispatch the compiled executable — the exact work the jit would
    have done lazily, now accounted. Steady state: one attribute read
    + one compiled dispatch per call (no signature recomputation — the
    overwhelmingly common case is a fixed-shape loop).

    A call whose arguments no longer match the compiled program (a
    partial final eval batch, a re-run at new shapes) raises from the
    compiled dispatch; the wrapper then falls back to the wrapped jit
    callable for that call and every future non-matching signature —
    identical behavior (and identical retrace cost) to the
    uninstrumented seam, minus ledger rows for the extra shapes.

    ``lower`` delegates to the wrapped jit (bench.py AOT-compiles
    through the seam itself); unknown attributes delegate too, so the
    wrapper is drop-in for callers that introspect the jitted object.
    """

    def __init__(
        self,
        jitted: Callable,
        *,
        kind: str,
        key: str,
        ledger: Optional[ProgramLedger] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        self._jitted = jitted
        self._kind = kind
        self._key = key
        self._ledger = ledger
        self._attrs = dict(attrs or {})
        self._compiled = None
        self._signature = None
        self.ledger_entry: Optional[ProgramRecord] = None

    @property
    def compiled(self):
        """The ``jax.stages.Compiled`` this wrapper dispatches (None
        before the first call): jax's own ``memory_analysis()`` /
        ``cost_analysis()`` of the very executable that runs."""
        return self._compiled

    def _ledger_obj(self) -> ProgramLedger:
        return self._ledger if self._ledger is not None else default_ledger()

    def _compile_first(self, args):
        import time as _time

        t0 = _time.perf_counter()
        lowered = self._jitted.lower(*args)
        t1 = _time.perf_counter()
        compiled = lowered.compile()
        t2 = _time.perf_counter()
        sig = _signature(args)
        entry = self._ledger_obj().record(
            self._kind,
            f"{self._key}/args{len(sig)}x{abs(hash(sig)) % 10**8:08d}",
            lowered=lowered,
            compiled=compiled,
            lower_ms=(t1 - t0) * 1e3,
            compile_ms=(t2 - t1) * 1e3,
            attrs=self._attrs,
        )
        self._signature = sig
        self.ledger_entry = entry
        self._compiled = compiled
        return compiled

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is None:
            compiled = self._compile_first(args)
            entry = self.ledger_entry
            entry.dispatches += 1
            return compiled(*args)
        entry = self.ledger_entry
        try:
            out = compiled(*args)
        except (TypeError, ValueError):
            # Aval/sharding signature mismatch (jax raises TypeError for
            # differing argument types, ValueError for sharding/device
            # mismatches) — dispatch through the plain jit, which
            # reshards/retraces exactly like the uninstrumented seam.
            # Compiled argument checks run BEFORE donation, so the
            # arguments are intact. A signature (shape + dtype +
            # sharding) that DOES match the compiled program cannot
            # reach here: the same error would re-raise identically
            # from the jit fallback anyway.
            if _signature(args) == self._signature:
                raise  # same signature — a real error, not a re-spec
            return self._jitted(*args)
        if entry is not None:
            entry.dispatches += 1
        return out

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def __getattr__(self, name):
        # Fallback for introspection (only consulted when the attribute
        # is not on the wrapper itself).
        return getattr(self._jitted, name)
