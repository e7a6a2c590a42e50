"""Unified observability: host-side span tracing, typed metrics, live
export.

The cross-cutting layer the north star's "production under heavy
traffic" claim requires (docs/DESIGN.md §13). Three modules, stdlib
only, all zero-cost until opted in:

- ``trace`` — ``span()``/``event()`` into a bounded ring buffer with a
  Chrome trace-event exporter: host phases (data wait, slab dispatch,
  metrics readback, checkpoint write, batcher coalescing, preemption
  drain) open in Perfetto alongside the ``jax.profiler`` device trace.
- ``registry`` — Counter/Gauge/Histogram instruments behind a typed
  name table; ``ServingMetrics`` and the background subsystems record
  into it.
- ``export`` — Prometheus text exposition + a stdlib HTTP
  ``/metrics``-``/statusz``-``/trace`` endpoint
  (``TrainingExperiment.metrics_port`` / ``ServingConfig.metrics_port``
  opt in).

The device-side half (docs/DESIGN.md §14) rides the same substrate:

- ``ledger`` — the process-global program ledger: every lower/compile
  seam records identity key, XLA cost-analysis FLOPs/bytes, compile
  wall time and compiled memory analysis; feeds the ``zk_serve_mfu``
  gauge and a ``/statusz`` section.
- ``watchdog`` — EWMA+MAD step-time anomaly detection over the
  slab/step/dispatch duration streams (``step_time_anomaly`` /
  ``recompile_detected`` events + counters).
- ``device`` — the ``zk-device-probe`` ``memory_stats()`` poller
  behind the live ``zk_hbm_*`` per-device gauges.
- ``peaks`` — the hardware peak anchors (datasheet tables + the
  measured-peak aggregation) shared with ``bench.py`` so live and
  offline MFU divide by the same roofline.

The request-scoped half (docs/DESIGN.md §16) joins the layers:

- ``requests`` — monotone rid minting + the bounded per-service
  ``RequestLog`` of terminal request summaries; rids tag trace records
  and render as Chrome flow events.
- ``recorder`` — the anomaly-triggered ``FlightRecorder``: watchdog
  anomalies, recompiles, worker crashes, NaN-halts, fault injections
  and manual ``POST /debugz`` dump a rate-limited, bounded-retention
  bundle (trace ring + exposition text + ledger + statusz +
  RequestLog tails + manifest).
"""

from zookeeper_tpu.observability import trace
from zookeeper_tpu.observability.device import (
    DeviceProbe,
    device_memory_stats,
    device_summary,
)
from zookeeper_tpu.observability.export import (
    ObservabilityServer,
    render_prometheus,
)
from zookeeper_tpu.observability.ledger import (
    LedgeredExecutable,
    ProgramLedger,
    cost_analysis_dict,
    cost_flops,
    default_ledger,
    mfu,
)
from zookeeper_tpu.observability.recorder import FlightRecorder
from zookeeper_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from zookeeper_tpu.observability.requests import RequestLog, next_rid
from zookeeper_tpu.observability.trace import (
    Tracer,
    event,
    export_chrome_trace,
    span,
    to_chrome_trace,
)
from zookeeper_tpu.observability.watchdog import StepTimeWatchdog

__all__ = [
    "Counter",
    "DeviceProbe",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "LedgeredExecutable",
    "MetricsRegistry",
    "ObservabilityServer",
    "ProgramLedger",
    "RequestLog",
    "StepTimeWatchdog",
    "Tracer",
    "cost_analysis_dict",
    "cost_flops",
    "default_ledger",
    "default_registry",
    "device_memory_stats",
    "device_summary",
    "event",
    "export_chrome_trace",
    "mfu",
    "next_rid",
    "render_prometheus",
    "span",
    "to_chrome_trace",
    "trace",
]
