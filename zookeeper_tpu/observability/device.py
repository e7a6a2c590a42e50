"""Device memory probe: live per-device HBM gauges.

``jax`` exposes per-device allocator statistics through
``Device.memory_stats()`` (bytes in use, peak bytes, limit) on the TPU
and GPU backends; nothing in the repo surfaced them, so "which process
/ which program owns the HBM" needed a manual profiler capture. The
probe publishes them as registry gauges a live ``/metrics`` scrape
reads:

- ``zk_hbm_bytes_in_use{device=N}`` — current allocator usage.
- ``zk_hbm_peak_bytes_in_use{device=N}`` — the high-water mark (what
  actually bounds batch/bucket sizing).
- ``zk_hbm_bytes_limit{device=N}`` — the per-device capacity.

Backends without allocator stats (CPU returns ``None``) publish the
documented ``-1`` sentinel instead of dropping the series — a
dashboard/CI assertion can always find the gauge, and ``-1 bytes`` is
unambiguous where a silent absence is not (the same convention as
``serving_weights_step``'s bind-time ``-1``).

``poll_once()`` is the deterministic unit (tests/CI); ``start()`` runs
it on a ``zk-device-probe`` daemon thread every ``interval_s``.
Polling reads allocator COUNTERS — no device computation, no sync, no
dispatch — so the probe's cost on the step path is zero by
construction; its host cost is a few microseconds per device per poll
(the bench's ``ZK_BENCH_OBS=1`` leg accounts it as part of the <= 2%
observability budget).
"""

import logging
import threading
from typing import Any, Dict, List, Optional

from zookeeper_tpu.observability.registry import (
    MetricsRegistry,
    default_registry,
)

__all__ = ["DeviceProbe", "device_memory_stats", "device_summary"]

logger = logging.getLogger(__name__)

#: The memory_stats keys published as gauges, in (stats key, gauge
#: suffix) pairs. Backends name them uniformly (PJRT convention).
_STAT_GAUGES = (
    ("bytes_in_use", "zk_hbm_bytes_in_use"),
    ("peak_bytes_in_use", "zk_hbm_peak_bytes_in_use"),
    ("bytes_limit", "zk_hbm_bytes_limit"),
)


def device_summary() -> Dict[str, Any]:
    """The devices this process computes on, as jax reports them
    (``platform``, ``device_kind``, device count). Every result line
    carries it, so a number taken on the CPU backend can never be read
    as a chip number."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_memory_stats() -> List[Dict[str, Any]]:
    """Best-effort ``memory_stats()`` for every local device: one dict
    per device (``{"device": i, "kind": ..., **stats}``); ``stats`` is
    empty when the backend exposes none. Never raises — a metrics
    poller must not be able to kill its host process."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return []
    out = []
    for i, dev in enumerate(devices):
        stats: Dict[str, Any] = {}
        try:
            raw = dev.memory_stats()
            if isinstance(raw, dict):
                stats = raw
        except Exception:
            stats = {}
        out.append(
            {
                "device": i,
                "kind": getattr(dev, "device_kind", "unknown"),
                **stats,
            }
        )
    return out


class DeviceProbe:
    """Poll per-device allocator stats into HBM gauges.

    ``registry`` defaults to the process-global one (HBM is a process
    asset with no per-component owner — the same rationale as the
    prefetch-occupancy gauge). Start/stop are idempotent;
    ``poll_once()`` works without a thread (the tier-1/CI mode)."""

    def __init__(
        self,
        interval_s: float = 10.0,
        registry: Optional[MetricsRegistry] = None,
    ) -> None:
        if interval_s <= 0:
            raise ValueError(f"interval_s={interval_s} must be > 0.")
        self._interval_s = float(interval_s)
        self._registry = (
            registry if registry is not None else default_registry()
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def poll_once(self) -> List[Dict[str, Any]]:
        """One poll: publish every device's gauges (``-1`` sentinel
        where the backend exposes no stats) and return the raw stats."""
        stats = device_memory_stats()
        for row in stats:
            labels = {"device": str(row["device"])}
            for stat_key, gauge_name in _STAT_GAUGES:
                value = row.get(stat_key)
                self._registry.gauge(
                    gauge_name,
                    help=f"per-device allocator {stat_key} "
                    "(-1 = backend exposes no memory stats)",
                    labels=labels,
                    initial=-1,
                ).set(float(value) if isinstance(value, (int, float)) else -1)
        return stats

    @property
    def alive(self) -> bool:
        thread = self._thread
        return thread is not None and thread.is_alive()

    def start(self) -> "DeviceProbe":
        if self.alive:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    self.poll_once()
                except Exception as e:  # pragma: no cover - defensive
                    logger.warning("device probe poll failed: %s", e)
                self._stop.wait(self._interval_s)

        self._thread = threading.Thread(
            target=loop, name="zk-device-probe", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        self._thread = None
        if thread is not None:
            thread.join(timeout=5)
