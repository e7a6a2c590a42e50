"""Decode-path observability: TTFT, per-token latency, slot occupancy.

The decode analogue of :class:`~zookeeper_tpu.serving.metrics.\
ServingMetrics`, built the same way on the typed registry
(docs/DESIGN.md §13): every lifetime total is a Counter, every sampled
series feeds a bounded window (exact ``np.percentile`` snapshots) plus
a fixed-bucket Histogram (live ``/metrics`` scraping), recorders are
O(1) and thread-safe. Every instrument renders as ``zk_decode_*`` in
Prometheus text exposition — the CI scrape smoke asserts the whole
family.

The tracked quantities are the decode cost model's levers
(docs/DESIGN.md §15):

- ``zk_decode_ttft_ms`` — submit-to-first-token wall time (prefill
  queue wait + the bucketed prefill dispatch): the interactive-latency
  number, dominated by slot availability under load.
- ``zk_decode_token_ms`` — wall time of one decode dispatch (one token
  for EVERY active slot): the steady-state streaming rate; tokens/s =
  active_slots / token_ms.
- ``zk_decode_active_slots`` / ``zk_decode_slot_occupancy`` — how full
  the slot array runs; sustained occupancy 1.0 with queue depth > 0
  means the slot array, not the chip, is the bottleneck (add slots).
- ``zk_decode_kv_pages_in_use`` — pages the pool's allocator has
  handed out (active slots and prefix-cache-retained pages).

The speculative-decode family (docs/DESIGN.md §18) deliberately renders
under its own ``zk_spec_*`` prefix (the schedule spans two engines, not
just the decode path): ``zk_spec_draft_tokens_total`` /
``zk_spec_accepted_tokens_total`` lifetime counters (their ratio is the
acceptance rate — the one number that decides whether speculation
pays), the live ``zk_spec_acceptance_rate`` gauge, and the
``zk_spec_accept_length`` per-window histogram (how many of the ``k``
drafts each verify accepted: a mass at 0 means the draft disagrees with
the teacher; a mass at ``k`` means ``k`` could go higher).
"""

from collections import deque
from typing import Dict, Mapping, Optional

import numpy as np

from zookeeper_tpu.core import Field, component
from zookeeper_tpu.observability.registry import (
    DEFAULT_MS_BUCKETS,
    MetricsRegistry,
)
from zookeeper_tpu.serving.metrics import (
    _emit_snapshot,
    _get_or_build_obs,
    _observe_sample,
    _reset_obs,
    _window_series,
)

__all__ = ["DecodeMetrics"]

_PREFIX = "zk_decode_"

#: Lifetime counters, in ``totals`` reporting order.
_COUNTER_NAMES = (
    # Generated tokens delivered to streams (the throughput numerator).
    "tokens_total",
    "requests_total",
    # Prefill dispatches (slot admissions — continuous-batching refills
    # included; requests_total - slots at steady state ~= refills).
    "prefills_total",
    # Decode dispatches (each serves every active slot one token).
    "decode_steps_total",
    # The decode pipeline (docs/DESIGN.md §13): steps launched with the
    # step before them unread, and tokens decoded for a stream that had
    # ended by then (an EOS is seen one step late).
    "steps_in_flight_total",
    "tokens_dropped_total",
    # PR 4 admission-control family.
    "rejected_total",
    "deadline_expired_total",
    "worker_restarts_total",
    "weight_swaps_total",
)

#: Chunked-prefill counters (docs/DESIGN.md §25): registered under the
#: ``zk_prefill_`` prefix (the chunk schedule is an admission-side
#: concern, like ``zk_prefix_`` is the cache's); reported in ``totals``
#: after the decode family.
_CHUNK_COUNTER_NAMES = ("prefill_chunks_total",)

#: Speculative-decode counters: registered under the ``zk_spec_``
#: prefix (NOT ``zk_decode_``); reported in ``totals`` after the
#: decode family.
_SPEC_COUNTER_NAMES = (
    "spec_draft_tokens_total",
    "spec_accepted_tokens_total",
)

#: Disaggregated page-handoff counters (``zk_transfer_`` prefix);
#: reported in ``totals`` after the spec family.
_TRANSFER_COUNTER_NAMES = (
    "transfer_handoffs_total",
    "transfer_pages_total",
    "transfer_bytes",
)

#: Accept-length histogram buckets: counts of accepted drafts per
#: verify window (small ints, not milliseconds).
_SPEC_ACCEPT_BUCKETS = (0, 1, 2, 3, 4, 6, 8, 12, 16)


@component
class DecodeMetrics:
    """Bounded-window aggregator for decode samples (see module
    docstring); API shape mirrors ``ServingMetrics``."""

    #: Samples retained per series; percentiles reduce this window.
    window: int = Field(4096)

    # -- lazy state ------------------------------------------------------

    def _obs(self) -> dict:
        return _get_or_build_obs(self, self._build_obs)

    def _build_obs(self) -> dict:
        registry = MetricsRegistry()
        return {
            "registry": registry,
            "counters": {
                **{
                    name: registry.counter(
                        _PREFIX + name, help=f"lifetime decode {name}"
                    )
                    for name in _COUNTER_NAMES
                },
                "spec_draft_tokens_total": registry.counter(
                    "zk_spec_draft_tokens_total",
                    help="draft tokens proposed across all speculative "
                    "windows (k per slot per window)",
                ),
                "spec_accepted_tokens_total": registry.counter(
                    "zk_spec_accepted_tokens_total",
                    help="draft tokens the teacher verify accepted "
                    "(longest prefix match; ratio to proposed = "
                    "acceptance rate)",
                ),
                # Disaggregated page-handoff family (docs/DESIGN.md
                # §22): its own zk_transfer_ prefix like zk_spec_ —
                # the transfer spans two engines/roles, not just the
                # decode path. Registered unconditionally (zero-valued
                # under single-mesh serving) so the scrape surface is
                # stable across topologies.
                "transfer_pages_total": registry.counter(
                    "zk_transfer_pages_total",
                    help="KV pages moved prefill->decode across all "
                    "handoffs",
                ),
                "transfer_bytes": registry.counter(
                    "zk_transfer_bytes",
                    help="KV bytes moved prefill->decode (real page "
                    "bytes, padding lanes excluded)",
                ),
                "transfer_handoffs_total": registry.counter(
                    "zk_transfer_handoffs_total",
                    help="completed page handoffs (one per stream "
                    "admitted into a decode slot)",
                ),
                # Chunked-prefill family (docs/DESIGN.md §25):
                # registered unconditionally (zero-valued under
                # monolithic prefill) so the scrape surface is stable
                # across configs, like zk_transfer_.
                "prefill_chunks_total": registry.counter(
                    "zk_prefill_chunks_total",
                    help="prefill chunk lanes dispatched (one per slot "
                    "per chunk; a monolithic prefill counts zero)",
                ),
            },
            "gauges": {
                "active_slots": registry.gauge(
                    _PREFIX + "active_slots",
                    help="sequence slots currently decoding",
                ),
                "slot_occupancy": registry.gauge(
                    _PREFIX + "slot_occupancy",
                    help="active slots / total slots (1.0 = the slot "
                    "array is the bottleneck when the queue is nonempty)",
                ),
                "queue_depth": registry.gauge(
                    _PREFIX + "queue_depth",
                    help="requests waiting for a slot",
                ),
                "kv_pages_in_use": registry.gauge(
                    _PREFIX + "kv_pages_in_use",
                    help="KV pages holding live tokens across active "
                    "slots",
                ),
                "weights_step": registry.gauge(
                    _PREFIX + "serving_weights_step",
                    help="training step whose weights are live (-1 = "
                    "bind-time weights)",
                    initial=-1,
                ),
                "spec_acceptance_rate": registry.gauge(
                    "zk_spec_acceptance_rate",
                    help="lifetime accepted/proposed draft-token "
                    "fraction (-1 = no speculative window yet)",
                    initial=-1,
                ),
                # Page-pool family (docs/DESIGN.md §20): the pool
                # allocator's counts —
                # deliberately outside the zk_decode_ prefix like the
                # zk_spec_ family (the pool is engine state the
                # prefix cache and every slot share).
                "kv_pool_free_pages": registry.gauge(
                    "zk_kv_pool_free_pages",
                    help="free pages in the shared KV page pool (-1 = "
                    "no occupancy recorded yet)",
                    initial=-1,
                ),
                "prefix_cache_hit_rate": registry.gauge(
                    "zk_prefix_cache_hit_rate",
                    help="lifetime prompt-token fraction served from "
                    "prefix-cache-shared pages (-1 = no lookup yet or "
                    "prefix cache off)",
                    initial=-1,
                ),
            },
            "hist": {
                "transfer_ms": registry.histogram(
                    "zk_transfer_ms",
                    buckets=DEFAULT_MS_BUCKETS,
                    help="one page handoff: export gather + "
                    "device-to-device (or host-bounce) move + import "
                    "scatter",
                ),
                "ttft_ms": registry.histogram(
                    _PREFIX + "ttft_ms",
                    buckets=DEFAULT_MS_BUCKETS,
                    help="submit-to-first-token wall time",
                ),
                "token_ms": registry.histogram(
                    _PREFIX + "token_ms",
                    buckets=DEFAULT_MS_BUCKETS,
                    help="one decode dispatch (one token per active "
                    "slot)",
                ),
                "prefill_ms": registry.histogram(
                    _PREFIX + "prefill_ms",
                    buckets=DEFAULT_MS_BUCKETS,
                    help="one prefill dispatch (KV write + first token)",
                ),
                "spec_accept_length": registry.histogram(
                    "zk_spec_accept_length",
                    buckets=_SPEC_ACCEPT_BUCKETS,
                    help="accepted drafts per verify window per slot "
                    "(0..k; mass at k means raise k, mass at 0 means "
                    "the draft disagrees with the teacher)",
                ),
                "itl_ms": registry.histogram(
                    _PREFIX + "itl_ms",
                    buckets=DEFAULT_MS_BUCKETS,
                    help="inter-token latency: wall time between "
                    "consecutive delivered tokens of one stream — the "
                    "tail a decode-blocking monolithic prefill spikes "
                    "and chunked prefill flattens (docs/DESIGN.md §25)",
                ),
                "prefill_stall_ms": registry.histogram(
                    "zk_prefill_stall_ms",
                    buckets=DEFAULT_MS_BUCKETS,
                    help="per-request admission-to-first-token wall "
                    "time under chunked prefill: the decode-"
                    "interleaving wait a monolithic prefill trades "
                    "for blocked streams (the TTFT-vs-ITL tradeoff's "
                    "other half)",
                ),
            },
            "windows": {},
        }

    @property
    def registry(self) -> MetricsRegistry:
        """The typed instrument registry — attach to an
        ``ObservabilityServer`` to scrape every ``zk_decode_*`` series."""
        return self._obs()["registry"]

    def _series(self, name: str) -> deque:
        return _window_series(self._obs(), name, self.window)

    def _observe(self, name: str, value: float) -> None:
        _observe_sample(self._obs(), name, value, self.window)

    # -- recorders (called by DecodeScheduler) ---------------------------

    def record_ttft(self, ttft_ms: float) -> None:
        """A request's first token landed (prefill emission)."""
        self._observe("ttft_ms", ttft_ms)

    def record_prefill(self, prefill_ms: float, requests: int) -> None:
        obs = self._obs()
        self._observe("prefill_ms", prefill_ms)
        obs["counters"]["prefills_total"].inc()
        obs["counters"]["requests_total"].inc(int(requests))

    def record_decode_step(
        self,
        step_ms: Optional[float],
        tokens: int,
        *,
        in_flight: bool = False,
        dropped: int = 0,
    ) -> None:
        """One decode dispatch delivered ``tokens`` stream tokens.
        ``step_ms`` is None where no wall time is the step's alone
        (another program's readback came between its launch and its
        read): the step counts, the series takes no sample.
        ``in_flight``: it was launched with the step before it unread;
        ``dropped``: tokens it decoded for a stream that had ended
        (docs/DESIGN.md §13)."""
        obs = self._obs()
        if step_ms is not None:
            self._observe("token_ms", step_ms)
        obs["counters"]["decode_steps_total"].inc()
        obs["counters"]["tokens_total"].inc(int(tokens))
        obs["counters"]["steps_in_flight_total"].inc(int(in_flight))
        obs["counters"]["tokens_dropped_total"].inc(int(dropped))

    def record_first_tokens(self, n: int) -> None:
        """Prefill-emitted tokens count toward the stream total too."""
        self._obs()["counters"]["tokens_total"].inc(int(n))

    def record_itl(self, gap_ms: float) -> None:
        """One inter-token gap: wall time between a stream's previous
        delivered token and this one (docs/DESIGN.md §25) — the
        per-stream latency a decode-blocking prefill inflates."""
        self._observe("itl_ms", gap_ms)

    def record_prefill_chunks(
        self, chunks: int, dispatch_ms: float
    ) -> None:
        """One chunked-prefill dispatch served ``chunks`` lanes
        (docs/DESIGN.md §25): each lane is one slot's chunk; the
        dispatch wall time joins the prefill series (a chunk dispatch
        IS a prefill dispatch, just a bounded one)."""
        obs = self._obs()
        obs["counters"]["prefill_chunks_total"].inc(int(chunks))
        obs["counters"]["prefills_total"].inc()
        self._observe("prefill_ms", dispatch_ms)

    def record_prefill_finish(self, requests: int, stall_ms) -> None:
        """``requests`` streams' FINAL chunks landed: they are admitted
        requests now (the monolithic path counts these inside
        ``record_prefill``); each one's admission-to-first-token wall
        time feeds the stall series."""
        obs = self._obs()
        obs["counters"]["requests_total"].inc(int(requests))
        for ms in stall_ms:
            self._observe("prefill_stall_ms", float(ms))

    def record_occupancy(
        self, active: int, slots: int, queue_depth: int, kv_pages: int
    ) -> None:
        gauges = self._obs()["gauges"]
        gauges["active_slots"].set(int(active))
        gauges["slot_occupancy"].set(active / slots if slots else 0.0)
        gauges["queue_depth"].set(int(queue_depth))
        gauges["kv_pages_in_use"].set(int(kv_pages))

    def record_pool(self, free_pages: int, hit_rate: float) -> None:
        """Paged-KV pool vitals (docs/DESIGN.md §20): the allocator's
        real free-page count and the prefix cache's lifetime
        token-level hit rate, refreshed each scheduler iteration with
        the occupancy gauges."""
        gauges = self._obs()["gauges"]
        gauges["kv_pool_free_pages"].set(int(free_pages))
        gauges["prefix_cache_hit_rate"].set(float(hit_rate))

    def record_spec_window(
        self,
        proposed: int,
        accepted: int,
        accept_lengths,
        window_ms: float,
        delivered: int,
    ) -> None:
        """One speculative window committed (docs/DESIGN.md §18):
        ``proposed``/``accepted`` draft tokens across the window's
        slots, per-slot ``accept_lengths`` into the histogram, the
        window wall time into the decode token series (a window IS the
        spec path's decode dispatch unit), and ``delivered`` stream
        tokens into the throughput total."""
        obs = self._obs()
        obs["counters"]["spec_draft_tokens_total"].inc(int(proposed))
        obs["counters"]["spec_accepted_tokens_total"].inc(int(accepted))
        obs["counters"]["tokens_total"].inc(int(delivered))
        obs["counters"]["decode_steps_total"].inc()
        self._observe("token_ms", float(window_ms))
        for a in accept_lengths:
            obs["hist"]["spec_accept_length"].observe(float(a))
        total_p = obs["counters"]["spec_draft_tokens_total"].value
        total_a = obs["counters"]["spec_accepted_tokens_total"].value
        obs["gauges"]["spec_acceptance_rate"].set(
            total_a / total_p if total_p else -1.0
        )

    def record_transfer(
        self, pages: int, nbytes: int, transfer_ms: float
    ) -> None:
        """One completed page handoff (docs/DESIGN.md §22): ``pages``
        real pages / ``nbytes`` real bytes moved prefill->decode, wall
        time into the ``zk_transfer_ms`` histogram + window."""
        obs = self._obs()
        obs["counters"]["transfer_handoffs_total"].inc()
        obs["counters"]["transfer_pages_total"].inc(int(pages))
        obs["counters"]["transfer_bytes"].inc(int(nbytes))
        self._observe("transfer_ms", float(transfer_ms))

    def record_rejected(self) -> None:
        self._obs()["counters"]["rejected_total"].inc()

    def record_deadline_expired(self) -> None:
        self._obs()["counters"]["deadline_expired_total"].inc()

    def record_worker_restart(self) -> None:
        self._obs()["counters"]["worker_restarts_total"].inc()

    def record_weight_swap(self, step: Optional[int] = None) -> None:
        obs = self._obs()
        obs["counters"]["weight_swaps_total"].inc()
        if step is not None:
            obs["gauges"]["weights_step"].set(int(step))

    # -- reduction -------------------------------------------------------

    @property
    def weights_step(self) -> int:
        """The live-weights gauge as a plain int (-1 = bind-time
        weights); stamped onto RequestLog summaries."""
        return int(self._obs()["gauges"]["weights_step"].value)

    @property
    def totals(self) -> Dict[str, int]:
        obs = self._obs()
        return {
            name: int(obs["counters"][name].value)
            for name in (
                _COUNTER_NAMES
                + _CHUNK_COUNTER_NAMES
                + _SPEC_COUNTER_NAMES
                + _TRANSFER_COUNTER_NAMES
            )
        }

    def snapshot(self) -> Dict[str, float]:
        """Flat aggregate of the current windows + totals (absent
        series omitted — an idle engine emits only counters)."""
        windows = self._obs()["windows"]
        out: Dict[str, float] = {
            k: float(v) for k, v in self.totals.items()
        }
        proposed = out.get("spec_draft_tokens_total", 0.0)
        if proposed:
            out["spec_acceptance_rate"] = (
                out["spec_accepted_tokens_total"] / proposed
            )
        for name in (
            "ttft_ms",
            "token_ms",
            "prefill_ms",
            "transfer_ms",
            "itl_ms",
            "prefill_stall_ms",
        ):
            series = windows.get(name)
            if series:
                arr = np.asarray(series)
                out[f"{name[:-3]}_p50_ms"] = float(np.percentile(arr, 50))
                out[f"{name[:-3]}_p99_ms"] = float(np.percentile(arr, 99))
                out[f"{name[:-3]}_mean_ms"] = float(arr.mean())
        return out

    def emit(
        self, writer, step: int = 0, extra: Optional[Mapping[str, float]] = None
    ) -> Dict[str, float]:
        """Write the snapshot through a training-family MetricsWriter
        under the ``decode/`` prefix; returns the snapshot."""
        return _emit_snapshot(self, writer, step, extra, "decode")

    def reset(self) -> None:
        """Zero every series IN PLACE (instrument identity preserved —
        a live ``/metrics`` server keeps rendering; same contract as
        ``ServingMetrics.reset``)."""
        _reset_obs(self)
