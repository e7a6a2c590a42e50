"""The split prefill/decode scheduler (docs/DESIGN.md §22).

One :class:`~zookeeper_tpu.serving.decode.scheduler.DecodeScheduler`
loop, two engines. The inherited machinery — submit/shed/backpressure,
deadlines, rid minting, crash recovery, weight hot-swap staging —
carries over VERBATIM; only admission is re-expressed as two queues:

- **PrefillQueue** (the inherited ``_queue`` plus the prefill role's
  lane array): queued prompts ride bucketed prefill dispatches on the
  PREFILL engine, batched as wide as its ``prefill_buckets`` allow.
  The first token is delivered at prefill completion — TTFT is stamped
  HERE, so the handoff cost lands on token 2's inter-token gap, which
  is the disaggregation trade (wide prefill batching without decode
  jitter). A stream finished by its first token (EOS, ``max_new=1``,
  capacity) releases its lane and never transfers.
- **DecodeQueue** (the ``_parked`` deque of completed prefills): when
  a decode slot frees, the oldest handoff adopts destination pages
  (``PagePool.adopt_slot``), the :class:`~zookeeper_tpu.serving.disagg
  .transfer.PageTransfer` moves the prefill lane's pages across, and
  the stream continues through the UNCHANGED inherited decode loop —
  plain or speculative.

Refcount custody across the seam is atomic: destination pages are
adopted before the move, the source lane is released only after the
import lands, and every failure path (injected transfer failure,
prefill-role crash, close, deadline) unwinds whichever side it holds —
``leak_check() == 0`` on BOTH pools at every instant, pinned by the
chaos suite.

Chaos knobs (``resilience.faults``): ``fail_page_transfer`` fails the
next handoff's move (victim fails with
:class:`~zookeeper_tpu.serving.disagg.transfer.PageTransferError`,
everyone else unaffected); ``prefill_role_crash_at=N`` kills the
PREFILL role at the Nth handoff — its pool and lanes are lost
wholesale (reset, zero leaks by construction), every stream still on
the prefill side fails cleanly with partials readable, and the decode
role keeps serving its active slots.
"""

import logging
import time
from collections import deque
from typing import List, Optional, Tuple

import numpy as np

from zookeeper_tpu.core import component
from zookeeper_tpu.observability import recorder as _recorder
from zookeeper_tpu.observability import trace as _trace
from zookeeper_tpu.observability.requests import RequestLog
from zookeeper_tpu.serving.batcher import RejectedError, WorkerCrashedError
from zookeeper_tpu.serving.decode.scheduler import (
    DecodeScheduler,
    DecodeStream,
)
from zookeeper_tpu.serving.disagg.transfer import (
    PageTransfer,
    PageTransferError,
)

logger = logging.getLogger(__name__)

__all__ = ["DisaggScheduler"]

#: A parked handoff: (stream, prefill lane, first token, prompt tokens).
_Handoff = Tuple[DecodeStream, int, int, int]


@component
class DisaggScheduler(DecodeScheduler):
    """Disaggregated continuous batching over a prefill engine and a
    decode engine joined by a :class:`PageTransfer` (see module
    docstring). All :class:`DecodeScheduler` fields apply unchanged."""

    # -- wiring ----------------------------------------------------------

    def bind(
        self,
        prefill_engine,
        decode_engine,
        transfer: PageTransfer,
        metrics=None,
        request_log=None,
        speculative=None,
    ) -> "DisaggScheduler":
        """Bind both roles. The DECODE engine is the inherited
        ``_engine`` (slots, decode loop, capacity contracts inherit);
        the prefill engine contributes lanes and the wide prefill
        grid; ``transfer`` must be bound to exactly this (prefill,
        decode) pair."""
        prefill_engine._require_bound()
        decode_engine._require_bound()
        transfer._require_bound()
        if (
            transfer._src is not prefill_engine
            or transfer._dst is not decode_engine
        ):
            raise ValueError(
                "transfer is bound to a different engine pair; bind it "
                "as transfer.bind(prefill_engine, decode_engine)."
            )
        if prefill_engine.max_prompt < decode_engine.max_prompt:
            raise ValueError(
                f"prefill seq buckets top out at "
                f"{prefill_engine.max_prompt} tokens but the decode "
                f"role admits prompts up to {decode_engine.max_prompt} "
                "— widen the prefill engine's seq_buckets."
            )
        super().bind(
            decode_engine,
            metrics=metrics,
            request_log=(
                request_log
                if request_log is not None
                else RequestLog("disagg")
            ),
            speculative=speculative,
        )
        object.__setattr__(self, "_prefill_engine", prefill_engine)
        object.__setattr__(self, "_transfer", transfer)
        lanes = int(prefill_engine.slots)
        object.__setattr__(self, "_lane_stream", [None] * lanes)
        object.__setattr__(self, "_parked", deque())
        return self

    @property
    def prefill_engine(self):
        return getattr(self, "_prefill_engine", None)

    @property
    def transfer(self) -> Optional[PageTransfer]:
        return getattr(self, "_transfer", None)

    @property
    def parked(self) -> int:
        with self._lock:
            return len(self._parked)

    def _free_lane(self, lane: int) -> None:
        """Every lane retirement funnels here (the decode side's
        ``_free_slot`` twin): pages released, lane reusable. Caller
        holds ``_lock``."""
        self._lane_stream[lane] = None
        self._prefill_engine.release_slot(lane)

    # -- the split admission ---------------------------------------------

    def _admit(self) -> None:
        """One admission round: land parked handoffs first (frees
        lanes), refill prefill lanes from the queue, then land any
        handoff the fresh prefill round just parked — a single stream
        on an idle service reaches its decode slot within ONE scheduler
        iteration."""
        self._admit_decode()
        self._admit_prefill()
        self._admit_decode()

    def _admit_prefill(self) -> None:
        """PrefillQueue step: the base ``_admit`` re-expressed on the
        PREFILL engine's lanes. Identical discipline — reserve under
        ``_lock``, page-plan under ``_lock``, dispatch outside,
        identity-checked commit — but completion PARKS the stream as a
        handoff instead of entering the decode loop."""
        engine = self._prefill_engine
        while True:
            with self._lock:
                if self._swap_pending is not None or not self._queue:
                    return
                free = [
                    i for i, s in enumerate(self._lane_stream) if s is None
                ]
                if not free:
                    return
                group: List[DecodeStream] = []
                lanes: List[int] = []
                cap = min(len(free), max(engine._prefill_buckets))
                while self._queue and len(group) < cap:
                    stream = self._queue.popleft()
                    if stream.expired():
                        if stream._expire() and self._metrics is not None:
                            self._metrics.record_deadline_expired()
                        continue
                    group.append(stream)
                    lanes.append(free[len(group) - 1])
                if not group:
                    continue
                t0_ns = time.perf_counter_ns()
                for stream, lane in zip(group, lanes):
                    self._lane_stream[lane] = stream
                    stream._role = "prefill"
                    if stream._t_dispatch_ns is None:
                        stream._t_dispatch_ns = t0_ns
                    if _trace.enabled() and stream.rid is not None:
                        _trace.event(
                            "disagg_prefill_dispatch",
                            rid=stream.rid,
                            attrs={"lane": lane},
                        )
            # Page allocation on the PREFILL pool (same split as the
            # base: bookkeeping under _lock, CoW + prefill outside). An
            # exhausted-pool stream requeues at the head while anything
            # at all is in flight ANYWHERE (busy lanes, parked
            # handoffs, active decode slots all eventually free
            # prefill pages); with the whole pipeline idle it could
            # never run — shed.
            plans = []
            admitted: List[DecodeStream] = []
            admitted_lanes: List[int] = []
            with self._lock:
                overflow = []
                for stream, lane in zip(group, lanes):
                    if self._lane_stream[lane] is not stream:
                        continue  # failed by close()/crash already
                    plan = engine.admit_slot(lane, stream.prompt, copy=False)
                    if plan is None:
                        overflow.append((stream, lane))
                    else:
                        plans.append(plan)
                        admitted.append(stream)
                        admitted_lanes.append(lane)
                overflow_lanes = [l for _, l in overflow]
                others_active = (
                    any(
                        s is not None and i not in overflow_lanes
                        for i, s in enumerate(self._lane_stream)
                    )
                    or bool(admitted)
                    or bool(self._parked)
                    or any(s is not None for s in self._slot_stream)
                )
                for stream, lane in reversed(overflow):
                    self._lane_stream[lane] = None
                    if others_active:
                        self._queue.appendleft(stream)
                    else:
                        if self._metrics is not None:
                            self._metrics.record_rejected()
                        stream._fail(RejectedError(
                            "prefill KV page pool exhausted with "
                            "nothing in flight to wait for: the prompt "
                            "needs more pages than the prefill role's "
                            "pool_pages can ever free — raise it or "
                            "shorten the prompt."
                        ))
            if not admitted:
                if overflow:
                    return
                continue
            group, lanes = admitted, admitted_lanes
            for plan in plans:
                cow = plan.pop("cow", None)
                if cow is not None:
                    engine.copy_page(*cow)
            cold = [
                i for i, p in enumerate(plans)
                if not p.get("shared_tokens")
            ]
            warm = [
                i for i, p in enumerate(plans) if p.get("shared_tokens")
            ]
            t0 = time.perf_counter()
            first = np.zeros(len(group), np.int32)
            if cold:
                out = engine.prefill(
                    [group[i].prompt for i in cold],
                    [lanes[i] for i in cold],
                )
                for i, tok in zip(cold, out):
                    first[i] = tok
            if warm:
                out = engine.prefill_warm(
                    [group[i].prompt for i in warm],
                    [lanes[i] for i in warm],
                    [int(plans[i]["shared_tokens"]) for i in warm],
                )
                for i, tok in zip(warm, out):
                    first[i] = tok
            dt_ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                now = time.perf_counter()
                delivered = 0
                for stream, lane, token in zip(group, lanes, first):
                    if self._lane_stream[lane] is not stream:
                        continue  # failed by close()/crash mid-dispatch
                    stream.ttft_ms = (now - stream._t_submit) * 1e3
                    if self._metrics is not None:
                        self._metrics.record_ttft(stream.ttft_ms)
                    engine.insert_prefix(lane, stream.prompt)
                    token = int(token)
                    # First token delivered AT PREFILL: TTFT is the
                    # prefill role's number; the transfer rides token
                    # 2's gap (the §22 trade).
                    stream._deliver(token)
                    delivered += 1
                    prompt_len = int(stream.prompt.shape[0])
                    reason = None
                    if stream._eos is not None and token == stream._eos:
                        reason = "eos"
                    elif len(stream._tokens) >= stream._max_new:
                        reason = "length"
                    elif prompt_len + 1 >= self._engine.token_limit:
                        # Decode-role capacity: the sequence could
                        # never grow there (same truncate-at-exactly-
                        # token_limit contract as single-mesh).
                        reason = "capacity"
                    if reason is not None:
                        # Done at its first token: never parks, never
                        # transfers.
                        stream._finish(reason)
                        self._free_lane(lane)
                        if _trace.enabled() and stream.rid is not None:
                            _trace.event(
                                "decode_stream_finish",
                                rid=stream.rid,
                                attrs={
                                    "lane": lane,
                                    "reason": reason,
                                    "tokens": len(stream._tokens),
                                },
                            )
                    else:
                        stream._role = "transfer"
                        self._parked.append(
                            (stream, lane, token, prompt_len)
                        )
                        if _trace.enabled() and stream.rid is not None:
                            _trace.event(
                                "disagg_prefill_park",
                                rid=stream.rid,
                                attrs={
                                    "lane": lane,
                                    "parked": len(self._parked),
                                },
                            )
                if self._metrics is not None:
                    self._metrics.record_prefill(dt_ms, delivered)
                    self._metrics.record_first_tokens(delivered)

    def _admit_decode(self) -> None:
        """DecodeQueue step: land parked handoffs into free decode
        slots, oldest first. Per handoff: adopt destination pages
        under ``_lock``, run the chaos checks + page transfer OUTSIDE
        it (device work), commit with the identity check, and only
        then release the source lane — the atomic refcount handoff."""
        from zookeeper_tpu.resilience import faults

        engine = self._engine
        spec = getattr(self, "_speculative", None)
        while True:
            with self._lock:
                if self._swap_pending is not None or not self._parked:
                    return
                free = [
                    i for i, s in enumerate(self._slot_stream) if s is None
                ]
                if not free:
                    return
                stream, lane, token, prompt_len = self._parked.popleft()
                slot = free[0]
                n_pages = engine.page_pool.pages_for(prompt_len)
                pages = engine.page_pool.adopt_slot(slot, n_pages)
                if pages is None:
                    # Decode pool exhausted: wait parked (the prefill
                    # pages stay resident — nothing to redo) while any
                    # decode slot can still free pages; with the slot
                    # array idle it could never land — shed.
                    if any(s is not None for s in self._slot_stream):
                        self._parked.appendleft(
                            (stream, lane, token, prompt_len)
                        )
                        return
                    if self._metrics is not None:
                        self._metrics.record_rejected()
                    stream._fail(RejectedError(
                        "decode KV page pool exhausted with no active "
                        "streams to wait for: the handoff needs more "
                        "pages than the decode role's pool_pages can "
                        "ever free — raise it or shorten the prompt."
                    ))
                    self._free_lane(lane)
                    continue
                # Reserve the slot BEFORE the device work so close()/
                # crash can see (and fail) the stream mid-transfer.
                self._slot_stream[slot] = stream
                self._slot_lengths[slot] = prompt_len
                stream._slot = slot
                src_pages = [
                    int(p)
                    for p in self._prefill_engine.page_pool.table[
                        lane, :n_pages
                    ]
                ]
            plan = faults.active()
            if plan is not None and plan.take_prefill_role_crash():
                self._on_prefill_crash(stream, lane, slot)
                continue
            try:
                self._transfer.move(src_pages, pages, rid=stream.rid)
            except PageTransferError as e:
                # Victim-only failure: unwind the adopted destination
                # pages, release the source lane, fail the one stream.
                # Both pools leak-free; every other stream unaffected.
                with self._lock:
                    if self._slot_stream[slot] is stream:
                        self._slot_stream[slot] = None
                        engine.release_slot(slot)
                    if self._lane_stream[lane] is stream:
                        self._free_lane(lane)
                    stream._fail(e)
                continue
            if spec is not None:
                # At DECODE admission: the draft lives with the decode
                # role (the teacher's first token was already delivered
                # at the prefill role).
                self._seed_draft(spec, [stream], [slot])
            with self._lock:
                if self._slot_stream[slot] is not stream:
                    # Failed by close()/crash mid-transfer; its slot
                    # pages were released there. Drop the source lane
                    # reference if it is still ours.
                    if self._lane_stream[lane] is stream:
                        self._free_lane(lane)
                    continue
                # Import landed: the source side releases LAST, so at
                # no instant were the pages unowned.
                if self._lane_stream[lane] is stream:
                    self._free_lane(lane)
                stream._role = "decode"
                if spec is not None:
                    self._draft_lengths[slot] = prompt_len
                    self._draft_pending[slot] = []
                self._slot_tokens[slot] = int(token)
                if _trace.enabled() and stream.rid is not None:
                    _trace.event(
                        "disagg_decode_admit",
                        rid=stream.rid,
                        attrs={"slot": slot, "pages": n_pages},
                    )

    # -- failure shapes ---------------------------------------------------

    def _on_prefill_crash(
        self, stream: DecodeStream, lane: int, slot: int
    ) -> None:
        """The prefill ROLE died mid-handoff
        (``FaultPlan.prefill_role_crash_at``): its device state — pool,
        lanes, in-flight handoffs — is gone wholesale. Reset the
        prefill engine (zero leaks by construction), fail every stream
        still on the prefill side cleanly (partials readable), unwind
        the victim's adopted decode pages, and keep the decode role
        serving its active slots untouched."""
        with self._lock:
            wrapped = WorkerCrashedError(
                "prefill role crashed mid-handoff (FaultPlan."
                "prefill_role_crash_at); this stream was failed "
                "cleanly (partial tokens in tokens_so_far) — resubmit "
                "to prefill on the recovered role."
            )
            victims = [stream]
            for rec in self._parked:
                if all(rec[0] is not v for v in victims):
                    victims.append(rec[0])
            self._parked.clear()
            for i, s in enumerate(self._lane_stream):
                if s is not None and all(s is not v for v in victims):
                    victims.append(s)
                self._lane_stream[i] = None
            # The role's pool is lost with the role: reset rather than
            # release-by-release (the host allocator and device pool
            # come back empty and consistent — leak_check() == 0).
            self._prefill_engine._reset_cache()
            if self._slot_stream[slot] is stream:
                self._slot_stream[slot] = None
                self._engine.release_slot(slot)
            for v in victims:
                v._fail(wrapped)
            if self._metrics is not None:
                self._metrics.record_worker_restart()
            _trace.event(
                "disagg_prefill_role_crash",
                attrs={"failed_streams": len(victims)},
            )
        _recorder.notify(
            "disagg_prefill_role_crash",
            attrs={"failed_streams": len(victims)},
        )

    def _on_crash(self, error: BaseException) -> None:
        """Whole-scheduler crash: the prefill side's streams fail with
        the same wrapped error the base gives queue/slot streams, lanes
        release their pages, then the base cleanup runs."""
        with self._lock:
            victims: List[DecodeStream] = []
            for rec in getattr(self, "_parked", ()):
                victims.append(rec[0])
            if getattr(self, "_parked", None) is not None:
                self._parked.clear()
            for i, s in enumerate(getattr(self, "_lane_stream", ())):
                if s is not None and all(s is not v for v in victims):
                    victims.append(s)
                self._lane_stream[i] = None
                self._prefill_engine.release_slot(i)
            wrapped = WorkerCrashedError(
                f"DisaggScheduler crashed ({error!r}); this stream was "
                "failed cleanly (partial tokens in tokens_so_far) — "
                "resubmit to run on the restarted scheduler."
            )
            wrapped.__cause__ = error
            for v in victims:
                v._fail(wrapped)
        super()._on_crash(error)

    def close(self, drain: bool = False) -> None:
        if getattr(self, "_engine", None) is None:
            return
        if drain:
            try:
                self.drain()
            except Exception:
                pass  # per-stream errors already delivered
        err = RuntimeError(
            "DisaggScheduler closed with streams pending."
        )
        with self._lock:
            for rec in self._parked:
                rec[0]._fail(err)
            self._parked.clear()
            for i, s in enumerate(self._lane_stream):
                if s is not None:
                    s._fail(err)
                self._lane_stream[i] = None
                self._prefill_engine.release_slot(i)
        super().close(drain=False)

    # -- loop hooks -------------------------------------------------------

    def _has_work(self) -> bool:
        if super()._has_work():
            return True
        with self._lock:
            return bool(self._parked) or any(
                s is not None for s in self._lane_stream
            )

    def _expire_active(self) -> None:
        super()._expire_active()
        self._expire_parked()

    def _expire_parked(self) -> None:
        """Deadline sweep over the handoff queue (streams between the
        roles are as expirable as queued or active ones). Caller holds
        ``_lock`` (the ``_step_once`` sweep phase)."""
        now = time.perf_counter()
        if not any(rec[0].expired(now) for rec in self._parked):
            return
        kept = deque()
        for rec in self._parked:
            stream, lane = rec[0], rec[1]
            if stream.expired(now):
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()
                self._free_lane(lane)
            else:
                kept.append(rec)
        object.__setattr__(self, "_parked", kept)

    def _maybe_apply_swap(self) -> None:
        """One weight version per sequence, across BOTH roles: the swap
        waits for the queue/lanes/parked/slots pipeline to drain, then
        swaps the prefill engine (and drops its prefix cache — cached
        K/V belongs to the old weights) before the base applies the
        decode-role swap."""
        pending = getattr(self, "_swap_pending", None)
        if pending is None:
            return
        if self._parked or any(s is not None for s in self._lane_stream):
            return
        if any(s is not None for s in self._slot_stream):
            return
        params, model_state, _ = pending
        self._prefill_engine.swap_weights(params, model_state)
        self._prefill_engine.invalidate_prefix_cache()
        super()._maybe_apply_swap()

    # -- introspection ----------------------------------------------------

    def status(self) -> dict:
        """The single-mesh ``status()`` plus per-role sections: the
        decode numbers keep their inherited keys (dashboards reuse),
        ``prefill`` and ``transfer`` are the §22 additions."""
        out = super().status()
        pe = self._prefill_engine
        with self._lock:
            out["role_topology"] = "disagg"
            out["prefill"] = {
                "lanes": int(pe.slots),
                "busy_lanes": sum(
                    1 for s in self._lane_stream if s is not None
                ),
                "parked_handoffs": len(self._parked),
                "compiles": pe.compile_count,
                "recompiles_detected": pe.recompiles_detected,
                "kv_pool": pe.pool_status(),
            }
            out["transfer"] = self._transfer.status()
        return out
