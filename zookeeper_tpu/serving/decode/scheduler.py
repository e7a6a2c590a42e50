"""Slot-refill continuous batching: the host loop over the two decode
programs.

The ``MicroBatcher`` coalesces independent forwards; autoregressive
streams need a different shape — a sequence OCCUPIES device state (its
KV slot) across many dispatches, so the scheduling unit is the SLOT,
not the request. The ``DecodeScheduler`` owns that loop:

1. **Admit**: free slots are refilled from the FIFO queue — a group of
   queued prompts rides one bucketed prefill dispatch, which writes
   their KV pages and emits each request's first token (the TTFT
   emission). A finished sequence's slot is refilled WITHOUT draining
   or recompiling anything: the decode program's shape is the full
   slot array, always.
2. **Decode**: one ``decode_step`` dispatch advances EVERY active slot
   one token; tokens stream into each request's
   :class:`DecodeStream` as they are read back. The plain path keeps
   ONE step unread (docs/DESIGN.md §13): an iteration launches step
   N+1, then reads and delivers step N, and the tokens that feed N+1
   stay on the device — the host's work between two steps runs while
   the device computes.
3. **Finish**: EOS, per-request ``max_new_tokens``, the engine's
   KV/positional capacity, or a deadline ends a stream and frees its
   slot for the next admit round. An end the host can count is known
   before the step is planned; an EOS is seen one step late (one token
   decoded and dropped).

Admission control is the PR 4 machinery re-expressed for streams:
``shed_above`` sheds with :class:`RejectedError` before enqueueing,
per-request deadlines fail with :class:`DeadlineExpiredError` — at
admission planning (never prefilled late) and mid-stream (a stream
never runs past its deadline; ``result()`` never blocks past it) —
and an injected or real crash of the scheduling loop fails every
queued AND in-flight stream cleanly with :class:`WorkerCrashedError`
(``FaultPlan.decode_worker_crash`` drives the leg deterministically),
restarting on the next ``submit()``.

Weight hot-swaps go through :meth:`request_swap`, which upholds the
one-weight-version-per-SEQUENCE contract the dispatch-atomic
``swap_weights`` alone cannot (a stream spans many dispatches): the
swap is deferred, admission pauses so the slot array drains naturally
(bounded by ``max_new_tokens``/deadlines), and the swap applies at the
first empty-slot-array boundary — every in-flight stream finishes
entirely on the weights it started with, every stream admitted after
the swap runs entirely on the new ones. Under speculation the staged
swap is of the TEACHER (the authoritative model): the draft is never
swapped mid-flight — a stale draft only lowers acceptance, never
correctness.

With a bound :class:`SpeculativeDecoding` the decode phase runs the
two-model schedule instead (docs/DESIGN.md §18): per iteration the
draft proposes ``k`` tokens per active slot (one width-2 catch-up
append + ``k - 1`` draft steps), ONE teacher ``decode_verify`` scores
all ``k + 1`` window positions, and greedy acceptance (longest prefix
match, plus the teacher's own token at the first mismatch) commits
1..k+1 tokens per slot — mixed accept lengths across slots are pure
host bookkeeping, no drain, no recompile. Rollback is by-length: a
rejected suffix's cache rows are simply never advanced over. Slots
within a window of their token limit fall back to plain ``decode_step``
iterations (the capacity-truncation contract is the plain path's,
verbatim), and every emitted token remains the teacher's argmax given
the committed prefix — speculative greedy output is certified
bit-identical to plain greedy decode.

Threading mirrors the batcher: ``synchronous=True`` (default) is
thread- and clock-free — the caller drives via ``drain()`` /
``result()`` (deterministic tier-1 mode; deadline tests use
``deadline_ms=0`` = expiry-by-construction); async mode runs the loop
on one ``zk-decode-scheduler`` daemon thread.
"""

import logging
import threading
import time
from collections import deque
from typing import Any, List, NamedTuple, Optional

import numpy as np

from zookeeper_tpu.core import Field, component
from zookeeper_tpu.observability import recorder as _recorder
from zookeeper_tpu.observability import trace as _trace
from zookeeper_tpu.observability.requests import RequestLog, next_rid
from zookeeper_tpu.serving.batcher import (
    DeadlineExpiredError,
    RejectedError,
    WorkerCrashedError,
    outcome_of,
)

logger = logging.getLogger(__name__)

__all__ = ["DecodeScheduler", "DecodeStream"]


class DecodeStream:
    """Handle for one generation request: tokens stream in as the
    scheduler produces them; ``result()`` yields the full generated
    array. Iterating the handle yields tokens incrementally (in
    synchronous mode iteration DRIVES the scheduler, like
    ``PendingResult.result`` drives the batcher)."""

    def __init__(
        self,
        scheduler: "DecodeScheduler",
        prompt: np.ndarray,
        max_new_tokens: int,
        deadline_at: Optional[float],
        eos_token: Optional[int],
        rid: Optional[int] = None,
    ) -> None:
        self._scheduler = scheduler
        self.prompt = prompt
        self._max_new = int(max_new_tokens)
        self._deadline_at = deadline_at
        self._eos = eos_token
        self._tokens: List[int] = []
        #: ``perf_counter_ns`` of each token's delivery, stamped by the
        #: delivering thread. Filled only while tracing is on
        #: (``observability.trace``), so it may be shorter than the
        #: token list; empty otherwise.
        self.token_times_ns: List[int] = []
        self._done = False
        self._error: Optional[BaseException] = None
        self._finish_reason: Optional[str] = None
        # Speculative accounting (docs/DESIGN.md §18): drafts proposed /
        # accepted for THIS stream, stamped into its RequestLog detail.
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._t_submit = time.perf_counter()
        #: Submit-to-first-token milliseconds (None until it lands).
        self.ttft_ms: Optional[float] = None
        # Prompt tokens served from the radix prefix cache at admission
        # (stamped by _admit from the page-pool plan; stays 0 for cold
        # admissions).
        self._shared_tokens = 0
        #: Request id minted at submit (docs/DESIGN.md §16); its trace
        #: records render as one Perfetto flow and its terminal summary
        #: lands in the scheduler's RequestLog.
        self.rid = rid
        self._t_dispatch_ns: Optional[int] = None
        self._slot: Optional[int] = None
        # Which serving role last dispatched this stream ("" until the
        # first dispatch): single-mesh scheduling stamps "decode"; the
        # disaggregated scheduler advances it prefill -> transfer ->
        # decode, and the terminal RequestLog summary records where
        # the stream ended (docs/DESIGN.md §22).
        self._role: str = ""
        # Completion races between the worker (finish), a crash handler
        # (fail) and the caller's deadline expiry: first wins.
        self._cond = threading.Condition()

    # -- state -----------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._done

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    @property
    def finish_reason(self) -> Optional[str]:
        """"eos" / "length" (max_new_tokens) / "capacity" (KV or
        positional limit) — None while streaming or on failure."""
        return self._finish_reason

    @property
    def shared_tokens(self) -> int:
        """Prompt tokens whose KV came warm from the radix prefix
        cache at admission (0 = cold admission) — the
        per-request observability hook behind the fleet router's
        affinity certification (docs/DESIGN.md §23)."""
        return self._shared_tokens

    @property
    def tokens_so_far(self) -> np.ndarray:
        """Generated tokens delivered so far (valid even for a stream
        that later failed on deadline/crash — partial output is real
        output)."""
        with self._cond:
            return np.asarray(self._tokens, np.int32)

    def expired(self, now: Optional[float] = None) -> bool:
        if self._deadline_at is None:
            return False
        return (
            time.perf_counter() if now is None else now
        ) >= self._deadline_at

    # -- scheduler-side transitions --------------------------------------

    def _deliver(self, token: int) -> None:
        with self._cond:
            if self._done:
                return
            self._tokens.append(int(token))
            if _trace.enabled():
                # One event per delivered token, on the thread that
                # delivers it: gaps between a request's tokens are read
                # from here, with no client polling in between.
                self.token_times_ns.append(time.perf_counter_ns())
                _trace.event("token_delivered", rid=self.rid)
            self._cond.notify_all()

    def _finish(self, reason: str) -> None:
        with self._cond:
            if self._done:
                return
            self._done = True
            self._finish_reason = reason
            self._cond.notify_all()
        # Outside the cond (first-transition-wins above guarantees
        # exactly one terminal record per stream). Streams that rode
        # the speculative schedule carry accepted/proposed in their
        # terminal summary (docs/DESIGN.md §18).
        detail = reason
        if self._spec_proposed:
            detail = (
                f"{reason} spec={self._spec_accepted}/{self._spec_proposed}"
            )
        self._scheduler._log_terminal(self, "ok", detail=detail)

    def _fail(self, error: BaseException) -> bool:
        with self._cond:
            if self._done:
                return False
            self._done = True
            self._error = error
            self._cond.notify_all()
        self._scheduler._log_terminal(
            self, outcome_of(error), detail=type(error).__name__
        )
        return True

    def _expire(self) -> bool:
        waited_ms = (time.perf_counter() - self._t_submit) * 1e3
        return self._fail(
            DeadlineExpiredError(
                f"generation deadline expired after {waited_ms:.1f}ms "
                f"({len(self._tokens)} of {self._max_new} tokens "
                "generated; partial output in tokens_so_far)"
            )
        )

    # -- caller side -----------------------------------------------------

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """The full generated token array. Synchronous mode drives the
        scheduler to completion; async mode blocks — but NEVER past the
        request's deadline (on expiry the stream fails with
        :class:`DeadlineExpiredError` even if the worker is stalled)."""
        if not self._done:
            self._scheduler._drive(self, timeout)
        if self._error is not None:
            raise self._error
        return np.asarray(self._tokens, np.int32)

    def __iter__(self):
        """Incremental token stream (generated tokens, in order)."""
        served = 0
        while True:
            with self._cond:
                available = len(self._tokens)
            while served < available:
                yield self._tokens[served]
                served += 1
            if self._done:
                if self._error is not None:
                    raise self._error
                with self._cond:
                    remaining = self._tokens[served:]
                yield from remaining
                return
            self._scheduler._advance(self)


class _UnreadStep(NamedTuple):
    """The decode step the scheduler has launched and not yet read:
    the engine's handle and the host's view the step was launched
    from, which its delivery is checked against."""

    #: What ``engine.decode`` returned: the step's tokens, read when
    #: converted (a :class:`~zookeeper_tpu.serving.decode.engine.\
    #: DecodeStep`).
    step: Any
    #: Launched with the step before it unread (the pipeline was full).
    behind: bool
    #: ``_slot_stream`` as it was: a slot whose stream has changed
    #: since decoded a token nobody owns.
    snapshot: list
    #: The slots that decoded.
    active: List[int]
    #: Cached rows per slot BEFORE the step (its ``lengths`` operand).
    lengths: np.ndarray
    #: The draft's rows and catch-up counts (speculation bound only).
    dlengths: Optional[np.ndarray]
    counts: Optional[np.ndarray]


@component
class DecodeScheduler:
    """Continuous-batching scheduler over a
    :class:`~zookeeper_tpu.serving.decode.engine.DecodeEngine` (see
    module docstring)."""

    #: Default generation budget per request (``submit`` overrides).
    max_new_tokens: int = Field(32)
    #: Default per-request deadline in ms (0 = none); ``submit``'s
    #: ``deadline_ms`` overrides. Expired requests fail with
    #: :class:`DeadlineExpiredError` — queued, mid-stream, and in
    #: ``result()`` (which never blocks past it).
    default_deadline_ms: float = Field(0.0)
    #: Load-shedding threshold in QUEUED REQUESTS (0 = off): a submit
    #: that would grow the wait queue past this raises
    #: :class:`RejectedError` instead of queueing — overload fails
    #: fast. An empty queue always admits one request.
    shed_above: int = Field(0)
    #: Backpressure bound on the wait queue (requests): synchronous
    #: mode drains the backlog inline, async mode blocks the submitter.
    max_queue: int = Field(4096)
    #: End-of-sequence token id (-1 = none); ``submit`` overrides.
    #: Generation stops WITH the EOS token delivered.
    eos_token: int = Field(-1)
    #: Thread- and clock-free deterministic mode (tier-1 default):
    #: the caller drives via drain()/result(). False = one
    #: ``zk-decode-scheduler`` daemon thread runs the loop.
    synchronous: bool = Field(True)
    #: Per-iteration token budget for the chunked-prefill planner
    #: (docs/DESIGN.md §25; active only when the engine's
    #: ``prefill_chunk_tokens`` is on): each iteration spends the
    #: budget FIRST on every active decode slot (one token each; a
    #: speculative window counts k + 1), then on pending prefill
    #: chunks — decode never waits behind a prompt. 0 (default) sizes
    #: it automatically to ``slots × window + prefill_chunk_tokens``
    #: (full decode occupancy plus one whole chunk per iteration). A
    #: smaller explicit budget squeezes prefill harder under decode
    #: load, down to a 1-token/iteration progress floor.
    token_budget: int = Field(0)

    # -- wiring ----------------------------------------------------------

    def bind(
        self,
        engine,
        metrics=None,
        request_log=None,
        speculative=None,
        guard=None,
    ) -> "DecodeScheduler":
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={self.max_new_tokens} must be >= 1 "
                "(prefill always emits one token)."
            )
        if self.shed_above < 0 or self.default_deadline_ms < 0:
            raise ValueError(
                f"shed_above={self.shed_above} and default_deadline_ms="
                f"{self.default_deadline_ms} must be >= 0 (0 disables)."
            )
        if self.max_queue < 1:
            raise ValueError(f"max_queue={self.max_queue} must be >= 1.")
        if self.token_budget < 0:
            raise ValueError(
                f"token_budget={self.token_budget} must be >= 0 "
                "(0 sizes the chunked-prefill budget automatically)."
            )
        engine._require_bound()
        object.__setattr__(self, "_engine", engine)
        object.__setattr__(self, "_metrics", metrics)
        # Per-service terminal-request ring (docs/DESIGN.md §16).
        object.__setattr__(
            self,
            "_request_log",
            request_log if request_log is not None else RequestLog("decode"),
        )
        if speculative is not None:
            speculative._require_bound()
            if speculative.engine is not engine:
                raise ValueError(
                    "speculative binding mirrors a different teacher "
                    "engine; bind the scheduler and the speculative "
                    "config to the SAME DecodeEngine."
                )
        object.__setattr__(self, "_speculative", speculative)
        # Optional OverloadGuard (docs/DESIGN.md §24): predicted-miss
        # admission + brown-out. _brownout_active is the scheduler's
        # APPLIED state — it only tracks guard.brownout_engaged at the
        # drain boundary (_maybe_apply_brownout), never mid-batch.
        object.__setattr__(self, "_guard", guard)
        object.__setattr__(self, "_brownout_active", False)
        n = int(engine.slots)
        object.__setattr__(self, "_queue", deque())
        object.__setattr__(self, "_slot_stream", [None] * n)
        object.__setattr__(self, "_slot_lengths", np.zeros(n, np.int64))
        object.__setattr__(self, "_slot_tokens", np.zeros(n, np.int32))
        # Draft-cache bookkeeping (speculative schedule): valid draft
        # KV rows per slot, plus the <=1 committed token the teacher
        # has cached but the draft has not yet consumed (the full-
        # acceptance catch-up — docs/DESIGN.md §18).
        object.__setattr__(self, "_draft_lengths", np.zeros(n, np.int64))
        object.__setattr__(self, "_draft_pending", [[] for _ in range(n)])
        # Chunked prefill (docs/DESIGN.md §25): slot -> {"pos": next
        # uncommitted prompt offset, "admit_t": perf_counter at
        # admission} while a prompt is mid-prefill. A slot in
        # _chunk_state owns pages + a stream but must NOT decode —
        # its KV prefix is still being appended chunk by chunk.
        chunked = int(engine.prefill_chunk_tokens) > 0
        object.__setattr__(self, "_chunked", chunked)
        object.__setattr__(self, "_chunk_state", {})
        # Wall-clock of each slot's most recent token delivery, for
        # the inter-token-latency histogram; 0 = no token emitted yet
        # for the current occupant.
        object.__setattr__(self, "_slot_last_emit", np.zeros(n, np.float64))
        object.__setattr__(self, "_lock", threading.RLock())
        # Serializes scheduler ITERATIONS (plan -> dispatch -> commit)
        # so ``_lock`` can be released across the device dispatches:
        # submit()/status() only ever wait on bookkeeping, never on a
        # prefill/decode wall time (the MicroBatcher dispatch-outside-
        # the-lock discipline).
        object.__setattr__(self, "_step_lock", threading.Lock())
        object.__setattr__(self, "_cv", threading.Condition())
        object.__setattr__(self, "_worker", None)
        object.__setattr__(self, "_stop", threading.Event())
        object.__setattr__(self, "_swap_pending", None)
        # Iterations run so far: the ``step`` every trace record of one
        # iteration shares (the scheduler's leaves and the engine's
        # dispatch spans, through the tracer's thread-local step).
        object.__setattr__(self, "_iteration", 0)
        # The one decode step launched and not yet read (None: the
        # pipeline is empty), and the running counts of what the
        # pipeline did: steps launched with the step before unread,
        # tokens decoded for a slot whose stream had gone.
        object.__setattr__(self, "_unread", None)
        object.__setattr__(self, "_pipeline", {"in_flight": 0, "dropped": 0})
        return self

    def _require_bound(self) -> None:
        if getattr(self, "_engine", None) is None:
            raise RuntimeError(
                "DecodeScheduler is not bound: call "
                "scheduler.bind(engine) before submit()."
            )

    @property
    def request_log(self) -> Optional[RequestLog]:
        """This scheduler's terminal-request ring (None before bind)."""
        return getattr(self, "_request_log", None)

    def _log_terminal(
        self, stream: "DecodeStream", outcome: str, detail: Optional[str]
    ) -> None:
        """One compact RequestLog summary per TERMINAL stream (called
        by the stream's first-wins finish/fail transition)."""
        log = getattr(self, "_request_log", None)
        if log is None or stream.rid is None:
            return
        if outcome != "ok" and _trace.enabled():
            # The ok path already marked its terminal record
            # (decode_stream_finish, rid-tagged); failed streams get
            # theirs here so every outcome's flow chain has a terminus.
            _trace.event(
                "decode_stream_fail",
                rid=stream.rid,
                attrs={"outcome": outcome, "detail": detail},
            )
        complete_ns = time.perf_counter_ns()
        log.append(
            stream.rid,
            outcome,
            enqueue_ns=int(stream._t_submit * 1e9),
            dispatch_ns=stream._t_dispatch_ns,
            complete_ns=complete_ns,
            tokens=len(stream._tokens),
            slot=stream._slot,
            weights_step=(
                self._metrics.weights_step
                if self._metrics is not None
                else None
            ),
            detail=detail,
            role=stream._role or None,
        )
        guard = getattr(self, "_guard", None)
        if (
            guard is not None
            and guard.enabled
            and outcome == "ok"
            and stream._t_dispatch_ns is not None
        ):
            # Feed the admission estimator from observed successes:
            # service = dispatch→complete per generated token, wait =
            # submit→dispatch. Failures are excluded — their timings
            # describe the failure mode, not the service rate.
            dispatch_ns = stream._t_dispatch_ns
            guard.observe_service(
                (complete_ns - dispatch_ns) / 1e6,
                max(1, len(stream._tokens)),
            )
            guard.observe_wait(
                (dispatch_ns - stream._t_submit * 1e9) / 1e6
            )

    # -- submission ------------------------------------------------------

    def _deadline_at(self, deadline_ms: Optional[float]) -> Optional[float]:
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms or None
        if deadline_ms is None:
            return None
        if deadline_ms < 0:
            raise ValueError(f"deadline_ms={deadline_ms} must be >= 0.")
        return time.perf_counter() + deadline_ms / 1e3

    def submit(
        self,
        prompt: Any,
        *,
        max_new_tokens: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        eos_token: Optional[int] = None,
        rid: Optional[int] = None,
    ) -> DecodeStream:
        """Enqueue one prompt (1-D int tokens); returns a
        :class:`DecodeStream`. ``deadline_ms=None`` falls back to the
        component default (0 = none) while an EXPLICIT ``0`` is
        already-expired (the deterministic clock-free chaos idiom).
        Raises :class:`RejectedError` without enqueueing past the shed
        threshold. ``rid`` adopts an EXTERNALLY-minted request id —
        the fleet router propagates its own so one request is
        traceable router → worker across process boundaries
        (docs/DESIGN.md §23); None mints locally as before."""
        self._require_bound()
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(
                f"prompt must be a non-empty 1-D int token array, got "
                f"shape {prompt.shape}."
            )
        engine = self._engine
        if prompt.shape[0] > engine.max_prompt:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens exceeds the "
                f"largest seq bucket {engine.max_prompt}; widen "
                "engine.seq_buckets."
            )
        if prompt.shape[0] >= engine.token_limit:
            # token_limit is the hard TOTAL (prompt + generated); a
            # prompt at or past it leaves no room to emit even the
            # first token within the truncate-at-EXACTLY-token_limit
            # contract (docs/DESIGN.md §15).
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens leaves no room to "
                f"generate within token_limit={engine.token_limit} "
                f"(min of KV capacity {engine.capacity} and positional "
                f"table {engine.position_cap}); shorten the prompt or "
                "raise kv_capacity / the model's max_seq_len."
            )
        new = int(
            max_new_tokens if max_new_tokens is not None
            else self.max_new_tokens
        )
        if new < 1:
            raise ValueError(f"max_new_tokens={new} must be >= 1.")
        eos = eos_token if eos_token is not None else (
            int(self.eos_token) if int(self.eos_token) >= 0 else None
        )
        # Minted before admission control, so shed streams are
        # traceable and RequestLog-recorded too (docs/DESIGN.md §16);
        # a router-minted rid is adopted instead (docs/DESIGN.md §23).
        rid = next_rid() if rid is None else int(rid)
        stream = DecodeStream(
            self,
            prompt,
            new,
            self._deadline_at(deadline_ms),
            eos,
            rid=rid,
        )
        with self._lock:
            if (
                self.shed_above > 0
                and self._queue
                and len(self._queue) + 1 > self.shed_above
            ):
                if self._metrics is not None:
                    self._metrics.record_rejected()
                if _trace.enabled():
                    _trace.event(
                        "decode_request_shed",
                        rid=rid,
                        attrs={"queue_depth": len(self._queue)},
                    )
                self._log_terminal(stream, "shed", detail="RejectedError")
                raise RejectedError(
                    f"decode queue at {len(self._queue)} requests; "
                    f"admitting one more would exceed shed_above="
                    f"{self.shed_above} — request shed (service "
                    "overloaded, retry with backoff)."
                )
            self._guard_check(stream, new)
            backpressure = len(self._queue) + 1 > self.max_queue
            if not backpressure:
                self._queue.append(stream)
                if _trace.enabled():
                    _trace.event(
                        "decode_request_enqueue",
                        rid=rid,
                        attrs={
                            "prompt_tokens": int(prompt.shape[0]),
                            "queue_depth": len(self._queue),
                        },
                    )
        if backpressure:
            if self.synchronous:
                self.drain()  # serve the backlog inline, then queue
                with self._lock:
                    self._queue.append(stream)
            else:
                while True:
                    with self._lock:
                        if len(self._queue) + 1 <= self.max_queue:
                            self._queue.append(stream)
                            break
                    if self._stop.is_set():
                        raise RuntimeError(
                            "DecodeScheduler closed while submit was "
                            "blocked on backpressure."
                        )
                    # Bounded cv wait, not a busy-poll: the scheduler
                    # notifies per iteration; the timeout re-checks
                    # _stop/worker death (no lost-wakeup hang).
                    with self._cv:
                        self._cv.wait(0.01)
        if not self.synchronous:
            self._ensure_worker()
            with self._cv:
                self._cv.notify_all()
        return stream

    def _guard_check(self, stream: DecodeStream, new: int) -> None:
        """Predicted-miss admission (docs/DESIGN.md §24): shed at
        submit when the guard's EWMA completion estimate says this
        stream cannot meet its deadline behind the CURRENT queue.
        Queued work is measured in tokens-still-owed (each queued
        stream's max_new budget), the unit the per-token service EWMA
        speaks. With chunked prefill on, each stream additionally
        owes its REMAINING prefill chunks — one budget unit per chunk
        dispatch, work the iteration planner schedules exactly like a
        decode token (docs/DESIGN.md §25). Monolithic prefill keeps
        the historical prefill-is-free posture. Caller holds the
        lock; same empty-queue invariant as the static check."""
        guard = getattr(self, "_guard", None)
        if guard is None or not guard.enabled:
            return
        from zookeeper_tpu.serving.guardrails import PredictedMissError

        deadline_ms = (
            (stream._deadline_at - time.perf_counter()) * 1e3
            if stream._deadline_at is not None
            else None
        )
        queued_tokens = sum(
            s._max_new + self._chunk_units(int(s.prompt.shape[0]))
            for s in self._queue
        )
        if getattr(self, "_chunked", False):
            # Mid-prefill slots still owe their uncommitted chunks.
            for slot, st in self._chunk_state.items():
                s = self._slot_stream[slot]
                if s is None:
                    continue
                queued_tokens += self._chunk_units(
                    int(s.prompt.shape[0]) - int(st["pos"])
                )
        ok, predicted = guard.admit(
            queued_units=queued_tokens,
            request_units=new + self._chunk_units(int(stream.prompt.shape[0])),
            deadline_ms=deadline_ms,
        )
        if ok:
            return
        if self._metrics is not None:
            self._metrics.record_rejected()
        if _trace.enabled():
            _trace.event(
                "decode_request_shed",
                rid=stream.rid,
                attrs={
                    "queue_depth": len(self._queue),
                    "reason": "predicted_miss",
                    "predicted_ms": round(predicted, 3),
                },
            )
        self._log_terminal(
            stream,
            "shed",
            detail=f"PredictedMissError predicted_ms={predicted:.1f}",
        )
        raise PredictedMissError(
            f"predicted completion in {predicted:.1f}ms exceeds the "
            f"{deadline_ms:.1f}ms deadline with {queued_tokens} tokens "
            "queued ahead — shed at admission rather than served late."
        )

    def _chunk_units(self, prompt_tokens: int) -> int:
        """Remaining-prefill work in admission-budget units: the
        number of chunk dispatches still owed for ``prompt_tokens``
        uncommitted prompt tokens (ceil-divide by the chunk size).
        0 when chunking is off — monolithic prefill keeps the
        historical prefill-is-free estimator posture so existing
        deployments see identical admission decisions."""
        if not getattr(self, "_chunked", False) or prompt_tokens <= 0:
            return 0
        cap = int(self._engine.prefill_chunk_tokens)
        return -(-int(prompt_tokens) // cap)

    def generate(self, prompt: Any, **kwargs) -> np.ndarray:
        """Submit + block for the full generation — the one-call API
        (``tokens = scheduler.generate(prompt, max_new_tokens=64)``)."""
        return self.submit(prompt, **kwargs).result()

    # -- weight hot-swap -------------------------------------------------

    def request_swap(
        self, params: Any, model_state: Any = None, *, step: Optional[int] = None
    ) -> None:
        """Stage a weight hot-swap that preserves the one-weight-
        version-per-sequence contract: validation runs HERE (config
        bugs surface at the call site), admission pauses, in-flight
        streams finish on the weights they started with, and the swap
        applies at the first empty-slot-array boundary — zero
        recompiles. A second request before the first applies REPLACES
        it (newest wins, like the async checkpointer's supersede)."""
        self._require_bound()
        self._engine.check_swap(params, model_state)
        with self._lock:
            object.__setattr__(
                self, "_swap_pending", (params, model_state, step)
            )
        if not self.synchronous:
            self._ensure_worker()
            with self._cv:
                self._cv.notify_all()

    @property
    def swap_pending(self) -> bool:
        return getattr(self, "_swap_pending", None) is not None

    def _maybe_apply_swap(self) -> None:
        pending = getattr(self, "_swap_pending", None)
        if pending is None:
            return
        if any(s is not None for s in self._slot_stream):
            return  # in-flight sequences keep their weight version
        # A step an EOS left behind may still be unread: nothing of it
        # is owned, but the engine swaps at rest.
        self._resolve_unread()
        params, model_state, step = pending
        self._engine.swap_weights(params, model_state)
        # Paged layout: cached prefix pages hold K/V computed under the
        # OLD weights — a warm hit after the swap would splice stale
        # state into a new-weights stream. Invalidated here, EXACTLY
        # once per applied swap (the staged-swap boundary is the only
        # place weights change under a bound scheduler).
        dropped = self._engine.invalidate_prefix_cache()
        object.__setattr__(self, "_swap_pending", None)
        _trace.event(
            "decode_weight_swap",
            step=step,
            attrs={"deferred": True, "prefix_nodes_dropped": dropped},
        )
        if self._metrics is not None:
            self._metrics.record_weight_swap(step)
        logger.info(
            "decode weights hot-swapped%s (slot array drained, no "
            "recompile)",
            f" to training step {step}" if step is not None else "",
        )

    def _maybe_apply_brownout(self) -> None:
        """Track the guard's brown-out intent at the SAME safe boundary
        as a staged weight swap: the state flips only when the slot
        array is empty, so no in-flight stream ever sees its token
        budget rewritten or its speculation config change mid-sequence
        (docs/DESIGN.md §24). Loudly logged both ways; auto-recovering
        — the guard disengages on its own once admissions stop
        predicting misses. Caller holds ``_lock``."""
        guard = getattr(self, "_guard", None)
        if guard is None or not guard.enabled:
            return
        want = bool(guard.brownout_engaged)
        if want == self._brownout_active:
            return
        if any(s is not None for s in self._slot_stream):
            return  # in-flight sequences finish under the old posture
        object.__setattr__(self, "_brownout_active", want)
        guard.record_brownout_applied(want)
        _trace.event(
            "decode_brownout",
            attrs={
                "engaged": want,
                "max_new_tokens_cap": int(guard.brownout_max_new_tokens),
            },
        )
        if want:
            logger.warning(
                "BROWN-OUT ENGAGED: decode degrading — max_new_tokens "
                "capped at %d, speculation disabled for newly admitted "
                "streams (sustained predicted-miss pressure; "
                "auto-recovers when admissions stop shedding).",
                int(guard.brownout_max_new_tokens),
            )
        else:
            logger.warning(
                "brown-out released: decode back to full token budgets "
                "and speculation."
            )

    # -- the scheduling loop ---------------------------------------------

    def _has_work(self) -> bool:
        """Streams queued or in slots, or a decode step launched and
        not yet read (a caller that stops driving leaves it so)."""
        with self._lock:
            return (
                self._unread is not None
                or bool(self._queue)
                or any(s is not None for s in self._slot_stream)
            )

    def _free_slot(self, slot: int) -> None:
        self._slot_stream[slot] = None
        self._chunk_state.pop(slot, None)
        # Drop the slot's page references (prefix-cache-shared pages
        # stay resident), and the draft's share with them. Every slot
        # retirement path funnels here so pages can never leak.
        self._engine.release_slot(slot)
        spec = getattr(self, "_speculative", None)
        if spec is not None:
            spec.draft_engine.release_slot(slot)

    def _seed_draft(self, spec, streams, slots) -> None:
        """Seed the DRAFT cache with the prompts of ``streams`` (its
        first-token output is discarded — the teacher's is
        authoritative and already delivered). One extra dispatch per
        admission, amortized over the stream. Always the cold
        monolithic prefill, and each slot takes its FULL share of the
        draft's worst-case pool here and keeps it until
        :meth:`_free_slot`: no draft dispatch can ever wait on a page
        (pooling a private, correctness-irrelevant cache buys nothing).
        A stream failed by close()/crash meanwhile takes nothing."""
        pool = spec.draft_engine.page_pool
        with self._lock:
            live = [
                i for i, (stream, slot) in enumerate(zip(streams, slots))
                if self._slot_stream[slot] is stream
            ]
            for i in live:
                pool.adopt_slot(slots[i], pool.max_pages_per_slot)
        if live:
            spec.draft_engine.prefill(
                [streams[i].prompt for i in live], [slots[i] for i in live]
            )

    def _finish_or_continue(
        self, slot: int, token: int, rows: Optional[int] = None
    ) -> None:
        """Deliver ``token`` to the slot's stream and retire the slot
        when the stream is complete. ``rows``: the slot's cached rows
        once the dispatch that emitted ``token`` has run; the slot's
        current length unless a later step has advanced it already.
        Caller holds the lock."""
        if rows is None:
            rows = int(self._slot_lengths[slot])
        stream = self._slot_stream[slot]
        now = time.perf_counter()
        last = float(self._slot_last_emit[slot])
        if last > 0.0 and self._metrics is not None:
            # Inter-token gap as the CLIENT sees it: previous delivery
            # to this one. Speculative windows deliver their accepted
            # run back-to-back (near-zero gaps) — accurate, the tokens
            # really do arrive together.
            self._metrics.record_itl((now - last) * 1e3)
        self._slot_last_emit[slot] = now
        stream._deliver(token)
        reason = None
        if stream._eos is not None and token == stream._eos:
            reason = "eos"
        elif len(stream._tokens) >= stream._max_new:
            reason = "length"
        elif rows + 1 >= self._engine.token_limit:
            # The sequence now totals token_limit tokens (cached
            # lengths + the token just delivered): feeding the delivered
            # token back would write past the KV capacity or the
            # positional table. Truncate at EXACTLY token_limit, so
            # every delivered token is full-context-oracle-verifiable.
            reason = "capacity"
        if reason is not None:
            stream._finish(reason)
            self._free_slot(slot)
            if _trace.enabled():
                _trace.event(
                    "decode_stream_finish",
                    rid=stream.rid,
                    attrs={
                        "slot": slot,
                        "reason": reason,
                        "tokens": len(stream._tokens),
                    },
                )

    def _expire_queued(self) -> None:
        now = time.perf_counter()
        if not any(s.expired(now) for s in self._queue):
            return
        kept = deque()
        for stream in self._queue:
            if stream.expired(now):
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()
            else:
                kept.append(stream)
        object.__setattr__(self, "_queue", kept)

    def _expire_active(self) -> None:
        now = time.perf_counter()
        for slot, stream in enumerate(self._slot_stream):
            if stream is not None and stream.expired(now):
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()
                self._free_slot(slot)

    def _admit(self) -> int:
        """Refill free slots from the queue head: one bucketed prefill
        dispatch per admitted group. Paused while a weight swap is
        pending (the drain that makes the swap safe). Caller holds
        ``_step_lock``; ``_lock`` is taken per phase so the prefill
        dispatch itself runs unlocked — admitted streams are RESERVED
        into the slot array first, so ``close()``/``_on_crash`` see
        (and can fail) them mid-dispatch. Returns the streams
        admitted.

        Trace leaves (docs: ``observability.trace``): everything up to
        a group's dispatch is ``sched_admit_plan``, everything after it
        ``sched_admit_commit``; the engine's dispatch spans lie between
        and are enclosed by neither."""
        engine = self._engine
        admitted_total = 0
        while True:
            with _trace.span("sched_admit_plan"):
                with self._lock:
                    if self._swap_pending is not None or not self._queue:
                        return admitted_total
                    free = [
                        i for i, s in enumerate(self._slot_stream) if s is None
                    ]
                    if not free:
                        return admitted_total
                    group: List[DecodeStream] = []
                    slots: List[int] = []
                    cap = min(len(free), max(engine._prefill_buckets))
                    while self._queue and len(group) < cap:
                        stream = self._queue.popleft()
                        if stream.expired():
                            if stream._expire() and self._metrics is not None:
                                self._metrics.record_deadline_expired()
                            continue
                        if self._brownout_active:
                            # Brown-out: every stream admitted while
                            # engaged gets a capped token budget. Applied
                            # at ADMISSION only — in-flight budgets are
                            # never rewritten (docs/DESIGN.md §24).
                            stream._max_new = min(
                                stream._max_new,
                                int(self._guard.brownout_max_new_tokens),
                            )
                        group.append(stream)
                        slots.append(free[len(group) - 1])
                    if not group:
                        continue
                    t0_ns = time.perf_counter_ns()
                    for stream, slot in zip(group, slots):
                        self._slot_stream[slot] = stream
                        self._slot_lengths[slot] = int(stream.prompt.shape[0])
                        self._slot_last_emit[slot] = 0.0
                        # Dispatch attribution BEFORE the device work (a
                        # crash mid-prefill still shows the stream reached
                        # dispatch), rid-tagged so the exporter links the
                        # submit event to this slot's prefill.
                        stream._slot = slot
                        stream._role = "decode"
                        if stream._t_dispatch_ns is None:
                            stream._t_dispatch_ns = t0_ns
                        if _trace.enabled() and stream.rid is not None:
                            _trace.event(
                                "decode_request_dispatch",
                                rid=stream.rid,
                                attrs={"slot": slot},
                            )
                # Page allocation per admitted stream (docs/DESIGN.md
                # §20). The POOL bookkeeping
                # runs under _lock (close()/crash release pages under the
                # same lock — the PagePool is lock-guarded scheduler
                # state); only the rare one-page CoW copy dispatches
                # outside, like the prefill itself. A pool-exhausted
                # stream is put back at the QUEUE HEAD (its slot
                # reservation undone) — it admits as soon as finishing
                # streams release pages; if the pool cannot serve it even
                # with every slot idle and the prefix cache evicted, it is
                # shed with RejectedError (it could never run).
                plans = []
                admitted: List[DecodeStream] = []
                admitted_slots: List[int] = []
                with self._lock:
                    overflow = []
                    for stream, slot in zip(group, slots):
                        if self._slot_stream[slot] is not stream:
                            continue  # failed by close()/crash already
                        plan = engine.admit_slot(
                            slot, stream.prompt, copy=False
                        )
                        if plan is None:
                            overflow.append((stream, slot))
                        else:
                            stream._shared_tokens = int(
                                plan.get("shared_tokens") or 0
                            )
                            plans.append(plan)
                            admitted.append(stream)
                            admitted_slots.append(slot)
                    others_active = any(
                        s is not None
                        and i not in [sl for _, sl in overflow]
                        for i, s in enumerate(self._slot_stream)
                    ) or bool(admitted)
                    for stream, slot in reversed(overflow):
                        self._slot_stream[slot] = None
                        if others_active:
                            # Pages free as streams finish: requeue.
                            self._queue.appendleft(stream)
                        else:
                            # Nothing in flight and the pool still cannot
                            # hold this prompt: unservable.
                            if self._metrics is not None:
                                self._metrics.record_rejected()
                            stream._fail(RejectedError(
                                "KV page pool exhausted with no active "
                                "streams to wait for: the prompt needs "
                                "more pages than pool_pages can ever free "
                                "— raise engine.pool_pages or shorten the "
                                "prompt."
                            ))
                if not admitted:
                    if overflow:
                        return admitted_total
                    continue
                group, slots = admitted, admitted_slots
                # CoW copies outside the lock (device work). A page whose
                # stream was failed mid-loop just writes bytes into a
                # released page — unreferenced, overwritten or masked by
                # any future tenant (the validity invariant).
                for plan in plans:
                    cow = plan.pop("cow", None)
                    if cow is not None:
                        engine.copy_page(*cow)
                if getattr(self, "_chunked", False):
                    # Chunked admission (docs/DESIGN.md §25): pages are
                    # allocated and any warm prefix is already committed
                    # (CoW done above), but NO prefill dispatches here —
                    # the token-budget planner (_prefill_chunks) appends
                    # the prompt chunk by chunk, interleaved with decode
                    # iterations, and TTFT is stamped on the FINAL chunk.
                    # Warm hits start their cursor past the cached prefix,
                    # so fully-warm prompts cost a single 1-token chunk.
                    with self._lock:
                        now = time.perf_counter()
                        for stream, slot, plan in zip(group, slots, plans):
                            if self._slot_stream[slot] is not stream:
                                continue  # failed by close()/crash already
                            shared = int(plan.get("shared_tokens") or 0)
                            # While mid-prefill, _slot_lengths tracks the
                            # COMMITTED prefix (the chunk cursor), not the
                            # final prompt length.
                            self._slot_lengths[slot] = shared
                            self._chunk_state[slot] = {
                                "pos": shared,
                                "admit_t": now,
                            }
                            admitted_total += 1
                    continue
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
                    [slots[i] for i in cold],
                )
                for i, tok in zip(cold, out):
                    first[i] = tok
            if warm:
                # Warm-prefix admission: only the suffixes ride the
                # device (the shared pages are already resident) —
                # the TTFT collapse the prefix cache exists for.
                out = engine.prefill_warm(
                    [group[i].prompt for i in warm],
                    [slots[i] for i in warm],
                    [int(plans[i]["shared_tokens"]) for i in warm],
                )
                for i, tok in zip(warm, out):
                    first[i] = tok
            spec = getattr(self, "_speculative", None)
            if spec is not None:
                self._seed_draft(spec, group, slots)
            dt_ms = (time.perf_counter() - t0) * 1e3
            with _trace.span("sched_admit_commit"), self._lock:
                now = time.perf_counter()
                delivered = 0
                for stream, slot, token in zip(group, slots, first):
                    if self._slot_stream[slot] is not stream:
                        continue  # failed by close()/crash mid-dispatch
                    stream.ttft_ms = (now - stream._t_submit) * 1e3
                    if self._metrics is not None:
                        self._metrics.record_ttft(stream.ttft_ms)
                    if spec is not None:
                        # Both caches hold exactly the prompt now.
                        self._draft_lengths[slot] = int(
                            stream.prompt.shape[0]
                        )
                        self._draft_pending[slot] = []
                    # Cache the prompt's pages for future warm hits
                    # while the slot still references them.
                    engine.insert_prefix(slot, stream.prompt)
                    self._slot_tokens[slot] = int(token)
                    self._finish_or_continue(slot, int(token))
                    delivered += 1
                admitted_total += delivered
                if self._metrics is not None:
                    # Count tokens/requests actually DELIVERED (a
                    # stream failed mid-dispatch got no token) — the
                    # dispatch itself still counts once.
                    self._metrics.record_prefill(dt_ms, delivered)
                    self._metrics.record_first_tokens(delivered)

    def _decode(self) -> int:
        """Launch one decode step over the whole slot array, then read
        and deliver the step BEFORE it: the plain path keeps exactly
        one step unread (docs/DESIGN.md §13), so delivery, the sweeps,
        admission planning and the next plan run while the device
        computes, and the step after is queued before this one ends.

        The order of one call, with step N unread on entry:

        1. Plan N+1 from the host's state with N's effects applied
           ahead of its readback: each slot that decoded in N is one
           row longer already (``_slot_lengths`` advances at LAUNCH),
           and its input token is N's output, which stays on the device
           (``_slot_tokens`` -1: ``decode_fn`` keeps the device's). The
           host supplies a token only for a slot a prefill has just
           filled or when nothing was launched since the last read.
        2. Enqueue N+1 (``engine.decode``, which returns it unread);
           its ``decode_dispatch`` span waits for N after the boundary
           event.
        3. Deliver N (``sched_deliver``, ``_finish_or_continue``).

        A stream's end the host can count (its budget, ``token_limit``)
        is known when N+1 is planned: the slot sits N+1 out, no step is
        wasted (:meth:`_ending_unread`). An end only the token tells —
        EOS; also a deadline, ``close()``, a crash — is seen ONE STEP
        LATE: the slot decoded once more in N+1, that token is dropped
        when N+1 is read (counted: ``status()["decode_pipeline"]``,
        ``dropped`` on ``sched_iteration_end``), and its K/V row or
        state update lands in pages or a state block the slot owned at
        launch. A freed slot's pages and state are safe in the next
        occupant's hands because the device runs dispatches in the
        order they were enqueued: the occupant's prefill comes after
        the late step, and overwrites or masks (``j >= length``) what
        it wrote.

        With nothing to launch (every active stream completes in N, or
        none is left) N is read in a ``decode_readback`` leaf of its
        own and the pipeline is empty again. Who else reads the unread
        step, because they must see the engine quiet:
        :meth:`_maybe_apply_swap` and :meth:`close`
        (:meth:`_resolve_unread`); ``drain()`` and a synchronous
        caller's ``result()`` by driving until ``_has_work()`` is
        false, which an unread step keeps true; :meth:`_on_crash`
        discards it with the streams it fails.

        Returns the iteration's decode token SPEND for the
        chunked-prefill budget (one per slot launched; a speculative
        window counts k + 1 — docs/DESIGN.md §25). Mid-prefill slots
        (in ``_chunk_state``) are excluded from the active set: their
        streams own pages but must not emit tokens, and the batched
        dispatch's garbage write at their cursor row is overwritten by
        the chunk that commits that position later. Caller holds
        ``_step_lock``; the dispatch runs outside ``_lock`` over a
        snapshot of the slot arrays.

        With speculation bound the host must see every token to choose
        the next window, so nothing stays unread: the two-model window
        schedule (:meth:`_decode_spec`) runs instead — unless any
        active slot is within one window of its token limit, in which
        case THIS iteration falls back to the plain step, read at once
        (a clamped multi-token append would land on live rows; the
        plain path's truncate-at-exactly-token_limit contract takes
        over, and the slot finishes within a few iterations). Which of
        the two it is follows from what is bound, not from an option."""
        spec = getattr(self, "_speculative", None)
        if spec is not None:
            with _trace.span("sched_decode_plan"), self._lock:
                active = [
                    i for i, s in enumerate(self._slot_stream)
                    if s is not None and i not in self._chunk_state
                ]
                eligible = (
                    bool(active)
                    # Brown-out skips the speculative window but keeps
                    # ``spec`` bound below: the plain path's width-2
                    # draft catch-up still runs, so the draft KV cache
                    # stays in sync and speculation resumes cleanly
                    # when the brown-out releases (docs/DESIGN.md §24).
                    and not self._brownout_active
                    and all(
                        int(self._slot_lengths[i]) + spec.window
                        <= self._engine.token_limit
                        for i in active
                    )
                )
            if not active:
                return 0
            if eligible:
                return self._decode_spec(spec)
        engine = self._engine
        unread = self._unread
        with _trace.span("sched_decode_plan"), self._lock:
            # The unread step's effects, ahead of its readback: its
            # slots are one row longer already (advanced when it was
            # launched), and a stream it completes by the host's own
            # count sits this step out.
            ending = self._ending_unread(unread)
            self._ensure_active_rows(1, ending)
            snapshot = list(self._slot_stream)
            active = [
                i for i, s in enumerate(snapshot)
                if s is not None
                and i not in self._chunk_state
                and i not in ending
            ]
            counts = None
            if active:
                tokens = self._slot_tokens.astype(np.int32)
                lengths = self._slot_lengths.astype(np.int32)
                if spec is not None:
                    dlengths = self._slot_draft_state()
                    ctokens, counts = self._draft_catchup_window(
                        active, tokens
                    )
                for slot in active:
                    # From here on the slot's input token is this
                    # step's output, on the device (-1: keep it).
                    self._slot_lengths[slot] += 1
                    self._slot_tokens[slot] = -1
        if not active and unread is None:
            return 0
        launched = None
        if active:
            # Inside its span the engine waits for ``unread``, the step
            # before, which computes no longer than this launch took.
            launched = _UnreadStep(
                engine.decode(tokens, lengths), unread is not None,
                snapshot, active, lengths,
                dlengths if spec is not None else None, counts,
            )
            self._pipeline["in_flight"] += launched.behind
            if spec is not None:
                # Keep the DRAFT cache in sync through plain iterations
                # (the near-capacity fallback): the draft consumes the
                # same token(s) via its width-2 catch-up append, so the
                # gap-is-at-most-one invariant the speculative window
                # relies on holds across any mix of plain and
                # speculative iterations. At draft length ==
                # capacity-1 the width-2 window's second row lies past
                # the table and is dropped; that slot's stream is at
                # token_limit - 1 and finishes THIS iteration.
                spec.draft_engine.verify(ctokens, dlengths)
                # The host must see these tokens to choose the next
                # window: with speculation bound nothing stays unread.
                unread, launched = launched, None
        object.__setattr__(self, "_unread", launched)
        if unread is not None:
            self._deliver_step(unread)
        return len(active)

    def _ending_unread(self, unread) -> set:
        """The slots whose stream the UNREAD step's token completes by
        the host's own count — its budget (``max_new``) or
        ``token_limit`` — known before the token is: such a slot does
        not decode in the step planned now, so no step is wasted on it.
        An end only the token tells (EOS) is seen one step late
        (:meth:`_decode`). Caller holds ``_lock``."""
        if unread is None:
            return set()
        limit = self._engine.token_limit
        return {
            slot for slot in unread.active
            if self._slot_stream[slot] is unread.snapshot[slot]
            and (
                len(unread.snapshot[slot]._tokens) + 1
                >= unread.snapshot[slot]._max_new
                # _slot_lengths already counts the unread step's row
                or self._slot_lengths[slot] + 1 >= limit
            )
        }

    def _deliver_step(self, unread) -> None:
        """Read ``unread`` (converting it: instant where the next step's
        dispatch span has waited for it, else in a ``decode_readback``
        leaf of its own) and deliver each slot's token. A slot whose stream ended
        or failed after the step was launched — an EOS the step before
        revealed, a deadline, ``close()``, a crash — decoded a token
        nobody owns: it is dropped, and counted. Where no step has been
        launched since (``_unread`` is None), the tokens are also the
        slots' host-known inputs again. Caller holds ``_step_lock``."""
        nxt = np.asarray(unread.step)
        latest = self._unread is None
        with _trace.span("sched_deliver"), self._lock:
            delivered = dropped = 0
            for slot in unread.active:
                if self._slot_stream[slot] is not unread.snapshot[slot]:
                    dropped += 1
                    continue
                if unread.counts is not None:
                    self._draft_lengths[slot] = int(
                        unread.dlengths[slot]
                    ) + int(unread.counts[slot])
                    self._draft_pending[slot] = []
                token = int(nxt[slot])
                if latest:
                    self._slot_tokens[slot] = token
                self._finish_or_continue(
                    slot, token, rows=int(unread.lengths[slot]) + 1
                )
                delivered += 1
            self._pipeline["dropped"] += dropped
            if self._metrics is not None:
                # (an engine that hands back plain host tokens times nothing)
                seconds = getattr(unread.step, "seconds", None)
                self._metrics.record_decode_step(
                    None if seconds is None else seconds * 1e3, delivered,
                    in_flight=unread.behind, dropped=dropped,
                )

    def _resolve_unread(self) -> None:
        """Bring the engine to rest for whoever must see it quiet (a
        staged swap, ``close()``): read the unread step, if any, and
        deliver what of it still has an owner. Caller holds
        ``_step_lock`` or has stopped the loop."""
        unread = self._unread
        if unread is not None:
            object.__setattr__(self, "_unread", None)
            self._deliver_step(unread)

    def _ensure_active_rows(self, extra: int, skip=()) -> None:
        """Pre-dispatch page guarantee: every active slot must hold
        pages covering ``length + extra`` rows before the next decode (``extra=1``) or verify
        window (``extra=w``) writes them. A slot the pool cannot grow
        — even after prefix-cache eviction — fails its stream with
        :class:`RejectedError` (partial tokens stay readable; the
        resubmit lands once other streams release pages). Caller holds
        ``_lock``."""
        for slot, stream in enumerate(self._slot_stream):
            if stream is None or slot in self._chunk_state or slot in skip:
                # Mid-prefill slots already hold pages for the FULL
                # prompt (admit_slot allocates them up front); the
                # batched dispatch's garbage writes past their cursor
                # land in those pages or drop via the OOB sentinel. So
                # do those of ``skip``, the slots the unread step
                # completes: they write no further row of their own.
                continue
            if self._engine.ensure_rows(
                slot, int(self._slot_lengths[slot]) + int(extra)
            ):
                continue
            if self._metrics is not None:
                self._metrics.record_rejected()
            stream._fail(RejectedError(
                "KV page pool exhausted mid-generation: no free page "
                "for this stream's next token even after prefix-cache "
                "eviction (partial output in tokens_so_far; raise "
                "engine.pool_pages or lower concurrency and resubmit)."
            ))
            self._free_slot(slot)

    def _slot_draft_state(self) -> np.ndarray:
        """Draft cached-rows snapshot (caller holds ``_lock``)."""
        return self._draft_lengths.astype(np.int32).copy()

    def _draft_catchup_window(self, active, cur_tokens):
        """Build the draft's width-2 catch-up/append window: per active
        slot, the (at most one) committed token the draft has not yet
        consumed, then the current input token. Returns ``(tokens
        [slots, 2], counts [slots])`` — ``counts`` is how many of the
        two are real (the rest is padding whose KV row stays garbage
        beyond the advanced length)."""
        n = int(self._engine.slots)
        ctokens = np.zeros((n, 2), np.int32)
        counts = np.zeros((n,), np.int32)
        for i in active:
            pending = self._draft_pending[i]
            if pending:
                ctokens[i, 0] = int(pending[0])
                ctokens[i, 1] = int(cur_tokens[i])
                counts[i] = 2
            else:
                ctokens[i, 0] = int(cur_tokens[i])
                ctokens[i, 1] = int(cur_tokens[i])  # pad row, never valid
                counts[i] = 1
        return ctokens, counts

    def _decode_spec(self, spec) -> int:
        """One speculative window over the whole slot array
        (docs/DESIGN.md §18): the draft proposes ``k`` tokens per slot
        (one width-2 catch-up append + ``k - 1`` draft steps), ONE
        teacher ``decode_verify`` scores all ``k + 1`` positions, and
        greedy acceptance commits the longest draft/teacher prefix
        match plus the teacher's own token at the first mismatch —
        1..k+1 tokens per slot per iteration, mixed accept lengths
        handled as host bookkeeping. Rollback-by-length: rejected
        suffix rows in BOTH caches are never advanced over. Caller
        holds ``_step_lock``; every dispatch runs outside ``_lock``
        over a snapshot, with the same identity-checked commit as the
        plain path."""
        engine = self._engine
        draft = spec.draft_engine
        k = int(spec.k)
        n = int(engine.slots)
        with _trace.span("sched_decode_plan"), self._lock:
            # Teacher verify appends the whole window's rows (the
            # accepted prefix advances over them; rejected rows stay
            # masked garbage in allocated pages — rollback never
            # deallocates mid-stream).
            self._ensure_active_rows(spec.window)
            snapshot = list(self._slot_stream)
            active = [
                i for i, s in enumerate(snapshot)
                if s is not None and i not in self._chunk_state
            ]
            if not active:
                return 0
            cur = self._slot_tokens.astype(np.int32).copy()
            lengths = self._slot_lengths.astype(np.int32).copy()
            dlengths = self._slot_draft_state()
            ctokens, counts = self._draft_catchup_window(active, cur)
        t0 = time.perf_counter()
        proposals = np.zeros((n, k), np.int32)
        with _trace.span(
            "spec_draft",
            attrs=(
                {"slots": len(active), "k": k}
                if _trace.enabled()
                else None
            ),
        ):
            # 1. Catch-up + first proposal: one width-2 append brings
            # the draft cache level with the teacher's committed prefix
            # AND consumes the current input token; the last fed
            # position's argmax is the first draft proposal.
            out = draft.verify(ctokens, dlengths)
            step_lengths = dlengths.copy()
            for i in active:
                proposals[i, 0] = int(out[i, int(counts[i]) - 1])
                step_lengths[i] += int(counts[i])
            # 2. k-1 sequential draft steps propose the rest.
            step_tokens = proposals[:, 0].copy()
            for t in range(1, k):
                step_tokens = draft.decode(step_tokens, step_lengths)
                for i in active:
                    proposals[i, t] = int(step_tokens[i])
                    step_lengths[i] += 1
        # 3. ONE teacher dispatch verifies the whole window: input
        # [current, d_1..d_k], argmax scored at every position.
        vtokens = np.zeros((n, k + 1), np.int32)
        for i in active:
            vtokens[i, 0] = int(cur[i])
            vtokens[i, 1:] = proposals[i]
        with _trace.span(
            "spec_verify",
            attrs=(
                {"slots": len(active), "window": k + 1}
                if _trace.enabled()
                else None
            ),
        ):
            scored = engine.verify(vtokens, lengths)
        dt_ms = (time.perf_counter() - t0) * 1e3
        # 4. Host accept + commit (greedy = longest prefix match).
        with _trace.span("sched_deliver"), self._lock:
            delivered = 0
            proposed_total = 0
            accepted_total = 0
            accept_lengths = []
            for i in active:
                stream = snapshot[i]
                if self._slot_stream[i] is not stream:
                    continue  # failed by close()/crash mid-dispatch
                a = 0
                while a < k and int(proposals[i, a]) == int(scored[i, a]):
                    a += 1
                base = int(lengths[i])
                for j in range(a + 1):
                    # Identical bookkeeping to the plain path, one
                    # accepted token at a time: lengths advance over
                    # the consumed input, then the token is delivered
                    # and EOS/length/capacity checked — a stream that
                    # finishes mid-window discards the rest of the
                    # window (both caches' surplus rows stay masked
                    # garbage per the rollback contract).
                    self._slot_lengths[i] = base + j + 1
                    token = int(scored[i, j])
                    self._slot_tokens[i] = token
                    self._finish_or_continue(i, token)
                    delivered += 1
                    if self._slot_stream[i] is not stream:
                        break
                if self._slot_stream[i] is stream:
                    # Survived the window: the draft has consumed
                    # [current, d_1..d_{k-1}] — on full acceptance it
                    # still owes d_k, carried as the pending catch-up
                    # token for the next window.
                    self._draft_lengths[i] = base + 1 + min(a, k - 1)
                    self._draft_pending[i] = (
                        [int(proposals[i, k - 1])] if a == k else []
                    )
                stream._spec_proposed += k
                stream._spec_accepted += a
                proposed_total += k
                accepted_total += a
                accept_lengths.append(a)
                if _trace.enabled() and stream.rid is not None:
                    _trace.event(
                        "spec_accept",
                        rid=stream.rid,
                        attrs={"proposed": k, "accepted": a},
                    )
            if accept_lengths:
                spec.record_window(proposed_total, accepted_total)
                if self._metrics is not None:
                    self._metrics.record_spec_window(
                        proposed_total,
                        accepted_total,
                        accept_lengths,
                        dt_ms,
                        delivered,
                    )
        return len(active) * (k + 1)

    def _iteration_budget(self) -> int:
        """Tokens one scheduler iteration may spend across decode and
        prefill chunks (docs/DESIGN.md §25). Explicit ``token_budget``
        wins; 0 auto-sizes to full decode occupancy (every slot's
        window) plus one whole chunk, so saturated decode still
        advances exactly one chunk of prefill per iteration."""
        b = int(self.token_budget)
        if b > 0:
            return b
        spec = getattr(self, "_speculative", None)
        per = int(spec.window) if spec is not None else 1
        return int(self._engine.slots) * per + int(
            self._engine.prefill_chunk_tokens
        )

    def _prefill_chunks(self, decode_spend: int) -> int:
        """Spend the iteration's remaining token budget on pending
        prefill chunks (docs/DESIGN.md §25): after decode took
        ``decode_spend`` tokens, the remainder is dealt to mid-prefill
        slots in slot order — up to ``prefill_chunk_tokens`` per lane
        per dispatch, multiple dispatches while budget and pending
        lanes remain. The FINAL chunk of a prompt returns its real
        last-position logits: TTFT is stamped, the first token
        delivered, the prefix cached, and the slot leaves
        ``_chunk_state`` to decode next iteration. Caller holds
        ``_step_lock``; dispatches run outside ``_lock`` with the
        same identity-checked commit as prefill/decode. Returns the
        chunks dispatched (lanes summed over dispatches); planning and
        commit are the admission's trace leaves (``sched_admit_plan``
        / ``sched_admit_commit``)."""
        if not getattr(self, "_chunked", False):
            return 0
        chunks = 0
        engine = self._engine
        spec = getattr(self, "_speculative", None)
        chunk_cap = int(engine.prefill_chunk_tokens)
        lane_cap = max(engine._prefill_buckets)
        # Progress floor: even a decode-saturated budget grants one
        # token, so a full slot array can never livelock the pending
        # prefills it is itself waiting on.
        budget = max(1, self._iteration_budget() - int(decode_spend))
        while budget > 0:
            group = []  # (slot, stream, chunk, offset, is_final)
            with _trace.span("sched_admit_plan"), self._lock:
                for slot in sorted(self._chunk_state):
                    if len(group) >= lane_cap or budget < 1:
                        break
                    stream = self._slot_stream[slot]
                    if stream is None:
                        continue
                    st = self._chunk_state[slot]
                    pos = int(st["pos"])
                    total = int(stream.prompt.shape[0])
                    c = min(chunk_cap, total - pos, budget)
                    if c < 1:
                        continue
                    budget -= c
                    group.append((
                        slot,
                        stream,
                        stream.prompt[pos:pos + c],
                        pos,
                        pos + c >= total,
                    ))
            if not group:
                return chunks
            chunks += len(group)
            t0 = time.perf_counter()
            last = engine.prefill_chunk(
                [g[2] for g in group],
                [g[0] for g in group],
                [g[3] for g in group],
            )
            finals = [g for g in group if g[4]]
            if spec is not None and finals:
                # Only once the full prompt is committed, exactly like
                # the unchunked admission path (the teacher's
                # final-chunk token is authoritative).
                self._seed_draft(
                    spec, [g[1] for g in finals], [g[0] for g in finals]
                )
            dt_ms = (time.perf_counter() - t0) * 1e3
            with _trace.span("sched_admit_commit"), self._lock:
                now = time.perf_counter()
                finished = 0
                stalls = []
                for (slot, stream, chunk, pos, final), tok in zip(
                    group, last
                ):
                    if self._slot_stream[slot] is not stream:
                        continue  # failed by close()/crash mid-dispatch
                    st = self._chunk_state.get(slot)
                    if st is None:
                        continue
                    end = pos + int(np.shape(chunk)[0])
                    st["pos"] = end
                    self._slot_lengths[slot] = end
                    if not final:
                        continue
                    del self._chunk_state[slot]
                    stream.ttft_ms = (now - stream._t_submit) * 1e3
                    stalls.append((now - float(st["admit_t"])) * 1e3)
                    if self._metrics is not None:
                        self._metrics.record_ttft(stream.ttft_ms)
                    if spec is not None:
                        # Both caches hold exactly the prompt now.
                        self._draft_lengths[slot] = end
                        self._draft_pending[slot] = []
                    # Cache the prompt's pages for future warm hits
                    # while the slot still references them.
                    engine.insert_prefix(slot, stream.prompt)
                    self._slot_tokens[slot] = int(tok)
                    self._finish_or_continue(slot, int(tok))
                    finished += 1
                if self._metrics is not None:
                    self._metrics.record_prefill_chunks(len(group), dt_ms)
                    if finished:
                        self._metrics.record_prefill_finish(
                            finished, stalls
                        )
                        self._metrics.record_first_tokens(finished)
        return chunks

    def _release_behind_window(self) -> None:
        """Once an iteration, for a model with sliding-window layers
        (docs/DESIGN.md §20; a no-op without a window group): hand the
        window group's pages that every sequence has left wholly behind
        ``length - window`` back to its free list, so the group's small
        pool (``slots x (window + a page)``) keeps serving sequences of
        any length. While tracing, one ``kv_pages_released`` /
        ``kv_pages_allocated`` event an iteration says how many pages
        of each group moved since the last."""
        pool = self._engine.page_pool
        group = getattr(pool, "window_group", None)
        if group is None:
            return
        with _trace.span("sched_window_release"), self._lock:
            released = self._engine.release_behind_window(self._slot_lengths)
        seen = getattr(self, "_pages_seen", (0, 0))
        now = (pool.allocated_pages, group.allocated_pages)
        object.__setattr__(self, "_pages_seen", now)
        if not _trace.enabled():
            return
        if released:
            _trace.event(
                "kv_pages_released", attrs={"full": 0, "window": released}
            )
        if now != seen:
            _trace.event(
                "kv_pages_allocated",
                attrs={"full": now[0] - seen[0], "window": now[1] - seen[1]},
            )

    def _update_occupancy(self) -> None:
        if self._metrics is None:
            return
        self._metrics.record_occupancy(
            sum(1 for s in self._slot_stream if s is not None),
            int(self._engine.slots),
            len(self._queue),
            self._engine.kv_pages_in_use(),
        )
        pool = self._engine.page_pool
        self._metrics.record_pool(pool.free_pages, pool.prefix_hit_rate)

    def _step_once(self) -> bool:
        """One scheduler iteration: swap boundary, deadline sweeps,
        admit (prefill), decode. Returns whether work remains.

        The decode phase launches this iteration's step and THEN reads
        and delivers the step the last iteration launched
        (:meth:`_decode`), so on entry one step may be unread and
        computing: the sweeps, admission planning and the plan run
        under it, a prefill admitted here is enqueued behind it, and
        whatever frees a slot meanwhile (a deadline, ``close()``)
        leaves that step one token nobody owns, dropped when it is
        read. The swap boundary reads the unread step before the
        weights change. While tracing the closing event says what the
        pipeline did: ``in_flight`` (1: the step was enqueued with the
        one before unread) and ``dropped``.

        ``_step_lock`` serializes iterations (sync mode admits
        multi-threaded callers); ``_lock`` guards only the bookkeeping
        phases and is RELEASED across the device dispatches inside
        ``_admit``/``_decode`` so a concurrent ``submit()`` or
        ``/statusz`` ``status()`` never waits out a prefill or decode
        wall time."""
        from zookeeper_tpu.resilience import faults

        with self._step_lock:
            # Trace: every record of this iteration carries its number
            # as ``step`` (the engine's dispatch spans through the
            # tracer's thread-local step). The phases below are LEAF
            # spans that tile the time between the dispatch spans and
            # enclose none of them (the staged weight swap has a span
            # of its own inside the engine, so it stays outside the
            # leaves); one ``sched_iteration_end`` event closes them,
            # and while tracing carries the iteration's wall time and
            # this thread's CPU time: what is left of the first after
            # the second and the dispatches' readback waits is time the
            # thread neither ran nor waited for the device (the GIL,
            # ``_lock``, the machine's other threads).
            started = (
                (time.perf_counter_ns(), time.thread_time_ns())
                if _trace.enabled()
                else None
            )
            iteration = self._iteration + 1
            object.__setattr__(self, "_iteration", iteration)
            pipeline = dict(self._pipeline)
            _trace.set_current_step(iteration)
            with self._lock:
                plan = faults.active()
                if plan is not None and plan.take_decode_worker_crash():
                    raise WorkerCrashedError(
                        "injected decode scheduler crash "
                        "(FaultPlan.decode_worker_crash)"
                    )
                self._maybe_apply_swap()
            with _trace.span("sched_sweep"), self._lock:
                self._maybe_apply_brownout()
                self._expire_queued()
                self._expire_active()
            # (the disaggregated scheduler's _admit counts nothing)
            admitted = self._admit() or 0
            spent = self._decode()
            # Chunked prefill rides the SAME iteration after decode:
            # decode spends the budget first, pending chunks get the
            # remainder (docs/DESIGN.md §25). No-op when chunking off.
            chunks = self._prefill_chunks(spent)
            self._release_behind_window()
            with self._lock:
                self._maybe_apply_swap()  # slot array may have drained
            with _trace.span("sched_bookkeeping"):
                with self._lock:
                    self._maybe_apply_brownout()
                    self._update_occupancy()
                # Wake backpressured submitters and drain()/iterator
                # waiters: queue room and stream progress both change
                # per iteration.
                with self._cv:
                    self._cv.notify_all()
            if _trace.enabled():
                attrs = {
                    "admitted": admitted,
                    "decoded": spent,
                    "chunks": chunks,
                }
                if started is not None:
                    # What the pipeline did: 1 when this iteration's
                    # step was enqueued with the one before unread (0:
                    # the pipeline was empty, or speculation keeps it
                    # so), and the tokens decoded for nobody.
                    for key, before in pipeline.items():
                        attrs[key] = self._pipeline[key] - before
                    # CPU first: its interval lies inside the wall's.
                    attrs["cpu_ns"] = time.thread_time_ns() - started[1]
                    attrs["wall_ns"] = time.perf_counter_ns() - started[0]
                _trace.event("sched_iteration_end", attrs=attrs)
                _trace.set_current_step(None)
        return self._has_work()

    def _pump(self) -> bool:
        """_step_once with the crash contract: ANY loop failure fails
        every queued and in-flight stream cleanly (no result() ever
        hangs), then re-raises — the async worker's catch restarts on
        the next submit; synchronous callers see the error with the
        streams already failed."""
        try:
            return self._step_once()
        except BaseException as e:
            self._on_crash(e)
            raise

    def _on_crash(self, error: BaseException) -> None:
        _trace.set_current_step(None)  # the iteration never closed
        with self._lock:
            # The unread step dies with its streams: never read, its
            # tokens have no owner left.
            object.__setattr__(self, "_unread", None)
            streams = [s for s in self._slot_stream if s is not None]
            streams += list(self._queue)
            self._queue.clear()
            for i in range(len(self._slot_stream)):
                # Drop the failed streams' page references (a
                # dispatch-failure crash already reset the pool
                # wholesale inside the engine — releasing an empty row
                # is a no-op, so both crash shapes leave zero leaked
                # pages, which the chaos suite pins).
                self._free_slot(i)
                # Draft bookkeeping dies with the streams: the next
                # occupant's draft prefill re-seeds it.
                self._draft_lengths[i] = 0
                self._draft_pending[i] = []
            # Mid-prefill cursors die with their streams too (the
            # pages were released above; nothing left to resume).
            self._chunk_state.clear()
            object.__setattr__(self, "_worker", None)
            _trace.event(
                "decode_worker_crash",
                attrs={
                    "error": type(error).__name__,
                    "failed_streams": len(streams),
                },
            )
            if self._metrics is not None:
                self._metrics.record_worker_restart()
            wrapped = WorkerCrashedError(
                f"DecodeScheduler crashed ({error!r}); this stream was "
                "failed cleanly (partial tokens in tokens_so_far) — "
                "resubmit to run on the restarted scheduler."
            )
            wrapped.__cause__ = error
            for stream in streams:
                stream._fail(wrapped)
            self._update_occupancy()
        # Flight-recorder trigger, AFTER the fails (the bundle's
        # RequestLog tail already carries outcome=crashed) and OUTSIDE
        # the lock (a synchronous bundle write must not stall
        # submit()/status() waiting on _lock). One global read when no
        # recorder is installed; never raises (docs/DESIGN.md §16).
        _recorder.notify(
            "decode_worker_crash",
            attrs={
                "error": type(error).__name__,
                "failed_streams": len(streams),
            },
        )

    # -- driving (synchronous mode) --------------------------------------

    def drain(self) -> None:
        """Serve everything: run the loop until the queue and the slot
        array are empty (sync), or block until the worker drains them
        (async; returns early — with streams already failed clean — if
        the worker dies)."""
        self._require_bound()
        if self.synchronous:
            while self._has_work():
                self._pump()
            with self._lock:
                self._maybe_apply_swap()
            return
        self._ensure_worker()
        with self._cv:
            self._cv.notify_all()
        while self._has_work() and not self._stop.is_set():
            worker = getattr(self, "_worker", None)
            if worker is None or not worker.is_alive():
                break  # crash cleanup already failed the streams
            with self._cv:
                self._cv.wait(0.01)

    def _drive(self, stream: DecodeStream, timeout: Optional[float]) -> None:
        """Block/drive until ``stream`` completes; never past its
        deadline."""
        if self.synchronous:
            while not stream._done and self._has_work():
                self._pump()
            if not stream._done and stream.expired():
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()
            return
        self._ensure_worker()
        with self._cv:
            self._cv.notify_all()
        deadline = stream._deadline_at
        t_end = (
            time.perf_counter() + timeout if timeout is not None else None
        )
        with stream._cond:
            while not stream._done:
                now = time.perf_counter()
                if deadline is not None and now >= deadline:
                    break
                if t_end is not None and now >= t_end:
                    break
                waits = [0.05]
                if deadline is not None:
                    waits.append(deadline - now)
                if t_end is not None:
                    waits.append(t_end - now)
                stream._cond.wait(max(0.0, min(waits)))
        if not stream._done:
            if stream.expired():
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()
            else:
                raise TimeoutError(
                    f"generation not complete within {timeout}s (worker "
                    "stalled, or close() was called)."
                )

    def _advance(self, stream: DecodeStream) -> None:
        """One increment of progress for an iterating consumer."""
        if self.synchronous:
            if not stream._done and self._has_work():
                self._pump()
            elif not stream._done and stream.expired():
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()
        else:
            with stream._cond:
                if not stream._done:
                    stream._cond.wait(0.05)
            # The deadline binds the STREAMING consumer too (same
            # posture as result()/_drive): a wedged worker must not
            # block an iterator past the request's deadline.
            if not stream._done and stream.expired():
                if stream._expire() and self._metrics is not None:
                    self._metrics.record_deadline_expired()

    # -- async worker ----------------------------------------------------

    def _ensure_worker(self) -> None:
        # Check-and-spawn under the lock: concurrent first submits must
        # not each start a worker (an orphaned duplicate would keep
        # pumping a closed scheduler — the liveness-under-lock rule the
        # MicroBatcher documents).
        with self._lock:
            worker = getattr(self, "_worker", None)
            if worker is None or not worker.is_alive():
                thread = threading.Thread(
                    target=self._worker_loop,
                    name="zk-decode-scheduler",
                    daemon=True,
                )
                object.__setattr__(self, "_worker", thread)
                thread.start()

    def _worker_loop(self) -> None:
        while not self._stop.is_set():
            if not self._has_work() and not self.swap_pending:
                # No stream and no swap: the wait for work is a leaf of
                # its own, outside any iteration (its ``step`` is None),
                # so a trace can tell a device idle for want of traffic
                # from one the host keeps waiting.
                with _trace.span("worker_idle_wait"), self._cv:
                    self._cv.wait(0.05)
                continue
            try:
                self._pump()
            except BaseException:
                # Streams already failed clean in _on_crash; the next
                # submit() starts a fresh worker.
                return

    def close(self, drain: bool = False) -> None:
        """Stop the scheduler. ``drain=True`` serves everything first;
        otherwise pending streams are FAILED so no result() blocks
        forever. Safe to call repeatedly / unbound."""
        if getattr(self, "_engine", None) is None:
            return
        if drain:
            try:
                self.drain()
            except Exception:
                pass  # per-stream errors already delivered
        self._stop.set()
        worker = getattr(self, "_worker", None)
        if worker is not None:
            with self._cv:
                self._cv.notify_all()
            worker.join(timeout=5)
            object.__setattr__(self, "_worker", None)
        try:
            # The loop has stopped: what the unread step decoded still
            # reaches its streams, and the engine is left at rest.
            self._resolve_unread()
        except Exception:
            # A failed device read: the streams fail below.
            logger.warning(
                "close(): the unread decode step could not be read",
                exc_info=True,
            )
        err = RuntimeError("DecodeScheduler closed with streams pending.")
        with self._lock:
            for stream in list(self._queue):
                stream._fail(err)
            self._queue.clear()
            for i, stream in enumerate(self._slot_stream):
                if stream is not None:
                    stream._fail(err)
                    self._free_slot(i)
        self._stop.clear()

    # -- introspection ---------------------------------------------------

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active_slots(self) -> int:
        with self._lock:
            return sum(1 for s in self._slot_stream if s is not None)

    def status(self) -> dict:
        """``/statusz`` decode section: the numbers an operator checks
        before trusting the stream metrics."""
        engine = self._engine
        with self._lock:
            return {
                "slots": int(engine.slots),
                "active_slots": sum(
                    1 for s in self._slot_stream if s is not None
                ),
                "queue_depth": len(self._queue),
                "kv_pages_in_use": engine.kv_pages_in_use(),
                "kv_capacity_tokens": engine.capacity,
                "kv_cache_mb": round(engine.kv_cache_nbytes / 2**20, 2),
                # HBM accounting (docs/DESIGN.md §17): the provisioned
                # bytes (also the zk_decode_kv_bytes gauge) and the
                # per-slot share an operator sizes capacity with.
                "kv_cache_bytes": int(engine.kv_cache_nbytes),
                "kv_bytes_per_slot": int(
                    engine.kv_cache_nbytes // max(1, int(engine.slots))
                ),
                "decode_attention": engine.decode_attention_flavor,
                # Page-pool vitals (docs/DESIGN.md §20): pool fill,
                # prefix-cache hits, CoW count. ``kv_layout`` is a
                # constant that CI and operators still read.
                "kv_layout": str(engine.kv_layout),
                "kv_pool": engine.pool_status(),
                # Last dispatch's memory-bandwidth utilization (-1 =
                # unknown) — the roofline lens for the memory-bound
                # decode step.
                "decode_mbu": round(engine.decode_mbu, 4),
                "compiles": engine.compile_count,
                "recompiles_detected": engine.recompiles_detected,
                "swap_pending": self.swap_pending,
                # The decode pipeline (docs/DESIGN.md §13): whether a
                # step is unread now, steps launched with the one
                # before unread, tokens decoded for a stream that had
                # ended (an EOS is seen one step late).
                "decode_pipeline": {
                    "unread": self._unread is not None,
                    "steps_in_flight": self._pipeline["in_flight"],
                    "tokens_dropped": self._pipeline["dropped"],
                },
                # Speculative schedule vitals (docs/DESIGN.md §18): k,
                # live acceptance, draft compile discipline.
                "speculative": (
                    self._speculative.status()
                    if getattr(self, "_speculative", None) is not None
                    else {"enabled": False}
                ),
                # Chunked-prefill planner vitals (docs/DESIGN.md §25):
                # always present so scrapers need no layout branch;
                # enabled=False means monolithic prefill.
                "chunked_prefill": {
                    "enabled": bool(getattr(self, "_chunked", False)),
                    "chunk_tokens": int(engine.prefill_chunk_tokens),
                    "token_budget": (
                        self._iteration_budget()
                        if getattr(self, "_chunked", False)
                        else 0
                    ),
                    "pending_prefills": len(
                        getattr(self, "_chunk_state", {})
                    ),
                    "pending_prefill_tokens": sum(
                        int(self._slot_stream[i].prompt.shape[0])
                        - int(st["pos"])
                        for i, st in getattr(
                            self, "_chunk_state", {}
                        ).items()
                        if self._slot_stream[i] is not None
                    ),
                },
                # Overload guardrails (docs/DESIGN.md §24): admission
                # estimator state + the scheduler's APPLIED brown-out
                # posture (may lag the guard's intent by one drain).
                "guardrails": {
                    "guard": (
                        self._guard.status()
                        if getattr(self, "_guard", None) is not None
                        else {"enabled": False}
                    ),
                    "brownout_active": bool(
                        getattr(self, "_brownout_active", False)
                    ),
                },
            }
