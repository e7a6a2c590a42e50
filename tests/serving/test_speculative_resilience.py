"""Speculative-decode chaos certification (FaultPlan-driven,
deterministic — docs/DESIGN.md §18): a scheduler crash mid-speculation
fails every in-flight stream cleanly and the restarted scheduler serves
token-exact with BOTH caches (teacher + draft) consistent across
recovery; a draft dispatch failure after donation exercises the draft
engine's ``_reset_cache`` path in isolation from the teacher's; and a
staged TEACHER hot-swap mid-speculation upholds the
one-weight-version-per-sequence contract (the draft is never swapped —
staleness only lowers acceptance, never correctness)."""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving import WorkerCrashedError
from zookeeper_tpu.serving.decode import DecodeMetrics, DecodeScheduler

from tests.serving.test_decode_engine import (
    VOCAB,
    build_lm,
    make_engine,
    oracle,
)
from tests.serving.test_speculative import make_spec, zero_tail_pair

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


def make_sched(engine, spec, **conf):
    m = DecodeMetrics()
    configure(m, {}, name="spec_chaos_metrics")
    s = DecodeScheduler()
    configure(s, dict(conf), name="spec_chaos_sched")
    s.bind(engine, metrics=m, speculative=spec)
    return s, m


def test_crash_mid_speculation_fails_streams_clean_and_restarts():
    """Injected loop crash with speculation bound: in-flight AND
    queued streams fail with WorkerCrashedError (partial tokens
    readable and oracle-exact), draft bookkeeping is cleared, and the
    restarted scheduler serves token-exact through the speculative
    schedule with zero new compiles on either engine."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, draft, k=3)
    warm = engine.compile_count
    dwarm = spec.draft_engine.compile_count
    sched, m = make_sched(engine, spec)
    p1 = np.arange(1, 8, dtype=np.int32)
    p2 = np.arange(2, 7, dtype=np.int32)
    in_flight = sched.submit(p1, max_new_tokens=12)
    sched._pump()  # prefill + first speculative window landed
    assert in_flight.tokens_so_far.shape[0] >= 1
    queued = sched.submit(p2, max_new_tokens=4)
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        with pytest.raises(WorkerCrashedError):
            sched.drain()
    for stream in (in_flight, queued):
        assert stream.done
        with pytest.raises(WorkerCrashedError):
            stream.result()
    partial = in_flight.tokens_so_far
    assert partial.shape[0] >= 1
    np.testing.assert_array_equal(
        partial, oracle(module, variables, p1, partial.shape[0])
    )
    assert m.totals["worker_restarts_total"] == 1
    assert sched.active_slots == 0 and sched.queue_depth == 0
    # Restarted: speculative, token-exact, compile-free — the dead
    # streams' rows in BOTH caches are invisible to the new occupants.
    out = sched.generate(p1, max_new_tokens=6)
    np.testing.assert_array_equal(out, oracle(module, variables, p1, 6))
    assert engine.compile_count == warm
    assert spec.draft_engine.compile_count == dwarm


def test_draft_dispatch_failure_resets_draft_cache_and_serves_resubmits():
    """A failure of the DRAFT's compiled call itself (after donation
    consumed the draft KV buffers): streams fail clean like any crash,
    the draft engine restores a usable zeroed cache via its own
    ``_reset_cache`` — teacher-cache state is untouched machinery-wise
    (its rows die with the failed streams per the validity invariant) —
    and resubmits serve token-exact with zero new compiles."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, draft, k=2)
    warm = engine.compile_count
    dwarm = spec.draft_engine.compile_count
    sched, _ = make_sched(engine, spec)
    draft_engine = spec.draft_engine
    key = ("verify", 2, draft_engine._partitioner.mesh)
    real = draft_engine._compiled_cache[key]

    def dying(variables_, cache, *operands):
        real(variables_, cache, *operands)  # donation happens
        raise RuntimeError("injected draft dispatch-time failure")

    draft_engine._compiled_cache[key] = dying
    p = np.arange(1, 6, dtype=np.int32)
    doomed = sched.submit(p, max_new_tokens=6)
    with pytest.raises(RuntimeError, match="injected draft"):
        sched.drain()
    with pytest.raises(WorkerCrashedError):
        doomed.result()
    # The draft's pool was reset with its cache, the teacher's slot
    # released: neither holds a page of the failed stream.
    for pool in (draft_engine.page_pool, engine.page_pool):
        assert pool.used_pages == 0 and pool.leak_check() == 0
    draft_engine._compiled_cache[key] = real
    revived = sched.submit(p, max_new_tokens=6)
    sched.drain()
    np.testing.assert_array_equal(
        revived.result(), oracle(module, variables, p, 6)
    )
    assert engine.compile_count == warm
    assert draft_engine.compile_count == dwarm


def test_teacher_hot_swap_mid_speculation_one_weight_version_per_stream():
    """request_swap staged while streams are mid-SPECULATION: the swap
    applies only at the drain boundary, in-flight streams finish
    bit-exact on their ORIGINAL teacher weights, and post-swap streams
    run bit-exact on the NEW teacher — with the DRAFT deliberately
    unswapped (it now disagrees with the new teacher, so acceptance
    drops, but every emitted token is still the live teacher's argmax:
    losslessness is independent of draft quality)."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    _, params_b, state_b, variables_b = build_lm(num_layers=3, seed=29)
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, draft, k=3)
    warm = engine.compile_count
    sched, m = make_sched(engine, spec)
    rng = np.random.default_rng(9)
    p1 = rng.integers(1, VOCAB, size=6).astype(np.int32)
    p2 = rng.integers(1, VOCAB, size=9).astype(np.int32)
    # Budgets span many k+1 windows so both streams are genuinely
    # mid-speculation at the swap request (a full-accept window
    # delivers up to 4 tokens per pump at k=3).
    s1 = sched.submit(p1, max_new_tokens=30)
    s2 = sched.submit(p2, max_new_tokens=24)
    sched._pump()
    sched._pump()  # both streams mid-speculation
    sched.request_swap(params_b, state_b, step=31)
    sched._pump()  # must NOT apply: slots occupied
    assert sched.swap_pending
    post = sched.submit(p1, max_new_tokens=5)  # admitted only post-swap
    sched.drain()
    assert not sched.swap_pending
    np.testing.assert_array_equal(
        s1.result(), oracle(module, variables, p1, 30)
    )
    np.testing.assert_array_equal(
        s2.result(), oracle(module, variables, p2, 24)
    )
    np.testing.assert_array_equal(
        post.result(), oracle(module, variables_b, p1, 5)
    )
    assert engine.compile_count == warm  # swap never recompiles
    assert m.totals["weight_swaps_total"] == 1


def test_crash_with_swap_pending_survives_into_speculative_restart():
    """Crash while a teacher swap is staged: streams fail clean, the
    staged swap survives and applies before the next admission — the
    post-crash stream speculates against the NEW teacher weights."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    _, params_b, state_b, variables_b = build_lm(num_layers=3, seed=29)
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, draft, k=2)
    sched, _ = make_sched(engine, spec)
    p = np.arange(1, 7, dtype=np.int32)
    victim = sched.submit(p, max_new_tokens=8)
    sched._pump()
    sched.request_swap(params_b, state_b)
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        with pytest.raises(WorkerCrashedError):
            sched.drain()
    assert victim.done and sched.swap_pending
    out = sched.generate(p, max_new_tokens=4)
    np.testing.assert_array_equal(
        out, oracle(module, variables_b, p, 4)
    )
    assert not sched.swap_pending
