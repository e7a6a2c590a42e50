"""Decode-path chaos certification (FaultPlan-driven, deterministic):
scheduler crash fails every in-flight STREAM and queued request cleanly
(`WorkerCrashedError`, no result() ever hangs) and restarts on the next
submit; weight hot-swap mid-decode keeps each in-flight sequence on one
weight version (the drain-boundary contract under fault pressure)."""

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

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def warm_engine(lm):
    module, params, state, _ = lm
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    return engine


def make_sched(engine, **conf):
    m = DecodeMetrics()
    configure(m, {}, name="metrics")
    s = DecodeScheduler()
    configure(s, dict(conf), name="sched")
    s.bind(engine, metrics=m)
    return s, m


def test_injected_crash_fails_streams_clean_and_restarts(lm, warm_engine):
    """Sync mode: an injected loop crash fails the in-flight stream AND
    the queued one with WorkerCrashedError (partial tokens readable),
    then the scheduler serves normally again — the continuous-batching
    analogue of the MicroBatcher worker-death leg."""
    module, _, _, variables = lm
    sched, m = make_sched(warm_engine)
    p1 = np.arange(1, 6, dtype=np.int32)
    p2 = np.arange(2, 7, dtype=np.int32)
    in_flight = sched.submit(p1, max_new_tokens=6)
    sched._pump()  # prefill landed: one token already streamed
    assert in_flight.tokens_so_far.shape[0] >= 1
    queued1 = sched.submit(p2, max_new_tokens=4)
    queued2 = sched.submit(p2, max_new_tokens=4)
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        with pytest.raises(WorkerCrashedError):
            sched.drain()
    for stream in (in_flight, queued1, queued2):
        assert stream.done
        with pytest.raises(WorkerCrashedError):
            stream.result()
    # Partial output of the in-flight stream is real output.
    partial = in_flight.tokens_so_far
    assert partial.shape[0] >= 1
    np.testing.assert_array_equal(
        partial, oracle(module, variables, p1, partial.shape[0])
    )
    assert m.totals["worker_restarts_total"] == 1
    assert sched.active_slots == 0 and sched.queue_depth == 0
    # The restarted scheduler serves token-exact, zero new compiles.
    warm = warm_engine.compile_count
    out = sched.generate(p1, max_new_tokens=5)
    np.testing.assert_array_equal(out, oracle(module, variables, p1, 5))
    assert warm_engine.compile_count == warm


def test_async_worker_crash_restarts_on_next_submit(lm, warm_engine):
    """Async mode: the worker THREAD dies on the injected crash; every
    pending stream fails (never hangs), and the next submit starts a
    fresh worker that serves normally."""
    module, _, _, variables = lm
    sched, m = make_sched(warm_engine, synchronous=False)
    try:
        p = np.arange(1, 5, dtype=np.int32)
        with faults.injected(FaultPlan(decode_worker_crash=1)):
            doomed = sched.submit(p, max_new_tokens=8)
            with pytest.raises(WorkerCrashedError):
                doomed.result(timeout=120)
        assert m.totals["worker_restarts_total"] == 1
        revived = sched.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(
            revived.result(timeout=120), oracle(module, variables, p, 4)
        )
    finally:
        sched.close()


def test_crash_keeps_kv_isolation_across_restart(lm, warm_engine):
    """After a crash mid-stream, the next occupant of the same slot is
    unaffected by the dead stream's cache rows (the validity invariant:
    prefill + masking make stale rows invisible)."""
    module, _, _, variables = lm
    sched, _ = make_sched(warm_engine)
    long_prompt = np.arange(1, 16, dtype=np.int32)
    victim = sched.submit(long_prompt, max_new_tokens=16)
    sched._pump()
    sched._pump()  # several KV rows written beyond any short prompt
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        with pytest.raises(WorkerCrashedError):
            sched.drain()
    assert victim.done
    short = np.array([7, 3], np.int32)
    np.testing.assert_array_equal(
        sched.generate(short, max_new_tokens=6),
        oracle(module, variables, short, 6),
    )


def test_hot_swap_mid_decode_one_weight_version_per_stream(lm):
    """The chaos-leg restatement of the swap contract: a swap staged
    while streams are mid-decode applies only at the drain boundary —
    in-flight sequences finish bit-exact on their ORIGINAL weights even
    though the swap request landed between their dispatches."""
    module, params, state, variables = lm
    _, params_b, state_b, variables_b = build_lm(seed=23)
    engine = make_engine(module, params, state, slots=2)
    warm = engine.warmup()
    sched, m = make_sched(engine)
    rng = np.random.default_rng(9)
    p1 = rng.integers(1, VOCAB, size=6).astype(np.int32)
    p2 = rng.integers(1, VOCAB, size=9).astype(np.int32)
    s1 = sched.submit(p1, max_new_tokens=8)
    s2 = sched.submit(p2, max_new_tokens=5)
    sched._pump()
    sched._pump()  # both streams mid-decode
    sched.request_swap(params_b, state_b, step=7)
    sched._pump()  # swap must NOT apply: slots are occupied
    assert sched.swap_pending
    post = sched.submit(p1, max_new_tokens=5)  # admitted only post-swap
    sched.drain()
    assert not sched.swap_pending
    np.testing.assert_array_equal(s1.result(), oracle(module, variables, p1, 8))
    np.testing.assert_array_equal(s2.result(), oracle(module, variables, p2, 5))
    np.testing.assert_array_equal(
        post.result(), oracle(module, variables_b, p1, 5)
    )
    assert engine.compile_count == warm  # swap never recompiles
    assert m.totals["weight_swaps_total"] == 1


def test_crash_with_swap_pending_preserves_staged_swap(lm):
    """A crash while a swap is staged: streams fail clean, the staged
    swap survives and applies before the next admission, so post-crash
    streams run on the NEW weights."""
    module, params, state, variables = lm
    _, params_b, state_b, variables_b = build_lm(seed=23)
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    sched, _ = make_sched(engine)
    p = np.arange(1, 7, dtype=np.int32)
    victim = sched.submit(p, max_new_tokens=8)
    sched._pump()
    sched.request_swap(params_b, state_b)
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        with pytest.raises(WorkerCrashedError):
            sched.drain()
    assert victim.done and sched.swap_pending
    out = sched.generate(p, max_new_tokens=4)
    np.testing.assert_array_equal(out, oracle(module, variables_b, p, 4))
    assert not sched.swap_pending


def test_dispatch_failure_resets_cache_and_serves_resubmits(lm):
    """A failure of the compiled call ITSELF (transient device/runtime
    error at execute time, after donation consumed the KV buffers):
    streams fail clean like any crash, and the engine restores a usable
    cache — resubmits on the restarted scheduler serve token-exact with
    zero new compiles instead of dying on deleted arrays."""
    module, params, state, variables = lm
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    warm = engine.compile_count
    sched, _ = make_sched(engine)
    key = ("decode_step", engine._partitioner.mesh)
    real = engine._compiled_cache[key]

    def dying(variables_, cache, *operands):
        real(variables_, cache, *operands)  # donation happens
        raise RuntimeError("injected dispatch-time device failure")

    engine._compiled_cache[key] = dying
    p = np.arange(1, 6, dtype=np.int32)
    doomed = sched.submit(p, max_new_tokens=4)
    # Sync drain re-raises the ORIGINAL dispatch error (the streams
    # carry the WorkerCrashedError wrapper).
    with pytest.raises(RuntimeError, match="injected dispatch-time"):
        sched.drain()
    with pytest.raises(WorkerCrashedError):
        doomed.result()
    engine._compiled_cache[key] = real
    revived = sched.submit(p, max_new_tokens=4)
    sched.drain()
    np.testing.assert_array_equal(
        revived.result(), oracle(module, variables, p, 4)
    )
    assert engine.compile_count == warm
