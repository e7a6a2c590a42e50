"""Chunked-prefill certification (docs/DESIGN.md §25): the decode
engine's ``prefill_chunk_tokens`` splits every admitted prompt into
bounded chunk dispatches the scheduler's token-budget planner
interleaves with decode steps — and the whole mode is pinned
TOKEN-IDENTICAL to monolithic prefill (which test_paged_kv.py pins
against the full-context greedy oracle, so
chunked == monolithic composes into chunked == oracle; the headline
test re-pins the oracle directly anyway) through real mid-prefill slot
refill, prefix-cache warm partial-chunk hits, chunk == page boundary
alignment, int8 KV, and the speculative schedule at both acceptance
extremes — with zero post-warmup compiles on every leg (chunk
dispatches ride the warmed ``prefill_extend`` grid).

The chaos leg pins crash-mid-chunk custody: pages released,
``leak_check() == 0``, the mid-prefill stream fails clean with
``WorkerCrashedError``. The guard leg regression-tests the §25
tokens-owed fix: remaining prefill chunks count toward predicted
completion. All CPU, synchronous scheduler.
"""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving import WorkerCrashedError
from zookeeper_tpu.serving.decode import (
    DecodeEngine,
    DecodeMetrics,
    DecodeScheduler,
    SpeculativeDecoding,
)
from zookeeper_tpu.serving.guardrails import OverloadGuard, PredictedMissError

from tests.serving.test_decode_engine import (
    VOCAB,
    build_lm,
    make_scheduler,
    oracle,
)
from tests.serving.test_paged_kv import paged_engine, serve

pytestmark = pytest.mark.serving

# Tier-1 keeps the tentpole certification (chunked == monolithic ==
# oracle through mid-prefill refill, compile-pinned) plus the instant
# config-seam rejections; the heavier legs (chunk-size sweep, page
# alignment, int8, both speculative extremes, warm-prefix skip, guard
# accounting, planner floor, statusz, crash-mid-chunk) are slow-marked
# and run UNFILTERED in the dedicated CI step — the same split as the
# disagg suite.


def monolithic_engine(module, params, state, *, name):
    """The reference: prompts prefilled whole and cold (its own parity
    with the full-context oracle is test_paged_kv.py's)."""
    return paged_engine(
        module, params, state, name=name, prefix_cache=False
    )


def chunked_engine(module, params, state, *, chunk=4, name="chunked",
                   **conf):
    return paged_engine(
        module, params, state, name=name,
        prefill_chunk_tokens=chunk, **conf,
    )


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(23)
    # > slots so admissions REFILL freed slots while OTHER prompts are
    # still mid-prefill — the planner must juggle decode, partial
    # cursors, and fresh admissions in the same iterations.
    return [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 16))).astype(
            np.int32
        )
        for _ in range(7)
    ]


# -- the parity certification ---------------------------------------------


@pytest.mark.slow
def test_chunked_token_identical_with_midprefill_refill(lm, prompts):
    module, params, state, variables = lm
    mono = paged_engine(module, params, state, name="chunkmono")
    chk = chunked_engine(module, params, state, chunk=4, name="chunkhead")
    mono_warm, chk_warm = mono.warmup(), chk.warmup()
    want = serve(mono, prompts)
    got = serve(chk, prompts)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    # And directly against the full-context greedy oracle.
    for p, out in zip(prompts[:3], got[:3]):
        np.testing.assert_array_equal(
            out, oracle(module, variables, p, out.shape[0])
        )
    # Refill happened (7 requests, 2 slots) and every chunk dispatch
    # rode the warmed extend grid: zero post-warmup compiles.
    assert mono.compile_count == mono_warm
    assert chk.compile_count == chk_warm
    assert chk.recompiles_detected == 0
    assert chk.page_pool.leak_check() == 0


@pytest.mark.slow
def test_chunk_size_sweep_token_identical(lm):
    """chunk=1 (every token its own dispatch) through chunk > prompt
    (a single chunk, the degenerate monolithic case) all agree."""
    module, params, state, _ = lm
    rng = np.random.default_rng(3)
    ps = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (1, 7, 13)
    ]
    mono = paged_engine(module, params, state, name="sweepmono")
    mono.warmup()
    want = serve(mono, ps, new_tokens=6)
    for chunk in (1, 5, 16):
        chk = chunked_engine(
            module, params, state, chunk=chunk, name=f"sweep{chunk}"
        )
        warm = chk.warmup()
        got = serve(chk, ps, new_tokens=6)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        assert chk.compile_count == warm, f"chunk={chunk} recompiled"
        assert chk.page_pool.leak_check() == 0


@pytest.mark.slow
def test_chunk_boundary_equals_page_boundary(lm):
    """chunk_tokens == page_size: every chunk fills exactly one page,
    so each dispatch's first row starts a fresh page (the alignment
    edge where an off-by-one would write across a page seam)."""
    module, params, state, _ = lm
    rng = np.random.default_rng(5)
    # 8 and 12 tokens land EXACTLY on 4-row page boundaries; 7 leaves
    # a partial final chunk.
    ps = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (8, 12, 7)
    ]
    mono = paged_engine(
        module, params, state, name="pagemono", page_size=4
    )
    mono.warmup()
    chk = chunked_engine(
        module, params, state, chunk=4, name="pagechunk", page_size=4
    )
    warm = chk.warmup()
    want = serve(mono, ps, new_tokens=6)
    got = serve(chk, ps, new_tokens=6)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert chk.compile_count == warm
    assert chk.page_pool.leak_check() == 0


@pytest.mark.slow
def test_chunked_int8_token_identical(lm):
    module, params, state, _ = lm
    mono = paged_engine(
        module, params, state, name="i8mono", kv_quant="int8"
    )
    mono.warmup()
    chk = chunked_engine(
        module, params, state, chunk=4, name="i8chunk", kv_quant="int8"
    )
    warm = chk.warmup()
    for seed in (0, 6):
        rng = np.random.default_rng(seed)
        ps = [
            rng.integers(1, VOCAB, size=int(rng.integers(1, 16))).astype(
                np.int32
            )
            for _ in range(5)
        ]
        a = serve(mono, ps)
        b = serve(chk, ps)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert chk.compile_count == warm


# -- prefix cache: warm partial-chunk hits ---------------------------------


@pytest.mark.slow
def test_warm_prefix_hit_skips_cached_chunks(lm):
    """A warm admission starts its chunk cursor PAST the cached prefix
    (shared pages are never re-prefilled), CoW fires exactly at the
    divergence, and streams stay identical to monolithic prefill. The
    12-token shared prefix with chunk=5 puts the cursor mid-chunk —
    the partial-chunk resume case."""
    module, params, state, _ = lm
    rng = np.random.default_rng(11)
    shared = rng.integers(1, VOCAB, size=12).astype(np.int32)
    ps = [
        np.concatenate(
            [shared, rng.integers(1, VOCAB, size=3).astype(np.int32)]
        )
        for _ in range(4)
    ] + [shared.copy()]  # an exact repeat of the shared prefix
    ref = monolithic_engine(module, params, state, name="warmchunkref")
    ref.warmup()
    want = serve(ref, ps, new_tokens=6)

    chk = chunked_engine(module, params, state, chunk=5, name="warmchunk")
    warm = chk.warmup()
    got = serve(chk, ps, new_tokens=6)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    pool = chk.page_pool
    assert pool.prefix.hits >= 3  # every admission after the first
    assert pool.cow_pages >= 3  # 12 % 16 != 0: divergence mid-page
    assert chk.compile_count == warm
    assert pool.leak_check() == 0


# -- speculative at both acceptance extremes -------------------------------


@pytest.mark.slow
def test_chunked_speculative_full_acceptance(lm, prompts):
    """Draft IS the teacher (acceptance ~1.0): the draft cache seeds
    on each FINAL chunk, then every window commits k+1 tokens —
    token-identical to the unchunked run."""
    module, params, state, _ = lm
    ref = monolithic_engine(module, params, state, name="chunkspecref")
    ref.warmup()
    want = serve(ref, prompts)

    teacher = chunked_engine(
        module, params, state, chunk=4, name="chunkspec"
    )
    teacher.warmup()
    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": 3}, name="chunk_spec")
    spec.bind(teacher, module, params, state)
    sched = DecodeScheduler()
    configure(sched, {"max_new_tokens": 8}, name="chunk_spec_sched")
    sched.bind(teacher, speculative=spec)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    got = [s.result() for s in streams]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert spec.acceptance_rate > 0.9  # draft IS the teacher
    assert teacher.page_pool.leak_check() == 0


@pytest.mark.slow
def test_chunked_speculative_low_acceptance(lm, prompts):
    """The rejection extreme: an independently-initialized draft
    disagrees almost always, so chunked admissions feed windows that
    roll back constantly — still token-identical."""
    module, params, state, _ = lm
    d_module, d_params, d_state, _ = build_lm(
        num_layers=1, d_model=32, num_heads=4, seed=99
    )
    ref = monolithic_engine(module, params, state, name="chunkrndref")
    ref.warmup()
    want = serve(ref, prompts)
    teacher = chunked_engine(
        module, params, state, chunk=4, name="chunkrnd"
    )
    teacher.warmup()
    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": 3}, name="chunk_spec_rnd")
    spec.bind(teacher, d_module, d_params, d_state)
    sched = DecodeScheduler()
    configure(sched, {"max_new_tokens": 8}, name="chunk_spec_rnd_sched")
    sched.bind(teacher, speculative=spec)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    got = [s.result() for s in streams]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


# -- the token-budget planner ----------------------------------------------


@pytest.mark.slow
def test_explicit_token_budget_floor_still_completes(lm):
    """token_budget=1 squeezes every iteration to the 1-token progress
    floor — prefill crawls one token per iteration but never
    livelocks, and the streams stay token-identical."""
    module, params, state, _ = lm
    rng = np.random.default_rng(9)
    ps = [rng.integers(1, VOCAB, size=10).astype(np.int32)
          for _ in range(3)]
    mono = paged_engine(module, params, state, name="floormono")
    mono.warmup()
    want = serve(mono, ps, new_tokens=4)
    chk = chunked_engine(module, params, state, chunk=4, name="floor")
    chk.warmup()
    got = serve(chk, ps, new_tokens=4, token_budget=1)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert chk.page_pool.leak_check() == 0


@pytest.mark.slow
def test_decode_never_stalls_behind_long_prompt(lm):
    """The tentpole's scheduling claim, pinned structurally: while a
    long prompt is mid-prefill, already-active streams KEEP receiving
    tokens in the same iterations (the monolithic path would freeze
    them for the whole prefill)."""
    module, params, state, _ = lm
    chk = chunked_engine(
        module, params, state, chunk=2, name="nostall", slots=2,
        seq_buckets=(8, 16), kv_capacity=64,
    )
    chk.warmup()
    sched = make_scheduler(chk, max_new_tokens=12)
    short = sched.submit(np.arange(1, 4, dtype=np.int32))
    # Admit + finish the short prompt's prefill first.
    sched._pump()
    tokens_before = len(short.tokens_so_far)
    assert tokens_before >= 1
    long = sched.submit(np.arange(1, 15, dtype=np.int32))  # 7 chunks
    progressed = []
    while long.ttft_ms is None and sched._has_work():
        sched._pump()
        progressed.append(len(short.tokens_so_far))
    # The short stream advanced DURING the long prompt's chunked
    # prefill — at least one token before the long TTFT landed.
    assert progressed and progressed[-1] > tokens_before
    sched.drain()
    assert long.result().shape[0] == 12
    st = sched.status()["chunked_prefill"]
    assert st["enabled"] and st["pending_prefills"] == 0


# -- config seam -----------------------------------------------------------


def test_chunking_needs_no_layout_key(lm):
    """Chunks append through the page table, and the pool is every
    engine's layout: ``prefill_chunk_tokens`` alone binds, and warms the
    extend grid it rides even with the prefix cache off."""
    module, params, state, _ = lm
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": 2, "seq_buckets": (8,), "prefill_chunk_tokens": 4,
            "prefix_cache": False,
        },
        name="chunk_default_seam",
    )
    engine.bind(module, params, state)
    engine.warmup()
    assert any(key[0] == "extend" for key in engine._compiled_cache)


def test_chunking_rejects_bad_sizes(lm):
    module, params, state, _ = lm
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": 2, "seq_buckets": (8,),
            "kv_capacity": 64, "prefill_chunk_tokens": -1,
        },
        name="chunk_neg_seam",
    )
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        engine.bind(module, params, state)
    wide = DecodeEngine()
    configure(
        wide,
        {
            "slots": 2, "seq_buckets": (8, 16),
            "kv_capacity": 64, "prefill_chunk_tokens": 32,
        },
        name="chunk_wide_seam",
    )
    with pytest.raises(ValueError, match="seq bucket"):
        wide.bind(module, params, state)


def test_scheduler_rejects_negative_token_budget(lm):
    module, params, state, _ = lm
    engine = chunked_engine(module, params, state, name="budget_seam")
    sched = DecodeScheduler()
    configure(sched, {"token_budget": -1}, name="budget_seam_sched")
    with pytest.raises(ValueError, match="token_budget"):
        sched.bind(engine)


def test_disagg_config_warn_degrades_chunking(caplog):
    """DisaggServingConfig: chunking on either role engine is LOUDLY
    degraded to monolithic prefill BEFORE bind (disagg already
    isolates the roles on separate slices — §25's problem does not
    exist there)."""
    import logging

    from zookeeper_tpu.serving import DisaggServingConfig

    svc = DisaggServingConfig()
    configure(
        svc,
        {
            "model.num_layers": 1, "model.d_model": 32,
            "model.num_heads": 4, "model.attention": "dense",
            "seq_len": 64, "vocab_size": 61,
            "engine.slots": 2, "engine.seq_buckets": (8,),
            "engine.prefill_buckets": (1,),
            "engine.prefill_chunk_tokens": 4,
            "prefill_engine.slots": 2,
            "prefill_engine.seq_buckets": (8,),
            "prefill_engine.prefill_buckets": (1, 2),
            "prefill_engine.prefill_chunk_tokens": 4,
            "requests": 0, "max_prompt": 6, "new_tokens": 2,
            "warmup": False, "verbose": False,
        },
        name="svc_disagg_chunk",
    )
    with caplog.at_level(logging.WARNING):
        engine, sched = svc.build_service()
    try:
        assert int(svc.engine.prefill_chunk_tokens) == 0
        assert int(svc.prefill_engine.prefill_chunk_tokens) == 0
        warned = [
            r for r in caplog.records
            if "prefill_chunk_tokens" in r.getMessage()
        ]
        assert len(warned) == 2  # one per role, loud
    finally:
        svc._teardown_service(suppress=True)


# -- guardrails: tokens-owed counts remaining chunks -----------------------


def _warmed_guard():
    guard = OverloadGuard()
    configure(guard, {"enabled": True}, name="chunk_guard")
    guard.bind()
    for _ in range(guard.min_samples):
        guard.observe_service(10.0, 1)  # 10 ms per unit
        guard.observe_wait(0.0)
    return guard


@pytest.mark.slow
def test_guard_admission_counts_remaining_prefill_chunks(lm):
    """The §25 estimator fix, as a regression on the predicted-miss
    math: queued 16-token prompts owe 4 chunk units each at chunk=4,
    so a deadline that clears the tokens-only estimate (monolithic
    posture) is predicted to MISS once prefill work is counted.

    queued = A's 8 tokens (+4 chunks chunked) ; request = 8 (+4).
    At 10 ms/unit: monolithic predicts 80 + 80 = 160 ms < 200 ms
    deadline (admit); chunked predicts 120 + 120 = 240 ms > 200 ms
    (shed)."""
    module, params, state, _ = lm
    prompt = np.arange(1, 17, dtype=np.int32)  # 16 tokens = 4 chunks

    mono = paged_engine(
        module, params, state, name="guardmono", seq_buckets=(8, 16, 32),
        kv_capacity=64,
    )
    mono.warmup()
    msched = make_scheduler(mono, max_new_tokens=8)
    object.__setattr__(msched, "_guard", _warmed_guard())
    msched.submit(prompt)  # queued ahead; scheduler not yet pumped
    msched.submit(prompt, deadline_ms=200.0)  # admits: 160 < 200
    msched.close()

    chk = chunked_engine(
        module, params, state, chunk=4, name="guardchunk",
        seq_buckets=(8, 16, 32), kv_capacity=64,
    )
    chk.warmup()
    csched = make_scheduler(chk, max_new_tokens=8)
    object.__setattr__(csched, "_guard", _warmed_guard())
    csched.submit(prompt)
    with pytest.raises(PredictedMissError):
        csched.submit(prompt, deadline_ms=200.0)  # sheds: 240 > 200
    csched.close()


# -- observability ---------------------------------------------------------


@pytest.mark.slow
def test_chunk_metrics_and_statusz(lm, prompts):
    module, params, state, _ = lm
    chk = chunked_engine(module, params, state, chunk=4, name="obs")
    chk.warmup()
    metrics = DecodeMetrics()
    configure(metrics, {}, name="chunk_obs_metrics")
    sched = DecodeScheduler()
    configure(sched, {"max_new_tokens": 6}, name="chunk_obs_sched")
    sched.bind(chk, metrics=metrics)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    for s in streams:
        s.result()
    totals = metrics.totals
    assert totals["prefill_chunks_total"] > len(prompts) / 2
    assert totals["requests_total"] == len(prompts)
    snap = metrics.snapshot()
    for key in (
        "itl_p50_ms", "itl_p99_ms", "prefill_stall_p50_ms",
        "prefill_stall_p99_ms",
    ):
        assert key in snap, key
    # The new series render as exposition text through the registry.
    names = {inst.name for inst in metrics.registry.collect()}
    assert "zk_decode_itl_ms" in names
    assert "zk_prefill_chunks_total" in names
    assert "zk_prefill_stall_ms" in names
    st = sched.status()["chunked_prefill"]
    assert st["enabled"] is True
    assert st["chunk_tokens"] == 4
    assert st["token_budget"] > 0
    assert st["pending_prefills"] == 0
    assert st["pending_prefill_tokens"] == 0


@pytest.mark.slow
def test_monolithic_statusz_reports_chunking_off(lm):
    module, params, state, _ = lm
    mono = paged_engine(module, params, state, name="obsmono")
    mono.warmup()
    sched = make_scheduler(mono, max_new_tokens=2)
    sched.generate(np.arange(1, 5, dtype=np.int32))
    st = sched.status()["chunked_prefill"]
    assert st["enabled"] is False
    assert st["chunk_tokens"] == 0
    assert st["token_budget"] == 0


# -- chaos -----------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow
def test_crash_mid_chunk_releases_pages(lm):
    """A crash while a prompt's chunk cursor is mid-prefill: its pages
    release, ``leak_check() == 0``, the stream fails clean with
    ``WorkerCrashedError``, and a resubmit on the restarted scheduler
    serves token-identically with zero new compiles."""
    module, params, state, _ = lm
    chk = chunked_engine(module, params, state, chunk=2, name="crash")
    warm = chk.warmup()
    sched = make_scheduler(chk, max_new_tokens=6)
    p = np.arange(1, 14, dtype=np.int32)  # 13 tokens = 7 chunks
    stream = sched.submit(p)
    sched._pump()  # admit + first chunk(s): cursor now mid-prompt
    st = sched.status()["chunked_prefill"]
    assert st["pending_prefills"] == 1
    assert 0 < st["pending_prefill_tokens"] < 13
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        with pytest.raises(WorkerCrashedError):
            sched._pump()
    with pytest.raises(WorkerCrashedError):
        stream.result()
    pool = chk.page_pool
    assert pool.leak_check() == 0
    assert sched.status()["chunked_prefill"]["pending_prefills"] == 0
    got = sched.generate(p)  # restarted scheduler
    ref = monolithic_engine(module, params, state, name="crashchunkref")
    ref.warmup()
    np.testing.assert_array_equal(
        got, make_scheduler(ref, max_new_tokens=6).generate(p)
    )
    assert chk.compile_count == warm
    assert pool.leak_check() == 0
