"""Page-pool certification (docs/DESIGN.md §20): the engine's one KV
layout — shared device page pool, per-slot page tables as runtime
operands, radix prefix cache with copy-on-write, int8 quantization —
pinned token-identical to the full-context greedy oracle
(``greedy_decode``; ``want``, computed once a module) through real slot
refill, warm-prefix admission, divergence CoW,
LRU eviction under pool pressure, pool exhaustion, and the chaos legs
(crash with a live pool, staged hot-swap invalidation). All CPU,
synchronous scheduler.
"""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving import RejectedError, WorkerCrashedError
from zookeeper_tpu.serving.decode import (
    DecodeEngine,
    DecodeMetrics,
    DecodeScheduler,
    SpeculativeDecoding,
)

from tests.serving.test_decode_engine import (
    VOCAB,
    build_lm,
    make_scheduler,
    oracle,
)

pytestmark = pytest.mark.serving


def paged_engine(module, params, state, *, slots=2, seq_buckets=(8, 16),
                 kv_capacity=64, name="paged", **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots,
            "seq_buckets": tuple(seq_buckets),
            "kv_capacity": kv_capacity,
            **conf,
        },
        name=f"pengine_{name}",
    )
    engine.bind(module, params, state)
    return engine


def serve(engine, prompts, new_tokens=8, **conf):
    sched = make_scheduler(engine, max_new_tokens=new_tokens, **conf)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    return [s.result() for s in streams]


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    # > slots so later admissions REFILL freed slots mid-traffic, and
    # freed PAGES get recycled mid-traffic — the paged twin of the
    # refill-garbage leg.
    return [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 16))).astype(
            np.int32
        )
        for _ in range(7)
    ]


@pytest.fixture(scope="module")
def want(lm, prompts):
    """The full-context greedy oracle's 8 tokens after each prompt."""
    module, _, _, variables = lm
    return [oracle(module, variables, p, 8) for p in prompts]


# -- the parity certification ---------------------------------------------


def test_paged_token_identical_to_oracle_with_refill(lm, prompts, want):
    module, params, state, variables = lm
    pag = paged_engine(module, params, state, name="parity")
    pag_warm = pag.warmup()
    pag_out = serve(pag, prompts)
    # The acceptance pin, including the streams that rode recycled
    # pages.
    for a, b in zip(want, pag_out):
        np.testing.assert_array_equal(a, b)
    # Refill happened (7 requests, 2 slots) with zero recompiles.
    assert pag.compile_count == pag_warm
    assert pag.recompiles_detected == 0


def test_poisoned_free_page_equality(lm, prompts):
    """The §20 free-page-garbage contract as an EQUALITY: poisoning
    every pool page at ±1e9 before traffic must produce the exact
    streams of the zeroed pool — prefill overwrites the rows it owns,
    lengths mask everything else, recycled-page garbage included."""
    import jax
    import jax.numpy as jnp

    module, params, state, _ = lm
    clean = paged_engine(module, params, state, name="clean")
    clean.warmup()
    want = serve(clean, prompts)

    poisoned = paged_engine(module, params, state, name="poisoned")
    poisoned.warmup()
    rng = np.random.default_rng(0)

    def poison(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            sign = rng.choice([-1.0, 1.0], size=x.shape)
            return jnp.asarray(sign * 1e9, x.dtype)
        return x

    object.__setattr__(
        poisoned,
        "_cache",
        poisoned._place_cache(jax.tree.map(poison, poisoned._cache)),
    )
    got = serve(poisoned, prompts)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_paged_capacity_truncation_matches_oracle(lm):
    """The truncate-at-EXACTLY-token_limit contract over page
    boundaries: a stream that exhausts its capacity fills its LAST
    page to the final row and stops, every token the oracle's."""
    module, params, state, variables = lm
    pag = paged_engine(
        module, params, state, name="cap", kv_capacity=16,
        page_size=4, slots=1,
    )
    pag.warmup()
    p = np.arange(1, 9, dtype=np.int32)
    sched = make_scheduler(pag, max_new_tokens=32)
    stream = sched.submit(p)
    sched.drain()
    got = stream.result()
    np.testing.assert_array_equal(got, oracle(module, variables, p, 8))
    assert stream.finish_reason == "capacity"
    assert got.shape[0] == 16 - 8  # total EXACTLY token_limit
    assert pag.page_pool.leak_check() == 0


# -- prefix cache ----------------------------------------------------------


def test_warm_prefix_hit_cow_and_parity(lm):
    """Warm repeats and a mid-page divergence: the second admission of
    a shared prefix reuses cached pages (hit rate > 0), copies exactly
    the divergence page (CoW), and every stream stays token-identical
    to the oracle (which never shares anything)."""
    module, params, state, variables = lm
    rng = np.random.default_rng(11)
    shared = rng.integers(1, VOCAB, size=12).astype(np.int32)
    ps = [
        np.concatenate(
            [shared, rng.integers(1, VOCAB, size=3).astype(np.int32)]
        )
        for _ in range(4)
    ] + [shared.copy()]  # an exact repeat of the shared prefix
    want = [oracle(module, variables, p, 6) for p in ps]

    pag = paged_engine(module, params, state, name="warm")
    warm = pag.warmup()
    got = serve(pag, ps, new_tokens=6)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    pool = pag.page_pool
    assert pool.prefix.hits >= 3  # every admission after the first
    assert pool.prefix_hit_rate > 0.3
    assert pool.cow_pages >= 3  # 12 % 16 != 0: divergence mid-page
    assert pag.compile_count == warm  # warm extends were pre-warmed
    assert pool.leak_check() == 0


def test_prefix_cache_off_serves_cold(lm, prompts, want):
    module, params, state, _ = lm
    pag = paged_engine(
        module, params, state, name="nocache", prefix_cache=False
    )
    pag.warmup()
    for x, y in zip(serve(pag, prompts[:4]), want[:4]):
        np.testing.assert_array_equal(x, y)
    assert pag.page_pool.prefix is None
    assert pag.pool_status()["used_pages"] == 0  # all released cold


def test_prefix_eviction_under_pool_pressure(lm):
    """A pool too small to cache everything: LRU eviction frees
    refcount-1 nodes, admissions keep serving, tokens stay identical
    to the oracle."""
    module, params, state, variables = lm
    rng = np.random.default_rng(13)
    # 6 distinct 14-token prompts at page_size 16 = one page each;
    # pool of 3 pages forces eviction after every admission.
    ps = [
        rng.integers(1, VOCAB, size=14).astype(np.int32) for _ in range(6)
    ]
    pag = paged_engine(
        module, params, state, name="evict", slots=1,
        pool_pages=3, page_size=16, kv_capacity=48,
    )
    pag.warmup()
    for p, got in zip(ps, serve(pag, ps, new_tokens=4)):
        np.testing.assert_array_equal(got, oracle(module, variables, p, 4))
    assert pag.page_pool.prefix.evicted_pages > 0
    assert pag.page_pool.leak_check() == 0


# -- pooling / exhaustion --------------------------------------------------


def test_pool_serves_more_than_its_worst_case_and_requeues(lm):
    """The overcommit claim: a pool provisioned BELOW slots × capacity
    serves a workload whose PER-SLOT worst case would not fit, by
    requeueing admissions until finishing streams release pages."""
    module, params, state, variables = lm
    rng = np.random.default_rng(17)
    ps = [
        rng.integers(1, VOCAB, size=6).astype(np.int32) for _ in range(6)
    ]
    # capacity 64 → 4 pages/slot worst case; 2 slots worst case = 8
    # pages. Pool of 4 pages = HALF the worst case: both slots can
    # never simultaneously hold worst-case streams, but actual streams
    # (6 prompt + 4 generated = 10 tokens = 1 page) fit many at once.
    pag = paged_engine(
        module, params, state, name="overcommit", pool_pages=4,
        prefix_cache=False,
    )
    pag.warmup()
    for p, got in zip(ps, serve(pag, ps, new_tokens=4)):
        np.testing.assert_array_equal(got, oracle(module, variables, p, 4))
    assert pag.page_pool.leak_check() == 0


def test_mid_generation_exhaustion_fails_one_stream_cleanly(lm):
    """Two active streams racing for the pool's LAST page: the one the
    pre-dispatch sweep reaches first fails with RejectedError (partial
    tokens readable — pool pressure is overload, not corruption), its
    released pages let the OTHER stream finish, and the scheduler
    keeps serving. The bind-time floor (pool >= one slot's worst case)
    means a LONE stream can always run to its token limit — genuine
    exhaustion needs concurrency, which is what this pins."""
    module, params, state, _ = lm
    pag = paged_engine(
        module, params, state, name="exhaust", slots=2,
        pool_pages=4, page_size=4, kv_capacity=16, prefix_cache=False,
    )
    pag.warmup()
    sched = make_scheduler(pag, max_new_tokens=6)
    # Two 8-token prompts = 2 pages each: the pool is FULL at
    # admission; the first decode needs a 3rd page per slot and there
    # are none.
    a = sched.submit(np.arange(1, 9, dtype=np.int32))
    b = sched.submit(np.arange(2, 10, dtype=np.int32))
    sched.drain()
    with pytest.raises(RejectedError, match="pool exhausted"):
        a.result()
    assert a.tokens_so_far.shape[0] >= 1  # the prefill emission landed
    assert b.result().shape[0] == 6  # freed pages let it finish
    assert pag.page_pool.leak_check() == 0
    # The scheduler survives: a servable prompt runs right after.
    out = sched.generate(np.arange(1, 5, dtype=np.int32))
    assert out.shape[0] == 6


# -- int8 quantization -----------------------------------------------------


def test_int8_argmax_token_exact_sweep(lm):
    """The engine-level half of the §20 int8 contract (the ULP bound
    is pinned at op level in tests/ops/test_pool_attention.py): int8
    pools must emit the exact fp token streams across a seed sweep —
    greedy argmax riding a 1/254-relative-step perturbation."""
    module, params, state, _ = lm
    fp = paged_engine(module, params, state, name="int8fp")
    fp.warmup()
    q8 = paged_engine(
        module, params, state, name="int8q", kv_quant="int8"
    )
    q8.warmup()
    # Pinned seeds: int8 KV is LOSSY (1/254 relative step), and a
    # fresh-init model's near-tie logits can flip argmax under it —
    # the §20 contract is documented-ULP plus argmax exactness in the
    # certified configs, not bit-exactness everywhere (the same
    # posture every quantized path in this repo takes).
    for seed in (0, 2, 6):
        rng = np.random.default_rng(seed)
        ps = [
            rng.integers(1, VOCAB, size=int(rng.integers(1, 16))).astype(
                np.int32
            )
            for _ in range(5)
        ]
        a = serve(fp, ps)
        b = serve(q8, ps)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_int8_needs_no_layout_key(lm):
    """Quantization lives with the page pool, and the pool is the
    layout of every engine: ``kv_quant`` alone binds int8 rows beside
    their scale pages."""
    module, params, state, _ = lm
    engine = DecodeEngine()
    configure(
        engine,
        {"slots": 2, "seq_buckets": (8,), "kv_quant": "int8"},
        name="int8_default",
    )
    engine.bind(module, params, state)
    layer = engine._cache[0]
    assert layer["k"].dtype == np.int8
    assert set(layer) == {"k", "v", "k_scale", "v_scale"}
    with pytest.raises(ValueError, match="kv_quant"):
        paged_engine(module, params, state, name="q4", kv_quant="int4")


# -- accounting / observability --------------------------------------------


def test_pool_accounting_gauges_and_statusz(lm, prompts):
    module, params, state, _ = lm
    pag = paged_engine(module, params, state, name="acct")
    pag.warmup()
    metrics = DecodeMetrics()
    configure(metrics, {}, name="acct_metrics")
    sched = DecodeScheduler()
    configure(sched, {"max_new_tokens": 6}, name="acct_sched")
    sched.bind(pag, metrics=metrics)
    streams = [sched.submit(p) for p in prompts[:4]]
    sched.drain()
    for s in streams:
        s.result()
    pool = pag.page_pool
    # Real allocator counts, not the length estimate: after the drain
    # only prefix-cache-retained pages remain in use.
    assert pag.kv_pages_in_use() == pool.used_pages
    gauges = metrics._obs()["gauges"]
    assert gauges["kv_pool_free_pages"].value == pool.free_pages
    assert (
        gauges["prefix_cache_hit_rate"].value == pool.prefix_hit_rate
    )
    status = sched.status()
    assert status["kv_layout"] == "paged"
    kv_pool = status["kv_pool"]
    for key in (
        "num_pages", "used_pages", "free_pages", "fill", "cow_pages",
        "prefix_hit_rate", "prefix_invalidations",
    ):
        assert key in kv_pool, (key, kv_pool)
    # Both new series render as exposition text through the registry.
    body = "\n".join(
        line
        for inst in metrics.registry.collect()
        for line in [inst.name]
    )
    assert "zk_kv_pool_free_pages" in body
    assert "zk_prefix_cache_hit_rate" in body


def test_one_layout_every_engine_reports_its_pool(lm):
    """``kv_layout`` is a constant: an engine configured without the
    key serves from a pool and says so; the removed ``"slots"`` value
    is refused at bind by name."""
    module, params, state, _ = lm
    engine = DecodeEngine()
    configure(
        engine,
        {"slots": 2, "seq_buckets": (8,), "kv_capacity": 64},
        name="default_layout",
    )
    engine.bind(module, params, state)
    engine.warmup()
    assert engine.page_pool.num_pages == 2 * 64 // 16  # the worst case
    sched = make_scheduler(engine, max_new_tokens=2)
    sched.generate(np.arange(1, 5, dtype=np.int32))
    status = sched.status()
    assert status["kv_layout"] == "paged"
    assert status["kv_pool"] == engine.pool_status()
    with pytest.raises(ValueError, match="only KV layout.*PR 29"):
        paged_engine(module, params, state, name="gone", kv_layout="slots")


# -- speculative over pages ------------------------------------------------


def test_speculative_paged_token_identical_high_acceptance(
    lm, prompts, want
):
    """The speculative window append/rollback over PAGE BOUNDARIES,
    teacher and draft each on a pool of its own, the teacher's with
    the prefix cache on: draft = the teacher itself (acceptance 1.0 —
    every window commits k+1 tokens through the page table), certified
    token-identical to the oracle; neither pool leaks a page."""
    module, params, state, _ = lm
    teacher = paged_engine(module, params, state, name="specteacher")
    teacher.warmup()
    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": 3}, name="paged_spec")
    spec.bind(teacher, module, params, state)
    sched = DecodeScheduler()
    configure(sched, {"max_new_tokens": 8}, name="paged_spec_sched")
    sched.bind(teacher, speculative=spec)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    got = [s.result() for s in streams]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert spec.acceptance_rate > 0.9  # draft IS the teacher
    assert teacher.page_pool.leak_check() == 0
    assert spec.draft_engine.page_pool.used_pages == 0
    assert spec.draft_engine.page_pool.leak_check() == 0


@pytest.mark.slow
def test_speculative_paged_token_identical_random_draft(
    lm, prompts, want
):
    """The pure-rejection extreme: an independently-initialized draft
    disagrees almost always, so every window exercises rollback-by-
    length over allocated-but-rejected page rows."""
    module, params, state, _ = lm
    d_module, d_params, d_state, _ = build_lm(
        num_layers=1, d_model=32, num_heads=4, seed=99
    )
    teacher = paged_engine(module, params, state, name="specrnd")
    teacher.warmup()
    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": 3}, name="paged_spec_rnd")
    spec.bind(teacher, d_module, d_params, d_state)
    sched = DecodeScheduler()
    configure(sched, {"max_new_tokens": 8}, name="paged_spec_rnd_sched")
    sched.bind(teacher, speculative=spec)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    got = [s.result() for s in streams]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


# -- chaos -----------------------------------------------------------------


@pytest.mark.chaos
def test_crash_with_live_pool_resets_cleanly(lm, prompts):
    """Decode-worker crash with a live page pool: streams fail clean,
    no page leaks, the prefix trie holds no stale references, and a
    resubmit on the restarted scheduler serves token-identically —
    the ``_reset_cache``-equivalent pool reallocation leg."""
    module, params, state, variables = lm
    pag = paged_engine(module, params, state, name="crash")
    warm = pag.warmup()
    sched = make_scheduler(pag, max_new_tokens=6)
    p = np.arange(1, 8, dtype=np.int32)
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        stream = sched.submit(p)
        with pytest.raises(WorkerCrashedError):
            stream.result()
    pool = pag.page_pool
    assert pool.leak_check() == 0
    got = sched.generate(p)  # restarted scheduler
    np.testing.assert_array_equal(got, oracle(module, variables, p, 6))
    assert pag.compile_count == warm
    assert pool.leak_check() == 0


@pytest.mark.chaos
def test_dispatch_failure_resets_pool_and_trie(lm):
    """A dispatch-path failure consumed the donated pool buffers: the
    engine's ``_reset_cache`` must reallocate the DEVICE pool and
    reset the HOST allocator together — refcounts zeroed, trie
    dropped (its nodes indexed bytes that no longer exist), zero
    leaked pages — and the restarted scheduler serves resubmits."""
    module, params, state, variables = lm
    pag = paged_engine(module, params, state, name="reset")
    pag.warmup()
    sched = make_scheduler(pag, max_new_tokens=4)
    sched.generate(np.arange(1, 10, dtype=np.int32))  # warm the trie
    pool = pag.page_pool
    assert pool.used_pages > 0 and pool.prefix.nodes > 0
    invalidations_before = pool.prefix.invalidations
    pag._reset_cache()
    pool = pag.page_pool
    assert pool.used_pages == 0
    assert pool.free_pages == pool.num_pages
    assert pool.prefix.nodes == 0
    assert pool.prefix.invalidations == invalidations_before + 1
    assert pool.leak_check() == 0
    p = np.arange(1, 10, dtype=np.int32)
    np.testing.assert_array_equal(
        sched.generate(p), oracle(module, variables, p, 4)
    )


@pytest.mark.chaos
def test_staged_swap_invalidates_prefix_cache_exactly_once(lm):
    """A staged weight hot-swap must invalidate the prefix cache
    EXACTLY once (cached pages hold OLD-weight K/V), and post-swap
    admissions of a previously-warm prompt run COLD — then re-warm
    under the new weights."""
    module, params, state, _ = lm
    pag = paged_engine(module, params, state, name="swap")
    pag.warmup()
    sched = make_scheduler(pag, max_new_tokens=4)
    p = np.arange(1, 12, dtype=np.int32)
    sched.generate(p)
    pool = pag.page_pool
    assert pool.prefix.nodes > 0
    hits_before = pool.prefix.hits
    inval_before = pool.prefix.invalidations
    sched.request_swap(params, state, step=123)
    sched.drain()  # slot array empty: swap applies at the boundary
    assert not sched.swap_pending
    assert pool.prefix.invalidations == inval_before + 1
    assert pool.prefix.nodes == 0
    # Post-swap: the same prompt admits COLD (no stale-weight hit)...
    sched.generate(p)
    assert pool.prefix.hits == hits_before  # lookup missed
    # ...and a THIRD serve warms against the re-inserted pages.
    sched.generate(p)
    assert pool.prefix.hits == hits_before + 1
    assert pool.leak_check() == 0


# -- sharded mesh leg ------------------------------------------------------


@pytest.mark.slow
def test_paged_dp_tp_mesh_leg_token_identical(lm, prompts):
    """dp2×tp2 mesh with page tables as RUNTIME data: pool heads shard
    over the model axis (pages replicate — any slot references any
    page), streams token-identical to the single-device paged engine."""
    from zookeeper_tpu.parallel.partitioner import MeshPartitioner
    from zookeeper_tpu.parallel.rules import transformer_tp_rules

    module, params, state, _ = lm
    single = paged_engine(module, params, state, name="mesh_single")
    single.warmup()
    want = serve(single, prompts)

    part = MeshPartitioner()
    configure(
        part,
        {
            "mesh_shape": (2, 2),
            "mesh_axes": ("data", "model"),
            "data_axes": ("data",),
            "num_devices": 4,
        },
        name="paged_mesh_part",
    )
    part.with_rules(transformer_tp_rules())
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": 2,
            "seq_buckets": (8, 16),
            "kv_capacity": 64,
        },
        name="pengine_mesh",
    )
    engine.bind(module, params, state, partitioner=part)
    warm = engine.warmup()
    got = serve(engine, prompts)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert engine.compile_count == warm
