"""Decode-engine certification: token parity against the full-context
oracle, slot-refill compile discipline, KV-capacity truncation, and the
cached-attention numerics contract (docs/DESIGN.md §15).

The parity pin is the subsystem's load-bearing claim: every token the
incremental cached-attention path emits must equal the token
``greedy_decode`` (full-context recompute, the oracle) emits from the
same weights — including mid-stream slot refill (a new occupant's
prefill lands in pages a retired stream gave back) and the capacity
boundary. The engines here run a worst-case pool with the prefix cache
off (``make_engine``): prefix reuse is ``test_paged_kv.py``'s.
All CPU, thread-free (synchronous scheduler).
"""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.models.transformer import TransformerLM, greedy_decode
from zookeeper_tpu.serving.decode import (
    DecodeEngine,
    DecodeScheduler,
    allocate_page_pool,
    page_pool_bytes,
)

pytestmark = pytest.mark.serving

VOCAB = 53
SEQ_LEN = 64


def build_lm(num_layers=2, d_model=32, num_heads=4, max_seq_len=SEQ_LEN,
             seed=0, **fields):
    model = TransformerLM()
    configure(
        model,
        {
            "num_layers": num_layers,
            "d_model": d_model,
            "num_heads": num_heads,
            "max_seq_len": max_seq_len,
            "attention": "dense",
            **fields,
        },
        name="lm",
    )
    module = model.build((max_seq_len,), VOCAB)
    params, state = model.initialize(module, (max_seq_len,), seed=seed)
    variables = {"params": params, **dict(state or {})}
    return module, params, state, variables


def make_engine(module, params, state, *, slots=3, seq_buckets=(8, 16),
                kv_capacity=SEQ_LEN, partitioner=None, **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots,
            "seq_buckets": tuple(seq_buckets),
            "kv_capacity": kv_capacity,
            "prefix_cache": False,
            **conf,
        },
        name="engine",
    )
    engine.bind(module, params, state, partitioner=partitioner)
    return engine


def make_scheduler(engine, **conf):
    sched = DecodeScheduler()
    configure(sched, dict(conf), name="sched")
    sched.bind(engine)
    return sched


def oracle(module, variables, prompt, steps):
    """Full-context greedy continuation (generated tokens only)."""
    out = np.asarray(greedy_decode(module, variables, prompt[None], steps))
    return out[0, prompt.shape[0]:]


@pytest.fixture(scope="module")
def lm():
    return build_lm()


# -- the parity certification ---------------------------------------------


def test_incremental_decode_matches_full_context_oracle(lm):
    """Every generated token equals the full-context oracle's, for
    prompts of varying length across both seq buckets."""
    module, params, state, variables = lm
    engine = make_engine(module, params, state)
    engine.warmup()
    sched = make_scheduler(engine, max_new_tokens=12)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (1, 2, 7, 8, 9, 16)
    ]
    streams = [sched.submit(p, max_new_tokens=10) for p in prompts]
    sched.drain()
    for p, s in zip(prompts, streams):
        got = s.result()
        want = oracle(module, variables, p, 10)
        np.testing.assert_array_equal(got, want)
        assert s.finish_reason == "length"


def test_slot_refill_parity_and_zero_post_warmup_compiles(lm):
    """The acceptance pin: many more requests than slots — finished
    slots are REFILLED mid-stream (new prefills overwrite retired
    streams' KV rows) — and every stream stays token-exact with ZERO
    compiles after warmup."""
    module, params, state, variables = lm
    engine = make_engine(module, params, state, slots=3)
    warm = engine.warmup()
    assert warm == engine.compile_count
    sched = make_scheduler(engine)
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 17))).astype(np.int32)
        for _ in range(11)
    ]
    # Varying budgets => staggered finishes => real mid-flight refills.
    budgets = [int(rng.integers(1, 9)) for _ in prompts]
    streams = [
        sched.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)
    ]
    sched.drain()
    for p, b, s in zip(prompts, budgets, streams):
        np.testing.assert_array_equal(s.result(), oracle(module, variables, p, b))
    assert engine.compile_count == warm  # the zero-recompile pin
    assert engine.recompiles_detected == 0


def test_capacity_boundary_truncates_with_parity(lm):
    """A stream that reaches the per-slot KV capacity (the ring
    boundary) truncates cleanly with reason "capacity" — and every
    token UP TO the boundary is still oracle-exact."""
    module, params, state, variables = lm
    engine = make_engine(
        module, params, state, slots=2, seq_buckets=(8,), kv_capacity=16
    )
    engine.warmup()
    assert engine.capacity == 16
    sched = make_scheduler(engine)
    prompt = np.arange(1, 7, dtype=np.int32)  # 6 tokens, 10 fit after
    stream = sched.submit(prompt, max_new_tokens=64)
    sched.drain()
    got = stream.result()
    assert stream.finish_reason == "capacity"
    assert got.shape[0] == engine.token_limit - prompt.shape[0]
    np.testing.assert_array_equal(
        got, oracle(module, variables, prompt, got.shape[0])
    )


def test_positional_table_bounds_generation():
    """token_limit is min(capacity, positional table): a module built
    with a short table truncates there even with KV headroom."""
    module, params, state, variables = build_lm(max_seq_len=16)
    engine = make_engine(
        module, params, state, slots=1, seq_buckets=(8,), kv_capacity=64
    )
    engine.warmup()
    assert engine.position_cap == 16
    assert engine.token_limit == 16
    sched = make_scheduler(engine)
    prompt = np.arange(1, 5, dtype=np.int32)
    stream = sched.submit(prompt, max_new_tokens=64)
    sched.drain()
    got = stream.result()
    assert stream.finish_reason == "capacity"
    assert prompt.shape[0] + got.shape[0] == 16
    np.testing.assert_array_equal(
        got, oracle(module, variables, prompt, got.shape[0])
    )


def test_grouped_prefill_parity(lm):
    """prefill_buckets > 1: several queued prompts ride ONE bucketed
    prefill dispatch (incl. a partial group padded with dropped rows)
    and stay oracle-exact."""
    module, params, state, variables = lm
    engine = make_engine(
        module, params, state, slots=4, prefill_buckets=(2, 4)
    )
    warm = engine.warmup()
    # (prefill buckets x seq buckets) + decode + the page copy
    assert warm == 2 * 2 + 2
    sched = make_scheduler(engine)
    rng = np.random.default_rng(2)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 9))).astype(np.int32)
        for _ in range(3)  # 3 => one full pair + one padded partial
    ]
    streams = [sched.submit(p, max_new_tokens=6) for p in prompts]
    sched.drain()
    for p, s in zip(prompts, streams):
        np.testing.assert_array_equal(s.result(), oracle(module, variables, p, 6))
    assert engine.compile_count == warm


# -- cached attention numerics --------------------------------------------


def test_cached_attention_matches_reference_row():
    """ops.cached_attention over a padded cache equals the full
    attention_reference row at the same position (the op-for-op
    numerics mirror the docstring commits to)."""
    import jax.numpy as jnp

    from zookeeper_tpu.ops import attention_reference, cached_attention

    rng = np.random.default_rng(3)
    b, s, h, d, cap = 2, 9, 4, 8, 16
    q = rng.normal(size=(b, s, h, d)).astype(np.float32)
    k = rng.normal(size=(b, s, h, d)).astype(np.float32)
    v = rng.normal(size=(b, s, h, d)).astype(np.float32)
    full = np.asarray(attention_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True
    ))
    # Cache rows past the live region hold garbage that MUST be masked.
    k_cache = rng.normal(size=(b, cap, h, d)).astype(np.float32)
    v_cache = rng.normal(size=(b, cap, h, d)).astype(np.float32)
    pos = s - 1
    k_cache[:, : pos + 1] = k[:, : pos + 1]
    v_cache[:, : pos + 1] = v[:, : pos + 1]
    got = np.asarray(cached_attention(
        jnp.asarray(q[:, pos : pos + 1]),
        jnp.asarray(k_cache),
        jnp.asarray(v_cache),
        jnp.full((b,), pos, np.int32),
    ))
    np.testing.assert_allclose(got[:, 0], full[:, pos], rtol=0, atol=2e-6)


# -- cache state ----------------------------------------------------------


def test_cache_allocation_and_accounting(lm):
    # 12 pages of 4 rows; a row folds 4 heads of 8 into one 128-lane
    # register.
    cache = allocate_page_pool(2, 12, 4, 4, 8, np.float32)
    assert len(cache) == 2
    assert cache[0]["k"].shape == (12, 1, 4, 128)
    assert page_pool_bytes(2, 12, 4, 4, 8, 4) == 2 * 2 * 12 * 4 * 128 * 4
    with pytest.raises(ValueError, match="num_pages >= 1"):
        allocate_page_pool(2, 0, 4, 4, 8, np.float32)
    # The engine's accounting is the allocator's: the worst-case pool
    # (slots x capacity / page_size), pages counted as handed out.
    module, params, state, _ = lm
    engine = make_engine(
        module, params, state, slots=3, kv_capacity=16, page_size=4,
        seq_buckets=(8,),
    )
    assert engine.page_pool.num_pages == 3 * 4
    assert engine.kv_cache_nbytes == page_pool_bytes(2, 12, 4, 4, 8, 4)
    assert engine.kv_pages_in_use() == 0
    # ceil(5/4) + ceil(8/4)
    assert engine.admit_slot(0, np.arange(1, 6, dtype=np.int32))
    assert engine.admit_slot(1, np.arange(1, 9, dtype=np.int32))
    assert engine.kv_pages_in_use() == 2 + 2
    engine.release_slot(0)
    assert engine.kv_pages_in_use() == 2


def test_capacity_page_alignment(lm):
    module, params, state, _ = lm
    engine = make_engine(
        module, params, state, kv_capacity=33, page_size=16,
        seq_buckets=(8,),
    )
    assert engine.capacity == 48  # 33 rounded up to the page boundary


# -- config validation ----------------------------------------------------


def test_bind_validation(lm):
    module, params, state, _ = lm

    def expect(match, **conf):
        engine = DecodeEngine()
        configure(engine, dict(conf), name="engine")
        with pytest.raises(ValueError, match=match):
            engine.bind(module, params, state)

    expect("seq_buckets", seq_buckets=())
    expect("seq_buckets", seq_buckets=(16, 8))
    expect("seq_buckets", seq_buckets=(0, 8))
    expect("prefill_buckets", prefill_buckets=(4, 2))
    expect("slots", slots=0)
    expect("exceeds", slots=2, prefill_buckets=(4,))
    expect("page_size", page_size=0)
    expect("kv_capacity", kv_capacity=0)
    expect("exceeds the KV capacity", seq_buckets=(32,), kv_capacity=16)
    expect("positional table", seq_buckets=(128,), kv_capacity=256)
    expect("only KV layout", kv_layout="slots")
    expect("decode_attention", decode_attention="module")

    class NotALM:
        pass

    engine = DecodeEngine()
    configure(engine, {}, name="engine")
    with pytest.raises(ValueError, match="prefill"):
        engine.bind(NotALM(), params, state)


def test_unbound_engine_raises():
    engine = DecodeEngine()
    configure(engine, {}, name="engine")
    with pytest.raises(RuntimeError, match="not bound"):
        engine.warmup()


def test_prompt_dispatch_validation(lm):
    module, params, state, _ = lm
    engine = make_engine(module, params, state)
    engine.warmup()
    with pytest.raises(ValueError, match="exceeds the largest seq bucket"):
        engine.seq_bucket_for(17)
    with pytest.raises(ValueError, match="unique"):
        engine.prefill(
            [np.array([1], np.int32), np.array([2], np.int32)], [0, 0]
        )
    with pytest.raises(ValueError, match="empty prompt"):
        engine.prefill([np.zeros((0,), np.int32)], [0])
    with pytest.raises(ValueError, match="slots"):
        engine.decode(np.zeros((5,), np.int32), np.zeros((5,), np.int32))


def test_decode_step_takes_its_tpu_options_on_a_tpu_only(lm, monkeypatch):
    """The decode step, and no other program, is compiled with the
    engine's TPU compiler options, and only where the backend is a TPU
    (no other compiler knows them)."""
    import jax

    from zookeeper_tpu.serving.decode import engine as engine_module

    module, params, state, _ = lm
    compile_ = jax.stages.Lowered.compile
    seen = []

    def recording(self, compiler_options=None):
        seen.append(compiler_options)
        return compile_(self)

    monkeypatch.setattr(jax.stages.Lowered, "compile", recording)
    make_engine(module, params, state).warmup()
    assert seen and not any(seen)
    seen.clear()
    monkeypatch.setattr(engine_module, "_compiles_for_tpu", lambda: True)
    engine = make_engine(module, params, state)
    engine.warmup()
    assert [o for o in seen if o] == [
        engine_module._DECODE_STEP_TPU_OPTIONS
    ]
    assert len(seen) == engine.compile_count > 1


# -- weight swap (engine level) -------------------------------------------


def test_check_swap_rejects_mismatched_weights(lm):
    module, params, state, _ = lm
    engine = make_engine(module, params, state)
    other_module, other_params, other_state, _ = build_lm(d_model=64)
    with pytest.raises(ValueError, match="shape/dtype mismatch"):
        engine.check_swap(other_params, other_state)


def test_swap_weights_changes_tokens_without_recompiling(lm):
    module, params, state, variables = lm
    engine = make_engine(module, params, state, slots=1, seq_buckets=(8,))
    warm = engine.warmup()
    _, params_b, state_b, variables_b = build_lm(seed=7)
    sched = make_scheduler(engine)
    prompt = np.arange(1, 6, dtype=np.int32)
    a = sched.generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(a, oracle(module, variables, prompt, 6))
    engine.swap_weights(params_b, state_b)
    b = sched.generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(
        b, oracle(module, variables_b, prompt, 6)
    )
    assert engine.compile_count == warm


# -- mesh legs (slow: multi-device compiles) ------------------------------


@pytest.mark.slow
def test_decode_parity_on_dp_tp_mesh():
    """Page pool sharded (heads on model; per-slot operands on data) on a
    2x4 mesh:
    token-exact vs the single-device oracle, zero post-warmup
    compiles. The dryrun_multichip leg re-certifies this under the
    clean-SPMD harness."""
    from zookeeper_tpu.parallel.partitioner import MeshPartitioner

    module, params, state, variables = build_lm()
    part = MeshPartitioner()
    configure(
        part,
        {
            "mesh_shape": (2, 4),
            "mesh_axes": ("data", "model"),
            "data_axes": ("data",),
        },
        name="part",
    )
    part.setup()
    engine = make_engine(
        module, params, state, slots=4, partitioner=part
    )
    warm = engine.warmup()
    sched = make_scheduler(engine)
    rng = np.random.default_rng(4)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(2, 15))).astype(np.int32)
        for _ in range(6)
    ]
    streams = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.drain()
    for p, s in zip(prompts, streams):
        np.testing.assert_array_equal(s.result(), oracle(module, variables, p, 8))
    assert engine.compile_count == warm


@pytest.mark.slow
def test_decode_kernel_parity_on_dp_tp_mesh():
    """The PALLAS pool decode kernel under the sharded path: slots on
    'data', heads on 'model' via the shard_map-composed
    ``sharded_pool_paged_decode_attention`` (docs/DESIGN.md §17) — still
    token-exact vs the full-context oracle, zero post-warmup compiles.
    The dryrun_multichip decode leg re-certifies this with the SPMD log
    asserted clean."""
    from zookeeper_tpu.parallel.partitioner import MeshPartitioner

    module, params, state, variables = build_lm()
    part = MeshPartitioner()
    configure(
        part,
        {
            "mesh_shape": (2, 4),
            "mesh_axes": ("data", "model"),
            "data_axes": ("data",),
        },
        name="part",
    )
    part.setup()
    engine = make_engine(
        module, params, state, slots=4, partitioner=part,
        decode_attention="pallas",
    )
    assert engine.decode_attention_flavor == "pallas"
    warm = engine.warmup()
    sched = make_scheduler(engine)
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(2, 15))).astype(np.int32)
        for _ in range(6)
    ]
    streams = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.drain()
    for p, s in zip(prompts, streams):
        np.testing.assert_array_equal(
            s.result(), oracle(module, variables, p, 8)
        )
    assert engine.compile_count == warm
    assert not engine._cache[0]["k"].sharding.is_fully_replicated

    # The indivisible-geometry posture with the kernel selected:
    # slots=3 cannot shard over the 2-way data axis, the cache goes
    # REPLICATED, and the kernel runs under fully-replicated shard_map
    # specs — still token-exact.
    engine3 = make_engine(
        module, params, state, slots=3, partitioner=part,
        decode_attention="pallas",
    )
    assert engine3.decode_attention_flavor == "pallas"
    assert engine3._cache_replicated
    engine3.warmup()
    p = np.arange(1, 8, dtype=np.int32)
    np.testing.assert_array_equal(
        make_scheduler(engine3).generate(p, max_new_tokens=6),
        oracle(module, variables, p, 6),
    )


@pytest.mark.slow
def test_indivisible_cache_falls_back_replicated(caplog):
    """slots=3 on a 2-way data mesh cannot shard — the engine warns and
    decodes with a REPLICATED cache, still token-exact."""
    import logging

    from zookeeper_tpu.parallel.partitioner import MeshPartitioner

    module, params, state, variables = build_lm()
    part = MeshPartitioner()
    configure(
        part,
        {
            "mesh_shape": (2, 4),
            "mesh_axes": ("data", "model"),
            "data_axes": ("data",),
        },
        name="part",
    )
    part.setup()
    with caplog.at_level(logging.WARNING):
        engine = make_engine(
            module, params, state, slots=3, partitioner=part
        )
    assert any("REPLICATED" in r.message for r in caplog.records)
    engine.warmup()
    sched = make_scheduler(engine)
    prompt = np.arange(1, 8, dtype=np.int32)
    np.testing.assert_array_equal(
        sched.generate(prompt, max_new_tokens=6),
        oracle(module, variables, prompt, 6),
    )
