"""Speculative-decode certification (docs/DESIGN.md §18): the headline
pin is the repo's strongest kind — speculative greedy output is
BIT-IDENTICAL (token for token) to plain greedy decode, against the
full-context ``greedy_decode`` oracle, across mid-stream slot refill,
EOS inside a draft window, ``max_new_tokens`` landing mid-window, and
capacity truncation; with zero post-warmup compiles on BOTH engines.

Two draft constructions cover both halves of the acceptance spectrum:

- ``random`` — an independently-initialized draft that (almost) never
  agrees with the teacher: every window exercises the REJECTION path,
  so the rollback-by-length contract (rejected rows never advanced
  over) is what keeps parity.
- ``zero_tail`` — the teacher's own first layers as the draft, with the
  teacher's extra blocks' ``proj``/``down`` kernels zeroed so those
  blocks contribute exactly 0.0 to the residual stream: teacher and
  draft compute the same argmax while the teacher still pays full
  per-layer compute. Acceptance pins ~1.0, exercising full-accept
  windows, the ``k+1``-token emission, and the draft catch-up append —
  and it is the bench's pinned high-acceptance workload.

All CPU, thread-free (synchronous scheduler).
"""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.serving.decode import (
    DecodeMetrics,
    DecodeScheduler,
    SpeculativeDecoding,
)

from tests.serving.test_decode_engine import (
    VOCAB,
    build_lm,
    make_engine,
    oracle,
)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def lm():
    return build_lm(num_layers=2)


@pytest.fixture(scope="module")
def random_draft():
    """Independent weights: acceptance ~0, every window rejects."""
    return build_lm(num_layers=1, seed=17)


def zero_tail_pair(num_layers=3, draft_layers=1, seed=3):
    """The pinned high-acceptance construction: teacher with
    ``num_layers`` blocks whose blocks past ``draft_layers`` have
    zeroed ``proj``/``down`` kernels (residual contribution exactly
    0.0), and a draft that IS the teacher's first ``draft_layers``
    blocks + embed/pos/final-norm. Same argmax by construction, full
    per-layer teacher compute."""
    import jax.numpy as jnp

    t_module, t_params, t_state, _ = build_lm(
        num_layers=num_layers, seed=seed
    )
    t_params = dict(t_params)
    for i in range(draft_layers, num_layers):
        block = {**t_params[f"block{i}"]}
        block["proj"] = {"kernel": jnp.zeros_like(block["proj"]["kernel"])}
        block["down"] = {"kernel": jnp.zeros_like(block["down"]["kernel"])}
        t_params[f"block{i}"] = block
    t_variables = {"params": t_params, **dict(t_state or {})}
    d_module, d_params, d_state, _ = build_lm(
        num_layers=draft_layers, seed=seed + 1
    )
    d_params = dict(d_params)
    for key in d_params:
        d_params[key] = t_params[key]
    return (
        (t_module, t_params, t_state, t_variables),
        (d_module, d_params, d_state),
    )


def make_spec(engine, draft, k=3):
    d_module, d_params, d_state = draft[0], draft[1], draft[2]
    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": k}, name="spec")
    spec.bind(engine, d_module, d_params, d_state)
    return spec


def make_sched(engine, spec, metrics=False, **conf):
    m = None
    if metrics:
        m = DecodeMetrics()
        configure(m, {}, name="spec_metrics")
    s = DecodeScheduler()
    configure(s, dict(conf), name="spec_sched")
    s.bind(engine, metrics=m, speculative=spec)
    return s, m


# -- THE parity certification ----------------------------------------------


@pytest.mark.parametrize("draft_kind", ["random", "zero_tail"])
@pytest.mark.parametrize("k", [1, 3])
def test_speculative_token_identical_to_plain_greedy(
    lm, random_draft, draft_kind, k
):
    """Every token the speculative schedule emits equals the
    full-context greedy oracle's — including mid-stream slot REFILL
    (more requests than slots, staggered budgets) — at both ends of
    the acceptance spectrum, with zero post-warmup compiles on both
    engines. Plain greedy decode is certified against the same oracle
    (test_decode_engine), so spec == oracle == plain, token for
    token."""
    if draft_kind == "zero_tail":
        teacher, draft = zero_tail_pair()
        module, params, state, variables = teacher
    else:
        module, params, state, variables = lm
        draft = random_draft
    engine = make_engine(module, params, state, slots=3)
    engine.warmup()
    spec = make_spec(engine, draft, k=k)
    warm = engine.compile_count
    dwarm = spec.draft_engine.compile_count
    sched, _ = make_sched(engine, spec)
    rng = np.random.default_rng(2)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 17))).astype(np.int32)
        for _ in range(9)
    ]
    budgets = [int(rng.integers(1, 13)) for _ in prompts]
    streams = [
        sched.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)
    ]
    sched.drain()
    for p, b, s in zip(prompts, budgets, streams):
        np.testing.assert_array_equal(
            s.result(), oracle(module, variables, p, b)
        )
    assert engine.compile_count == warm
    assert spec.draft_engine.compile_count == dwarm
    assert engine.recompiles_detected == 0
    assert spec.draft_engine.recompiles_detected == 0
    if draft_kind == "zero_tail":
        # The construction's point: near-total agreement, so windows
        # commit full k+1 emissions (the catch-up/pending path runs).
        assert spec.acceptance_rate > 0.9
    else:
        assert spec.acceptance_rate < 0.5  # rejection path exercised


def test_eos_inside_draft_window(lm):
    """EOS landing MID-WINDOW (between two accepted positions of one
    verify) stops the stream WITH the eos token delivered and discards
    the window's surplus; other slots are unaffected; output is
    oracle-exact."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, draft, k=4)
    sched, _ = make_sched(engine, spec)
    prompt = np.arange(1, 6, dtype=np.int32)
    want = oracle(module, variables, prompt, 12)
    # Pick an eos position that cannot be a window boundary: windows
    # commit up to k+1=5 tokens, so a token at index 2 lands mid-window
    # under full acceptance.
    eos = int(want[2])
    steps_to_eos = int(np.argmax(want == eos)) + 1
    stream = sched.submit(prompt, max_new_tokens=12, eos_token=eos)
    other = sched.submit(prompt[:2], max_new_tokens=9)
    sched.drain()
    got = stream.result()
    assert stream.finish_reason == "eos"
    assert got.shape[0] == steps_to_eos and got[-1] == eos
    np.testing.assert_array_equal(got, want[:steps_to_eos])
    np.testing.assert_array_equal(
        other.result(), oracle(module, variables, prompt[:2], 9)
    )


def test_max_new_tokens_lands_mid_window(lm):
    """A generation budget that is not a multiple of the window size
    finishes mid-window with reason "length" and exactly the budgeted
    token count — surplus accepted tokens are discarded, and a
    follow-up stream in the same slot is unaffected by the discarded
    rows (rollback-by-length)."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(module, params, state, slots=1)
    engine.warmup()
    spec = make_spec(engine, draft, k=3)  # window 4
    sched, _ = make_sched(engine, spec)
    prompt = np.arange(2, 9, dtype=np.int32)
    for budget in (2, 5, 6):  # none divisible by window=4... 2,5,6
        stream = sched.submit(prompt, max_new_tokens=budget)
        sched.drain()
        got = stream.result()
        assert stream.finish_reason == "length"
        assert got.shape[0] == budget
        np.testing.assert_array_equal(
            got, oracle(module, variables, prompt, budget)
        )


def test_capacity_truncation_with_speculation(lm):
    """A stream nearing its token limit: speculation becomes
    ineligible (a clamped multi-token append would land on live rows),
    the iteration falls back to plain decode — with the DRAFT kept in
    sync through the fallback — and the stream truncates at EXACTLY
    token_limit with every token oracle-exact."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(
        module, params, state, slots=2, seq_buckets=(8,), kv_capacity=16
    )
    engine.warmup()
    assert engine.token_limit == 16
    spec = make_spec(engine, draft, k=3)
    sched, _ = make_sched(engine, spec)
    prompt = np.arange(1, 7, dtype=np.int32)  # 6 tokens, 10 fit after
    stream = sched.submit(prompt, max_new_tokens=64)
    # A second, shorter stream shares the slot array across the other
    # slot: the per-iteration fallback must keep IT exact too.
    short = sched.submit(prompt[:3], max_new_tokens=4)
    sched.drain()
    got = stream.result()
    assert stream.finish_reason == "capacity"
    assert got.shape[0] == engine.token_limit - prompt.shape[0]
    np.testing.assert_array_equal(
        got, oracle(module, variables, prompt, got.shape[0])
    )
    np.testing.assert_array_equal(
        short.result(), oracle(module, variables, prompt[:3], 4)
    )


def test_mixed_accept_lengths_without_drain(lm, random_draft):
    """Slots accept different prefix lengths in the same window (the
    random draft guarantees spread): commits are pure host bookkeeping
    — no drain, no recompile — and every stream stays exact."""
    module, params, state, variables = lm
    engine = make_engine(module, params, state, slots=3)
    engine.warmup()
    spec = make_spec(engine, random_draft, k=4)
    warm = engine.compile_count
    sched, m = make_sched(engine, spec, metrics=True)
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(2, 15))).astype(np.int32)
        for _ in range(6)
    ]
    streams = [sched.submit(p, max_new_tokens=9) for p in prompts]
    sched.drain()
    for p, s in zip(prompts, streams):
        np.testing.assert_array_equal(
            s.result(), oracle(module, variables, p, 9)
        )
    assert engine.compile_count == warm
    totals = m.totals
    assert totals["spec_draft_tokens_total"] > 0
    assert totals["tokens_total"] == sum(
        len(s.result()) for s in streams
    )


# -- module-level units ----------------------------------------------------


def pool_cache(module, slots, page_size, max_pages):
    """A zeroed pool of ``slots x max_pages`` pages and the table that
    gives slot ``s`` the pages ``s * max_pages ...`` in order."""
    import jax.numpy as jnp

    from zookeeper_tpu.serving.decode import allocate_page_pool

    cache = allocate_page_pool(
        int(module.num_layers), slots * max_pages, page_size,
        int(module.num_heads), int(module.head_dim), jnp.float32,
    )
    table = jnp.arange(slots * max_pages, dtype=jnp.int32).reshape(
        slots, max_pages
    )
    return cache, table


def test_multi_token_append_and_rollback_module_unit(lm):
    """``decode_verify_paged`` vs the same window fed token-by-token
    through ``decode_step_paged``: argmax-identical logits at every
    position and ULP-identical cache rows; then ROLLBACK — committing
    only a prefix (advancing lengths short of the window) and decoding
    onward equals a run that never wrote the rejected rows, i.e.
    garbage rows beyond length are invisible (the §17 poisoned-row
    contract, exercised through the append path)."""
    import jax.numpy as jnp

    module, params, state, variables = lm
    b, ps, max_pages = 2, 8, 4
    cache, table = pool_cache(module, b, ps, max_pages)
    rng = np.random.default_rng(4)
    toks = rng.integers(1, VOCAB, size=(b, 12)).astype(np.int32)
    L, w = 5, 4  # the window crosses the first page boundary

    def step(c, j):
        lens = jnp.full((b,), j, jnp.int32)
        return module.apply(
            variables, jnp.asarray(toks[:, j]), lens, c, table,
            method="decode_step_paged",
        )

    c = cache
    for j in range(L):
        _, c = step(c, j)
    # One w-wide verify vs w sequential steps.
    c_seq = c
    seq_logits = []
    for j in range(L, L + w):
        lg, c_seq = step(c_seq, j)
        seq_logits.append(np.asarray(lg))
    v_logits, c_ver = module.apply(
        variables,
        jnp.asarray(toks[:, L : L + w]),
        jnp.full((b,), L, jnp.int32),
        c,
        table,
        method="decode_verify_paged",
    )
    assert np.array_equal(
        np.argmax(np.asarray(v_logits), -1),
        np.argmax(np.stack(seq_logits, 1), -1),
    )
    np.testing.assert_allclose(
        np.asarray(v_logits), np.stack(seq_logits, 1), rtol=0, atol=2e-6
    )
    for seq_layer, ver_layer in zip(c_seq, c_ver):
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(ver_layer[name]), np.asarray(seq_layer[name]),
                rtol=0, atol=2e-6,
            )
    # Rollback-by-length as an EQUALITY (the §17 poisoned-row idiom):
    # accept only the first window token (lengths advance to L+1) and
    # poison every row past it with +-1e9 garbage — the next
    # decode step must be BIT-identical to the step over the
    # un-poisoned rolled-back cache, i.e. rejected rows have exactly
    # zero influence once lengths never advanced over them.
    lg_rolled, _ = step(c_ver, L + 1)
    # Row j of slot s lives at (page s * max_pages + j // ps, j % ps).
    dead = np.arange(max_pages * ps) >= L + 2
    dead = np.tile(dead.reshape(max_pages, 1, ps, 1), (b, 1, 1, 1))
    poisoned = tuple(
        {
            "k": jnp.where(dead, 1e9, layer["k"]),
            "v": jnp.where(dead, -1e9, layer["v"]),
        }
        for layer in c_ver
    )
    lg_poisoned, _ = step(poisoned, L + 1)
    np.testing.assert_array_equal(
        np.asarray(lg_rolled), np.asarray(lg_poisoned)
    )
    # And the rolled-back continuation matches the never-speculated
    # path within the documented reassociation tolerance, argmax-exact
    # (the end-to-end certs pin full token-exactness through the real
    # schedule).
    c_clean = c
    _, c_clean = step(c_clean, L)  # only the accepted token appended
    lg_clean, _ = step(c_clean, L + 1)
    assert np.array_equal(
        np.argmax(np.asarray(lg_rolled), -1),
        np.argmax(np.asarray(lg_clean), -1),
    )
    np.testing.assert_allclose(
        np.asarray(lg_rolled), np.asarray(lg_clean), rtol=0, atol=2e-6
    )


def test_verify_attention_width_one_is_cached_attention():
    """``verify_cached_attention`` at w=1 is bitwise
    ``cached_attention`` (same ops, degenerate window), and each
    position of a wider window matches the single-position op at the
    shifted length within the documented reassociation tolerance."""
    import jax.numpy as jnp

    from zookeeper_tpu.ops import cached_attention, verify_cached_attention

    rng = np.random.default_rng(6)
    b, cap, h, d, w = 2, 16, 4, 8, 3
    q = jnp.asarray(rng.normal(size=(b, w, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, cap, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, cap, h, d)).astype(np.float32))
    lengths = jnp.asarray([3, 7], jnp.int32)
    one = cached_attention(q[:, :1], k, v, lengths)
    also_one = verify_cached_attention(q[:, :1], k, v, lengths)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(also_one))
    wide = np.asarray(verify_cached_attention(q, k, v, lengths))
    for j in range(w):
        ref = np.asarray(
            cached_attention(q[:, j : j + 1], k, v, lengths + j)
        )
        np.testing.assert_allclose(
            wide[:, j : j + 1], ref, rtol=0, atol=2e-6
        )


def test_window_write_crosses_pages_and_drops_past_the_table(lm):
    """The multi-row append through the page table: a window that
    crosses a page boundary lands in two pages, and the rows of a
    window that runs past a slot's table write NOWHERE (idle-slot
    safety: no other slot's page is touched)."""
    import jax.numpy as jnp

    module, params, state, variables = lm
    b, ps, max_pages, w = 2, 8, 2, 3
    cache, table = pool_cache(module, b, ps, max_pages)
    # Slot 0 writes rows 6, 7, 8 (pages 0 and 1); slot 1 starts at its
    # last row, 15: rows 16 and 17 lie past its two pages.
    lengths = jnp.asarray([6, 15], jnp.int32)
    _, out = module.apply(
        variables, jnp.ones((b, w), jnp.int32), lengths, cache, table,
        method="decode_verify_paged",
    )
    k = np.asarray(out[0]["k"])[:, 0]  # [pages, page_size, row_width]
    written = np.abs(k).sum(axis=-1) > 0
    want = np.zeros_like(written)
    want[0, 6:8] = True
    want[1, 0] = True
    want[3, 7] = True
    np.testing.assert_array_equal(written, want)


# -- engine/config validation ----------------------------------------------


def test_spec_bind_validation(lm, random_draft):
    module, params, state, _ = lm
    engine = make_engine(module, params, state)
    d_module, d_params, d_state, _ = random_draft
    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": 0}, name="bad_k")
    with pytest.raises(ValueError, match="k=0"):
        spec.bind(engine, d_module, d_params, d_state)

    # Vocab mismatch: proposals in a different token id space.
    from zookeeper_tpu.models.transformer import TransformerLM

    other = TransformerLM()
    configure(
        other,
        {
            "num_layers": 1, "d_model": 32, "num_heads": 4,
            "max_seq_len": 64, "attention": "dense",
        },
        name="other_vocab",
    )
    o_module = other.build((64,), VOCAB + 7)
    o_params, o_state = other.initialize(o_module, (64,), seed=0)
    spec2 = SpeculativeDecoding()
    configure(spec2, {"enabled": True}, name="bad_vocab")
    with pytest.raises(ValueError, match="vocab"):
        spec2.bind(engine, o_module, o_params, o_state)

    # Scheduler refuses a speculative binding of a DIFFERENT engine.
    engine_b = make_engine(module, params, state)
    engine_b.warmup()
    spec3 = SpeculativeDecoding()
    configure(spec3, {"enabled": True, "k": 2}, name="wrong_engine")
    spec3.bind(engine_b, d_module, d_params, d_state)
    sched = DecodeScheduler()
    configure(sched, {}, name="wrong_engine_sched")
    with pytest.raises(ValueError, match="SAME DecodeEngine"):
        sched.bind(engine, speculative=spec3)

    with pytest.raises(RuntimeError, match="not bound"):
        SpeculativeDecoding().status()

    # A draft with window layers: its slot takes the whole share of ONE
    # page group.
    windowed = TransformerLM()
    configure(
        windowed,
        {
            "num_layers": 2, "d_model": 32, "num_heads": 4,
            "max_seq_len": 64, "attention": "dense", "positions": "rope",
            "layer_types": ("window", "full"), "window": 8,
        },
        name="windowed_draft",
    )
    w_module = windowed.build((64,), VOCAB)
    w_params, w_state = windowed.initialize(w_module, (64,), seed=0)
    spec4 = SpeculativeDecoding()
    configure(spec4, {"enabled": True}, name="windowed_draft_spec")
    with pytest.raises(ValueError, match="window layers"):
        spec4.bind(engine, w_module, w_params, w_state)


def test_draft_pool_full_share_a_slot_and_leak_free(lm, random_draft):
    """The draft engine is a pool engine provisioned for the worst case
    with the prefix cache off: a slot takes its FULL share of pages at
    its draft prefill and hands it back when its stream retires, so no
    draft dispatch can wait on a page; after a run with refills (more
    requests than slots, some shed mid-window by their budgets) neither
    pool holds or leaks a page."""
    module, params, state, variables = lm
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, random_draft, k=3)
    draft = spec.draft_engine
    pool = draft.page_pool
    share = pool.max_pages_per_slot
    assert not draft.prefix_cache
    assert pool.num_pages == 2 * share
    sched, _ = make_sched(engine, spec)
    rng = np.random.default_rng(9)
    prompts = [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 17))).astype(np.int32)
        for _ in range(7)
    ]
    budgets = [8, 3, 11, 1, 6, 9, 2]
    streams = [
        sched.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)
    ]
    sched._step_once()
    # Both slots admitted: each owns every page of its share, whatever
    # its prompt's length.
    assert sched.active_slots == 2
    assert pool.counts.tolist() == [share, share]
    assert pool.free_pages == 0
    sched.drain()
    for p, b, s in zip(prompts, budgets, streams):
        np.testing.assert_array_equal(
            s.result(), oracle(module, variables, p, b)
        )
    for engine_pool in (pool, engine.page_pool):
        assert engine_pool.used_pages == 0
        assert engine_pool.leak_check() == 0


def test_verify_width_validation(lm):
    module, params, state, _ = lm
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    with pytest.raises(ValueError, match="width"):
        engine.warmup_verify(0)
    with pytest.raises(ValueError, match="verify expects"):
        engine.verify(np.zeros((2,), np.int32), np.zeros((2,), np.int32))


# -- observability ---------------------------------------------------------


def test_spec_metrics_status_and_requestlog(lm):
    """The zk_spec_* family (docs/DESIGN.md §18): counters + live
    acceptance gauge + per-window accept-length histogram render from
    the metrics registry; /statusz carries the speculative section;
    the stream's terminal RequestLog detail records accepted/proposed."""
    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    spec = make_spec(engine, draft, k=2)
    sched, m = make_sched(engine, spec, metrics=True)
    prompt = np.arange(1, 7, dtype=np.int32)
    stream = sched.submit(prompt, max_new_tokens=8)
    sched.drain()
    assert stream.result().shape[0] == 8

    totals = m.totals
    assert totals["spec_draft_tokens_total"] > 0
    assert 0 < totals["spec_accepted_tokens_total"] <= (
        totals["spec_draft_tokens_total"]
    )
    snap = m.snapshot()
    assert 0.0 < snap["spec_acceptance_rate"] <= 1.0

    # Every zk_spec_* instrument renders in exposition text.
    from zookeeper_tpu.observability.export import render_prometheus

    body = render_prometheus([m.registry])
    for series in (
        "zk_spec_draft_tokens_total",
        "zk_spec_accepted_tokens_total",
        "zk_spec_acceptance_rate",
        "zk_spec_accept_length_bucket",
    ):
        assert series in body, series

    status = sched.status()["speculative"]
    assert status["enabled"] and status["k"] == 2
    assert status["acceptance_rate"] > 0.9
    assert status["draft_recompiles_detected"] == 0

    tail = sched.request_log.tail(5)
    mine = [r for r in tail if r["rid"] == stream.rid]
    assert mine and "spec=" in mine[0]["detail"], mine

    # reset() zeroes in place (instrument identity preserved).
    m.reset()
    assert m.totals["spec_draft_tokens_total"] == 0


def test_plain_scheduler_unaffected(lm):
    """No speculative binding: the plain path is byte-for-byte the old
    behavior (no draft arrays consulted, no zk_spec_ samples)."""
    module, params, state, variables = lm
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    sched, m = make_sched(engine, None, metrics=True)
    p = np.arange(1, 6, dtype=np.int32)
    np.testing.assert_array_equal(
        sched.generate(p, max_new_tokens=5), oracle(module, variables, p, 5)
    )
    assert m.totals["spec_draft_tokens_total"] == 0
    assert sched.status()["speculative"] == {"enabled": False}


# -- config surface --------------------------------------------------------


def test_lm_serving_config_speculative_end_to_end(tmp_path):
    """LMServingConfig.speculative: fresh-init draft serves (flagged),
    the result line reports the resolved state, and an unavailable
    draft checkpoint degrades LOUDLY to plain decode."""
    from zookeeper_tpu.serving import LMServingConfig

    base = {
        "model.num_layers": 2, "model.d_model": 32, "model.num_heads": 4,
        "model.attention": "dense", "seq_len": 64, "vocab_size": 61,
        "engine.slots": 2, "engine.seq_buckets": (8,),
        "requests": 5, "max_prompt": 6, "new_tokens": 4,
        "verbose": False,
    }
    svc = LMServingConfig()
    configure(
        svc,
        {
            **base,
            "speculative.enabled": True,
            "speculative.k": 2,
            "speculative.draft_model.num_layers": 1,
            "speculative.draft_model.d_model": 32,
            "speculative.draft_model.num_heads": 4,
            "speculative.draft_model.attention": "dense",
        },
        name="svc_spec",
    )
    res = svc.run()
    assert res["speculative"] is True and res["spec_k"] == 2
    assert res["recompiles_after_warmup"] == 0
    assert res["spec_draft_tokens_total"] > 0

    degraded = LMServingConfig()
    configure(
        degraded,
        {
            **base,
            "speculative.enabled": True,
            "speculative.draft_checkpoint": str(tmp_path / "missing"),
        },
        name="svc_spec_degraded",
    )
    res2 = degraded.run()
    assert res2["speculative"] is False and res2["spec_k"] == 0
    assert res2["requests"] == 5  # the teacher service stayed up


# -- mesh leg (slow: multi-device compiles) --------------------------------


@pytest.mark.slow
def test_speculative_parity_on_dp_tp_mesh():
    """Both pools sharded through the same page_pool_sharding seam
    (slots on 'data', heads on 'model', 2x4 mesh): the speculative
    schedule stays token-exact vs the single-device oracle with zero
    post-warmup compiles on either engine."""
    from zookeeper_tpu.parallel.partitioner import MeshPartitioner

    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    part = MeshPartitioner()
    configure(
        part,
        {
            "mesh_shape": (2, 4),
            "mesh_axes": ("data", "model"),
            "data_axes": ("data",),
        },
        name="spec_part",
    )
    part.setup()
    engine = make_engine(module, params, state, slots=4, partitioner=part)
    engine.warmup()
    spec = make_spec(engine, draft, k=3)
    assert not spec.draft_engine._cache[0]["k"].sharding.is_fully_replicated
    warm = engine.compile_count
    dwarm = spec.draft_engine.compile_count
    sched, _ = make_sched(engine, spec)
    rng = np.random.default_rng(8)
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
    assert spec.draft_engine.compile_count == dwarm
    assert spec.acceptance_rate > 0.9
