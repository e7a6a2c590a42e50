"""Disaggregated-serving certification (docs/DESIGN.md §22): prefill
on one role engine, decode on another, KV pages streamed between the
pools. The headline pin is the repo's strongest kind — disagg greedy
output is TOKEN-IDENTICAL to the single-mesh ``DecodeScheduler`` (and
re-pinned against the full-context greedy oracle directly) through
real slot refill, on fp paged KV, int8 KV on both sides, and the
speculative schedule at both ends of the acceptance spectrum; with
zero post-warmup compiles on either role.

The chaos legs pin the refcount-custody contract: an injected
page-transfer failure or a prefill-role crash mid-handoff must leave
``leak_check() == 0`` on BOTH pools, fail only its victims (partial
tokens readable), and leave every survivor token-identical.

All CPU, thread-free (synchronous scheduler); the two roles overlap on
the single CPU device (``DisaggPartitioner``'s portable fallback), so
every protocol step — export, place, import, refcount handoff — runs
for real.
"""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.observability import trace
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving import (
    DeadlineExpiredError,
    DisaggPartitioner,
    DisaggScheduler,
    PageTransfer,
    PageTransferError,
    WorkerCrashedError,
)
from zookeeper_tpu.serving.decode import DecodeEngine, DecodeMetrics

from tests.serving.test_decode_engine import (
    VOCAB,
    build_lm,
    make_scheduler,
    oracle,
)
from tests.serving.test_speculative import make_spec, zero_tail_pair

pytestmark = pytest.mark.serving


def role_engine(module, params, state, *, name, slots=2,
                seq_buckets=(8, 16), kv_capacity=64, **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots,
            "seq_buckets": tuple(seq_buckets),
            "kv_capacity": kv_capacity,
            **conf,
        },
        name=f"dg_{name}",
    )
    engine.bind(module, params, state)
    return engine


def make_disagg(lm, *, lanes=2, slots=2, host_bounce=False, draft=None,
                k=3, metrics=False, warm=False, engine_conf=None,
                **sched_conf):
    """A full disagg stack on one device: (sched, prefill, decode,
    transfer, metrics)."""
    module, params, state, _ = lm
    engine_conf = dict(engine_conf or {})
    pre = role_engine(
        module, params, state, name="prefill", slots=lanes,
        prefill_buckets=(1, 2), **engine_conf,
    )
    dec = role_engine(
        module, params, state, name="decode", slots=slots,
        prefill_buckets=(1,), prefix_cache=False, **engine_conf,
    )
    if warm:
        pre.warmup()
        dec.warmup()
        pre.warmup_transfer()
        dec.warmup_transfer()
    m = None
    if metrics:
        m = DecodeMetrics()
        configure(m, {}, name="dg_metrics")
    transfer = PageTransfer()
    configure(transfer, {"host_bounce": host_bounce}, name="dg_transfer")
    transfer.bind(pre, dec, metrics=m)
    spec = make_spec(dec, draft, k=k) if draft is not None else None
    sched = DisaggScheduler()
    configure(sched, dict(sched_conf), name="dg_sched")
    sched.bind(pre, dec, transfer, metrics=m, speculative=spec)
    return sched, pre, dec, transfer, m


def leak_free(*engines):
    return all(e.page_pool.leak_check() == 0 for e in engines)


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(11)
    # > slots AND > lanes: later admissions refill freed prefill lanes
    # and freed decode slots mid-traffic, and transferred pages land in
    # recycled destination pages.
    return [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 16))).astype(
            np.int32
        )
        for _ in range(7)
    ]


# -- THE parity certification ----------------------------------------------


@pytest.mark.slow
def test_disagg_token_identical_to_single_mesh_and_oracle(lm, prompts):
    """Every token the disaggregated service emits equals the
    single-mesh paged DecodeScheduler's AND the full-context greedy
    oracle's, through prefill-lane refill, the page handoff, and
    decode-slot refill."""
    module, params, state, variables = lm
    sched, pre, dec, _, _ = make_disagg(lm)
    got = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.drain()
    single = role_engine(module, params, state, name="single")
    base = make_scheduler(single, max_new_tokens=8)
    want = [base.submit(p) for p in prompts]
    base.drain()
    for p, g, w in zip(prompts, got, want):
        np.testing.assert_array_equal(g.result(), w.result())
        np.testing.assert_array_equal(
            g.result(), oracle(module, variables, p, 8)
        )
    assert leak_free(pre, dec, single)


def test_disagg_int8_token_identical_to_single_mesh_int8(lm, prompts):
    """int8 KV on BOTH roles: quantized rows transfer verbatim, so the
    disagg stream equals the single-mesh int8 stream token for token
    (int8-vs-fp parity is the paged suite's contract, not this one's)."""
    module, params, state, _ = lm
    sched, pre, dec, _, _ = make_disagg(
        lm, engine_conf={"kv_quant": "int8"}
    )
    got = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.drain()
    single = role_engine(
        module, params, state, name="single_i8", kv_quant="int8"
    )
    base = make_scheduler(single, max_new_tokens=8)
    want = [base.submit(p) for p in prompts]
    base.drain()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.result(), w.result())
    assert leak_free(pre, dec)


@pytest.mark.slow
@pytest.mark.parametrize("draft_kind", ["random", "zero_tail"])
def test_disagg_speculative_token_identical(draft_kind, prompts):
    """Speculative decoding rides the disaggregated decode loop
    unchanged: token-identical to the full-context oracle at BOTH ends
    of the acceptance spectrum (random draft = every window rejects;
    zero-tail draft = windows fully accept)."""
    if draft_kind == "zero_tail":
        teacher, draft = zero_tail_pair()
    else:
        teacher = build_lm(num_layers=2)
        draft = build_lm(num_layers=1, seed=17)
    module, params, state, variables = teacher
    sched, pre, dec, _, _ = make_disagg(teacher, draft=draft, k=3)
    got = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.drain()
    for p, g in zip(prompts, got):
        np.testing.assert_array_equal(
            g.result(), oracle(module, variables, p, 8)
        )
    if draft_kind == "zero_tail":
        assert sched._speculative.acceptance_rate > 0.9
    assert leak_free(pre, dec)


@pytest.mark.slow
def test_host_bounce_path_token_identical_and_counted(lm, prompts):
    """``transfer.host_bounce=True`` forces the portable host path:
    same tokens, every handoff counted as a bounce."""
    module, params, state, variables = lm
    sched, pre, dec, transfer, _ = make_disagg(lm, host_bounce=True)
    got = [sched.submit(p, max_new_tokens=6) for p in prompts[:4]]
    sched.drain()
    for p, g in zip(prompts, got):
        np.testing.assert_array_equal(
            g.result(), oracle(module, variables, p, 6)
        )
    status = transfer.status()
    assert status["host_bounce_forced"] is True
    assert status["host_bounces"] == status["handoffs_total"] > 0
    assert leak_free(pre, dec)


@pytest.mark.slow
def test_compile_free_steady_state_on_both_roles(lm, prompts):
    """After warmup (role programs + both transfer halves), serving
    never compiles again on EITHER engine — the §22 twin of the
    single-mesh AOT discipline."""
    sched, pre, dec, transfer, _ = make_disagg(lm, warm=True)
    pre_c, dec_c = pre.compile_count, dec.compile_count
    streams = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.drain()
    assert all(s.result().shape[0] == 8 or s.done for s in streams)
    assert transfer.handoffs >= len(prompts) - 1
    assert pre.compile_count == pre_c
    assert dec.compile_count == dec_c
    assert pre.recompiles_detected == 0
    assert dec.recompiles_detected == 0


# -- accounting / observability seams --------------------------------------


def test_transfer_metrics_and_status(lm, prompts):
    sched, pre, dec, transfer, m = make_disagg(lm, metrics=True)
    streams = [sched.submit(p, max_new_tokens=4) for p in prompts[:5]]
    sched.drain()
    [s.result() for s in streams]
    assert m.totals["transfer_handoffs_total"] == 5
    assert m.totals["transfer_pages_total"] >= 5
    assert m.totals["transfer_bytes"] > 0
    snap = m.snapshot()
    assert snap["transfer_p50_ms"] >= 0
    assert snap["transfer_p99_ms"] >= snap["transfer_p50_ms"]
    ts = transfer.status()
    assert ts["handoffs_total"] == 5
    assert ts["pages_total"] == m.totals["transfer_pages_total"]
    assert ts["bytes_total"] == m.totals["transfer_bytes"]
    assert ts["transfer_ms_p50"] > 0
    st = sched.status()
    assert st["role_topology"] == "disagg"
    assert st["prefill"]["lanes"] == 2
    assert st["prefill"]["busy_lanes"] == 0
    assert st["prefill"]["kv_pool"]["num_pages"] > 0
    assert st["transfer"]["handoffs_total"] == 5


def test_request_log_records_completing_role(lm):
    """Terminal summaries carry the role that completed dispatch:
    "decode" for a stream that crossed the seam, "prefill" for one
    finished by its first token (never transferred)."""
    sched, pre, dec, _, _ = make_disagg(lm)
    crossed = sched.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
    first_only = sched.submit(np.array([4, 5], np.int32), max_new_tokens=1)
    sched.drain()
    crossed.result(), first_only.result()
    by_rid = {r["rid"]: r for r in sched.request_log.tail()}
    assert by_rid[crossed.rid]["role"] == "decode"
    assert by_rid[first_only.rid]["role"] == "prefill"
    assert by_rid[crossed.rid]["outcome"] == "ok"


def test_rid_flow_spans_prefill_transfer_decode(lm):
    """One request's rid links the whole §22 chain in the Chrome
    trace: prefill dispatch -> park -> page_transfer -> decode admit
    -> finish, with flow start/finish present."""
    prior = trace.get_tracer()
    trace.install(trace.Tracer(4096))
    try:
        sched, _, _, _, _ = make_disagg(lm)
        stream = sched.submit(
            np.array([1, 2, 3, 4], np.int32), max_new_tokens=4
        )
        sched.drain()
        stream.result()
        doc = trace.to_chrome_trace()
        names = [
            e["name"]
            for e in doc["traceEvents"]
            if e.get("args", {}).get("rid") == stream.rid
        ]
        for name in ("disagg_prefill_dispatch", "disagg_prefill_park",
                     "page_transfer", "disagg_decode_admit",
                     "decode_stream_finish"):
            assert name in names, (name, names)
        phases = {
            e["ph"]
            for e in doc["traceEvents"]
            if e.get("cat") == "rid" and e["id"] == stream.rid
        }
        assert phases >= {"s", "f"}
    finally:
        trace.install(prior)


def test_queued_deadline_semantics_inherit(lm):
    """deadline_ms=0 = expired-by-construction: the inherited queue
    sweep fails it before any prefill; live traffic unaffected."""
    sched, pre, dec, _, m = make_disagg(lm, metrics=True)
    p = np.array([1, 2, 3], np.int32)
    doomed = sched.submit(p, max_new_tokens=4, deadline_ms=0)
    alive = sched.submit(p, max_new_tokens=4)
    sched.drain()
    with pytest.raises(DeadlineExpiredError):
        doomed.result()
    assert doomed.tokens_so_far.shape[0] == 0
    assert alive.result().shape[0] == 4
    assert m.totals["deadline_expired_total"] == 1
    assert leak_free(pre, dec)


def test_close_fails_parked_and_lane_streams_without_leaks(lm):
    """close() with handoffs still parked: pending streams fail
    cleanly, both pools leak-free."""
    sched, pre, dec, _, _ = make_disagg(lm, slots=1)
    streams = [
        sched.submit(np.array([1, 2, 3], np.int32), max_new_tokens=32)
        for _ in range(3)
    ]
    # One synchronous iteration: prefill admits, parks, one handoff
    # lands; the rest stay parked/queued.
    sched._step_once()
    sched.close()
    assert any(s.done and s._error is not None for s in streams)
    for s in streams:
        assert s.done
    assert leak_free(pre, dec)


# -- construction validation ----------------------------------------------


def test_transfer_bind_rejects_bad_geometry(lm):
    module, params, state, _ = lm
    paged = role_engine(module, params, state, name="v_paged")
    t = PageTransfer()
    configure(t, {}, name="dg_v_t")
    # An engine configured with no layout key is a pool engine: a role.
    plain = DecodeEngine()
    configure(
        plain,
        {"slots": 2, "seq_buckets": (8, 16), "kv_capacity": 64},
        name="dg_v_plain",
    )
    plain.bind(module, params, state)
    t.bind(plain, paged)
    other = role_engine(
        module, params, state, name="v_ps", page_size=8
    )
    with pytest.raises(ValueError, match="page_size|transfer_width"):
        t.bind(paged, other)
    unbound = PageTransfer()
    configure(unbound, {}, name="dg_v_unbound")
    with pytest.raises(RuntimeError, match="not bound"):
        unbound.move([0], [0])


def test_scheduler_bind_rejects_mismatched_pair(lm):
    module, params, state, _ = lm
    pre = role_engine(module, params, state, name="v_pre")
    dec = role_engine(module, params, state, name="v_dec")
    other = role_engine(module, params, state, name="v_other")
    t = PageTransfer()
    configure(t, {}, name="dg_v_pair")
    t.bind(other, dec)
    sched = DisaggScheduler()
    configure(sched, {}, name="dg_v_sched")
    with pytest.raises(ValueError, match="different engine pair"):
        sched.bind(pre, dec, t)
    narrow = role_engine(
        module, params, state, name="v_narrow", seq_buckets=(8, 48)
    )
    t2 = PageTransfer()
    configure(t2, {}, name="dg_v_pair2")
    with pytest.raises(ValueError, match="transfer_width"):
        t2.bind(narrow, dec)


def test_partitioner_validates_and_falls_back_overlapping():
    bad = DisaggPartitioner()
    configure(bad, {"prefill_devices": 0}, name="dg_part_bad")
    with pytest.raises(ValueError, match="must be"):
        bad.setup()
    import jax

    huge = DisaggPartitioner()
    configure(
        huge,
        {"prefill_devices": len(jax.devices()) + 1},
        name="dg_part_huge",
    )
    with pytest.raises(ValueError, match="exceed"):
        huge.setup()
    part = DisaggPartitioner()
    configure(part, {}, name="dg_part_auto")
    part.setup()
    desc = part.describe()
    assert part.prefill.mesh is not None
    assert part.decode.mesh is not None
    if len(jax.devices()) == 1:
        # The portable fallback: both roles on device 0, flagged.
        assert not part.disjoint and not desc["disjoint"]
        assert desc["prefill_devices"] == desc["decode_devices"]
    else:
        assert part.disjoint == desc["disjoint"]
    # The ABC delegation surface answers with the DECODE role's mesh.
    assert part.mesh is part.decode.mesh


# -- chaos: the refcount-custody contract ----------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_injected_transfer_failure_is_victim_only_and_leak_free(
    lm, prompts
):
    """FaultPlan.fail_page_transfer: the first handoff's stream fails
    with PageTransferError — its prefill-delivered first token
    readable in partials, its adopted decode pages unwound — while
    every other stream serves token-identical to the oracle and BOTH
    pools finish leak-free."""
    module, params, state, variables = lm
    sched, pre, dec, _, m = make_disagg(lm, metrics=True)
    with faults.injected(FaultPlan(fail_page_transfer=1)):
        streams = [
            sched.submit(p, max_new_tokens=8) for p in prompts[:5]
        ]
        sched.drain()
    failed = [s for s in streams if s._error is not None]
    assert len(failed) == 1
    victim = failed[0]
    with pytest.raises(PageTransferError, match="fail_page_transfer"):
        victim.result()
    # First token was delivered at prefill — partials readable.
    assert victim.tokens_so_far.shape[0] == 1
    for p, s in zip(prompts, streams):
        if s is victim:
            continue
        np.testing.assert_array_equal(
            s.result(), oracle(module, variables, p, 8)
        )
    assert leak_free(pre, dec)
    assert m.totals["transfer_handoffs_total"] == 4
    # The service keeps working after the injection drained.
    again = sched.submit(prompts[0], max_new_tokens=4)
    sched.drain()
    np.testing.assert_array_equal(
        again.result(), oracle(module, variables, prompts[0], 4)
    )
    assert leak_free(pre, dec)


@pytest.mark.slow
@pytest.mark.chaos
def test_prefill_role_crash_mid_handoff_decode_side_survives(lm):
    """FaultPlan.prefill_role_crash_at=N: the prefill role dies
    mid-handoff AFTER a stream already crossed into decode. The
    crossed stream keeps decoding to a token-identical finish (its
    slot uncorrupted), every prefill-side stream fails cleanly with
    partials readable, queued work serves on the recovered role, and
    BOTH pools finish leak-free."""
    module, params, state, variables = lm
    prompts = [
        np.array([1, 2, 3, 4, 5], np.int32),
        np.array([6, 7, 8], np.int32),
        np.array([9, 10, 11, 12], np.int32),
        np.array([13, 14], np.int32),
    ]
    sched, pre, dec, _, m = make_disagg(lm, metrics=True)
    with faults.injected(FaultPlan(prefill_role_crash_at=2)):
        streams = [sched.submit(p, max_new_tokens=8) for p in prompts]
        sched.drain()
    survivors = [s for s in streams if s._error is None]
    victims = [s for s in streams if s._error is not None]
    # Handoff 1 landed (the crossed stream); handoff 2 triggered the
    # crash, taking the in-flight stream and any stream still parked
    # or in a lane. Queued streams re-admit on the recovered role.
    assert victims
    assert len(survivors) == len(streams) - len(victims)
    for s in victims:
        with pytest.raises(WorkerCrashedError, match="prefill role"):
            s.result()
        assert s.tokens_so_far.shape[0] >= 1  # prefill token readable
    for p, s in zip(prompts, streams):
        if s in victims:
            continue
        np.testing.assert_array_equal(
            s.result(), oracle(module, variables, p, 8)
        )
    assert leak_free(pre, dec)
    assert m.totals["worker_restarts_total"] == 1
    # The recovered prefill role serves fresh traffic.
    again = sched.submit(prompts[0], max_new_tokens=4)
    sched.drain()
    np.testing.assert_array_equal(
        again.result(), oracle(module, variables, prompts[0], 4)
    )
    assert leak_free(pre, dec)


@pytest.mark.slow
@pytest.mark.chaos
def test_transfer_failure_after_warmup_stays_compile_free(lm):
    """The unwind paths allocate no new programs: an injected transfer
    failure plus recovery traffic leaves both engines at their warmup
    compile counts."""
    sched, pre, dec, _, _ = make_disagg(lm, warm=True)
    pre_c, dec_c = pre.compile_count, dec.compile_count
    with faults.injected(FaultPlan(fail_page_transfer=1)):
        streams = [
            sched.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
            for _ in range(3)
        ]
        sched.drain()
    assert sum(1 for s in streams if s._error is not None) == 1
    assert pre.compile_count == pre_c
    assert dec.compile_count == dec_c
    assert leak_free(pre, dec)
