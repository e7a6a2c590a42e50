"""The decode pipeline (docs/DESIGN.md §13): the plain path launches
step N+1 before it reads step N, the tokens that feed the next step stay
on the device, and every stream still holds exactly the tokens it held
when each step was read at once.

The reference is the same scheduler with the pipeline emptied after
every step (:class:`ReadAtOnce`): no step is ever launched behind an
unread one, every input token is supplied by the host, and an EOS is seen
in the step that emits it — the loop as it was before the pipeline. All
CPU, toy engines; thread-free except where a worker is the subject."""

import numpy as np
import pytest

from zookeeper_tpu.core import component, configure
from zookeeper_tpu.observability import trace
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving.batcher import WorkerCrashedError
from zookeeper_tpu.serving.decode import DecodeMetrics, DecodeScheduler

from tests.observability.trace_leaves import overlapping_spans
from tests.serving.test_decode_engine import (
    SEQ_LEN,
    VOCAB,
    build_lm,
    make_engine,
    oracle,
)

pytestmark = pytest.mark.serving

SLOTS = 3


@component
class ReadAtOnce(DecodeScheduler):
    """Every decode step read as soon as it is launched."""

    def _decode(self) -> int:
        spent = super()._decode()
        self._resolve_unread()
        return spent


def make_sched(engine, cls=DecodeScheduler, speculative=None, **conf):
    metrics = DecodeMetrics()
    configure(metrics, {}, name="pipeline_metrics")
    sched = cls()
    configure(sched, dict(conf), name="pipeline_sched")
    sched.bind(engine, metrics=metrics, speculative=speculative)
    return sched, metrics


def serve(sched, requests):
    """Submit everything, drain, and return each stream."""
    streams = [
        sched.submit(r["prompt"], max_new_tokens=r["max_new"], eos_token=r.get("eos"))
        for r in requests
    ]
    sched.drain()
    assert all(s.done and s.error is None for s in streams)
    return streams


def fresh_index(tokens, lo=1, hi=None):
    """The first index in ``[lo, hi)`` whose token has not come before it
    (an EOS planted there cuts the stream exactly there); None if none."""
    tokens = [int(t) for t in tokens]
    hi = len(tokens) if hi is None else hi
    return next(
        (j for j in range(lo, hi) if tokens[j] not in tokens[:j]), None
    )


def with_eos(requests, plain, picks):
    """``requests`` with an EOS planted in some: request ``i`` ends with
    the token the EOS-free run emitted at index ``picks[i]``."""
    out = [dict(r) for r in requests]
    for i, j in picks.items():
        out[i]["eos"] = int(plain[i].result()[j])
    return out


def late_eos(streams, requests):
    """The streams whose end only the token told, at a decode step: each
    decoded one more token, which was dropped."""
    return sum(
        1 for s, r in zip(streams, requests)
        if s.finish_reason == "eos" and 2 <= len(s.result()) < r["max_new"]
    )


def compare(engine, requests, *, reset=lambda: None):
    """Serve ``requests`` read-at-once and pipelined on ``engine``; the
    streams must agree token for token and reason for reason. Returns the
    pipelined run's ``(streams, scheduler, metrics)``."""
    ref_sched, ref_metrics = make_sched(engine, ReadAtOnce)
    want = serve(ref_sched, requests)
    assert ref_sched.status()["decode_pipeline"] == {
        "unread": False, "steps_in_flight": 0, "tokens_dropped": 0,
    }
    ref_sched.close()
    reset()
    sched, metrics = make_sched(engine)
    got = serve(sched, requests)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.result(), b.result())
        assert a.finish_reason == b.finish_reason
    assert metrics.totals["tokens_total"] == ref_metrics.totals["tokens_total"]
    pipeline = sched.status()["decode_pipeline"]
    assert pipeline["unread"] is False and not sched._has_work()
    assert pipeline["tokens_dropped"] == late_eos(got, requests)
    assert pipeline["steps_in_flight"] > 0
    assert metrics.totals["tokens_dropped_total"] == pipeline["tokens_dropped"]
    assert metrics.totals["steps_in_flight_total"] == pipeline["steps_in_flight"]
    # one extra step an EOS seen late, and no other
    assert (
        metrics.totals["decode_steps_total"]
        <= ref_metrics.totals["decode_steps_total"] + pipeline["tokens_dropped"]
    )
    assert engine.page_pool.leak_check() == 0
    return got, sched, metrics


@pytest.fixture(scope="module")
def lm():
    return build_lm()


def mixed_requests(rng, shared=None):
    """More requests than slots (every slot is refilled, most in the
    iteration after it was freed), budgets from 1 up, one prompt that
    runs into ``token_limit``."""
    lengths = (3, 9, 5, 14, 2, 7, 11, 4, 6)
    budgets = (6, 1, 9, 4, 12, 2, 7, 10, 5)
    requests = []
    for n, budget in zip(lengths, budgets):
        prompt = rng.integers(1, VOCAB, size=n).astype(np.int32)
        if shared is not None and n > 4:
            prompt = np.concatenate([shared, prompt])[: SEQ_LEN - 14]
        requests.append({"prompt": prompt, "max_new": budget})
    # token_limit: the prompt leaves room for 5 tokens of a budget of 40
    requests.append({
        "prompt": rng.integers(1, VOCAB, size=SEQ_LEN - 5).astype(np.int32),
        "max_new": 40,
    })
    return requests


@pytest.mark.parametrize("prefix_cache", [False, True], ids=["cold", "prefix_cache"])
def test_a_mixed_load_reads_the_same_tokens_as_a_run_read_at_once(lm, prefix_cache):
    module, params, state, variables = lm
    engine = make_engine(
        module, params, state, slots=SLOTS, seq_buckets=(8, 16, SEQ_LEN),
        prefix_cache=prefix_cache, page_size=4,
    )
    engine.warmup()
    warm = engine.compile_count
    rng = np.random.default_rng(36)
    shared = rng.integers(1, VOCAB, size=8).astype(np.int32) if prefix_cache else None
    requests = mixed_requests(rng, shared)
    plain_sched, _ = make_sched(engine, ReadAtOnce)
    plain = serve(plain_sched, requests)
    plain_sched.close()
    engine.invalidate_prefix_cache()
    # EOS mid-stream wherever a stream has a token to cut it at, and in
    # one at its budget's last token (the host counts that end: no step
    # is launched for it, nothing dropped)
    picks = {}
    for i in (0, 2, 4, 6, 7):
        j = fresh_index(plain[i].result(), 1, requests[i]["max_new"] - 1)
        if j is not None:
            picks[i] = j
    at_budget = [
        i for i in (3, 8) if fresh_index(
            plain[i].result(), requests[i]["max_new"] - 1
        ) is not None
    ]
    assert len(picks) >= 2
    requests = with_eos(
        requests, plain,
        {**picks, **{i: requests[i]["max_new"] - 1 for i in at_budget}},
    )
    got, sched, _ = compare(engine, requests, reset=engine.invalidate_prefix_cache)
    reasons = [s.finish_reason for s in got]
    assert reasons.count("eos") == len(picks) + len(at_budget)
    assert "capacity" in reasons and "length" in reasons
    assert sched.status()["decode_pipeline"]["tokens_dropped"] == len(picks)
    # and against the full-context oracle: the tokens are the model's own
    for stream, request in zip(got, requests):
        n = len(stream.result())
        np.testing.assert_array_equal(
            stream.result(), oracle(module, variables, request["prompt"], n)
        )
    assert len(got[-1].result()) == 5  # cut at exactly token_limit
    if prefix_cache:
        assert sum(s.shared_tokens for s in got) > 0
    assert engine.compile_count == warm and engine.recompiles_detected == 0


def recurrent_requests(rng, vocab, lengths, budgets):
    return [
        {"prompt": rng.integers(0, vocab, size=n).astype(np.int32), "max_new": b}
        for n, b in zip(lengths, budgets)
    ]


def window_engine():
    module, params, state, _ = build_lm(
        num_layers=2, layer_types=["window", "full"], window=8
    )
    engine = make_engine(
        module, params, state, slots=SLOTS, page_size=4,
        decode_attention="reference",
    )
    return engine, VOCAB


def ssm_engine():
    from tests.serving import test_falcon_h1_serving as falcon

    module, params = falcon.tiny.build()
    return falcon.make_engine(module, params, slots=SLOTS), falcon.tiny.VOCAB


def kda_engine():
    from tests.serving import test_solar_open2_serving as solar

    module, params = solar.tiny.build()
    return solar.make_engine(module, params), solar.tiny.VOCAB


@pytest.mark.parametrize(
    "build", [window_engine, ssm_engine, kda_engine],
    ids=["window_layers", "state_space", "delta_rule"],
)
def test_a_late_step_never_reaches_the_next_tenant(build):
    """A slot an EOS frees is refilled while the step that still decodes
    its last tenant is in flight: the tenant after it reads the same
    tokens as in a run where no step is ever late, so neither its pages
    nor its state block took anything from that step."""
    engine, vocab = build()
    engine.warmup()
    rng = np.random.default_rng(11)
    requests = recurrent_requests(
        rng, vocab, lengths=(5, 12, 3, 9, 14, 6, 4), budgets=(8, 5, 11, 3, 7, 9, 6),
    )
    plain_sched, _ = make_sched(engine, ReadAtOnce)
    plain = serve(plain_sched, requests)
    plain_sched.close()
    picks = {}
    for i in (0, 2, 4, 5):
        j = fresh_index(plain[i].result(), 1, requests[i]["max_new"] - 1)
        if j is not None:
            picks[i] = j
    assert len(picks) >= 2
    requests = with_eos(requests, plain, picks)
    got, sched, _ = compare(engine, requests)
    assert sched.status()["decode_pipeline"]["tokens_dropped"] == len(picks)
    assert engine.recompiles_detected == 0


@pytest.fixture(scope="module")
def warm_engine(lm):
    module, params, state, _ = lm
    engine = make_engine(module, params, state, slots=SLOTS)
    engine.warmup()
    return engine


def pumped_until_unread(sched):
    for _ in range(8):
        sched._pump()
        if sched._unread is not None:
            return
    raise AssertionError("no step was left unread")


def test_one_stream_alone_keeps_every_step_but_the_first_in_flight(lm, warm_engine):
    module, _, _, variables = lm
    sched, metrics = make_sched(warm_engine)
    prompt = np.arange(1, 6, dtype=np.int32)
    out = sched.generate(prompt, max_new_tokens=9)
    np.testing.assert_array_equal(out, oracle(module, variables, prompt, 9))
    # 8 decode steps: the first into an empty pipeline, the last known to
    # be the last when it was planned, so nothing was launched behind it
    assert metrics.totals["decode_steps_total"] == 8
    assert sched.status()["decode_pipeline"] == {
        "unread": False, "steps_in_flight": 7, "tokens_dropped": 0,
    }


def test_drain_reads_the_unread_step(lm, warm_engine):
    module, _, _, variables = lm
    sched, _ = make_sched(warm_engine)
    prompts = [np.arange(1, n, dtype=np.int32) for n in (4, 7, 10, 5)]
    streams = [sched.submit(p, max_new_tokens=6) for p in prompts]
    pumped_until_unread(sched)
    assert sched._has_work()
    sched.drain()
    assert sched._unread is None and not sched._has_work()
    for p, s in zip(prompts, streams):
        np.testing.assert_array_equal(s.result(), oracle(module, variables, p, 6))


def test_result_alone_drives_a_synchronous_scheduler(lm, warm_engine):
    """No ``drain()``: each ``result()`` drives until its own stream is
    done and stops, possibly with a step unread, which ``_has_work()``
    reports and the next caller's driving reads."""
    module, _, _, variables = lm
    sched, _ = make_sched(warm_engine)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (3, 8, 5, 6, 4)]
    budgets = (3, 10, 6, 2, 8)
    streams = [sched.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    left_unread = 0
    for p, b, s in zip(prompts, budgets, streams):
        np.testing.assert_array_equal(s.result(), oracle(module, variables, p, b))
        if sched._unread is not None:
            left_unread += 1
            assert sched._has_work()
    assert left_unread >= 1
    assert all(s.done for s in streams)
    sched.close()
    assert sched._unread is None and not sched._has_work()
    assert warm_engine.page_pool.leak_check() == 0


def test_close_reads_the_unread_step_then_fails_the_streams(lm, warm_engine):
    module, _, _, variables = lm
    sched, _ = make_sched(warm_engine)
    prompts = [np.arange(2, n, dtype=np.int32) for n in (6, 9)]
    streams = [sched.submit(p, max_new_tokens=12) for p in prompts]
    pumped_until_unread(sched)
    before = [len(s.tokens_so_far) for s in streams]
    sched.close()
    assert sched._unread is None and not sched._has_work()
    for p, s, n in zip(prompts, streams, before):
        assert s.done and isinstance(s.error, RuntimeError)
        # what the unread step had decoded reached the stream
        partial = s.tokens_so_far
        assert len(partial) == n + 1
        np.testing.assert_array_equal(partial, oracle(module, variables, p, n + 1))
    assert warm_engine.page_pool.leak_check() == 0
    # the engine is at rest: the next scheduler serves from it
    again, _ = make_sched(warm_engine)
    np.testing.assert_array_equal(
        again.generate(prompts[0], max_new_tokens=4),
        oracle(module, variables, prompts[0], 4),
    )


@pytest.mark.parametrize("shape", ["injected_sync", "dispatch_failure_worker"])
def test_a_crash_discards_the_unread_step(lm, warm_engine, shape):
    """Both crash shapes with a step unread: the injected loop crash
    (synchronous), and a compiled call that dies at its fourth decode
    launch, with the third step unread, under the worker thread."""
    module, _, _, variables = lm
    synchronous = shape == "injected_sync"
    sched, metrics = make_sched(warm_engine, synchronous=synchronous)
    prompt = np.arange(1, 8, dtype=np.int32)
    key = ("decode_step", warm_engine._partitioner.mesh)
    real = warm_engine._compiled_cache[key]
    try:
        if synchronous:
            victim = sched.submit(prompt, max_new_tokens=20)
            pumped_until_unread(sched)
            with faults.injected(FaultPlan(decode_worker_crash=1)):
                with pytest.raises(WorkerCrashedError):
                    sched.drain()
        else:
            calls = []

            def dying(*operands):
                calls.append(1)
                if len(calls) == 4:
                    raise RuntimeError("injected dispatch-time device failure")
                return real(*operands)

            warm_engine._compiled_cache[key] = dying
            victim = sched.submit(prompt, max_new_tokens=20)
            with pytest.raises(WorkerCrashedError):
                victim.result(timeout=120)
            warm_engine._compiled_cache[key] = real
        assert victim.done and sched._unread is None and not sched._has_work()
        partial = victim.tokens_so_far
        assert 1 <= len(partial) < 20
        np.testing.assert_array_equal(
            partial, oracle(module, variables, prompt, len(partial))
        )
        assert metrics.totals["worker_restarts_total"] == 1
        assert warm_engine.page_pool.leak_check() == 0
        # the restarted scheduler serves token-exact: nothing of the
        # discarded step is fed to the next one
        revived = sched.submit(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(
            revived.result(timeout=120), oracle(module, variables, prompt, 6)
        )
    finally:
        warm_engine._compiled_cache[key] = real
        sched.close()


def test_a_staged_swap_waits_for_the_unread_step(lm):
    """One weight version a sequence with a step in flight: the streams
    finish on the weights they started with, a step an EOS left behind is
    read (and dropped) before the weights change, and the next stream
    runs on the new ones."""
    module, params, state, variables = lm
    _, params_b, state_b, variables_b = build_lm(seed=23)
    engine = make_engine(module, params, state, slots=2)
    engine.warmup()
    sched, metrics = make_sched(engine)
    prompt = np.arange(3, 9, dtype=np.int32)
    plain = oracle(module, variables, prompt, 12)
    cut = fresh_index(plain, 2, 11)
    stream = sched.submit(prompt, max_new_tokens=12, eos_token=int(plain[cut]))
    pumped_until_unread(sched)
    sched.request_swap(params_b, state_b, step=7)
    sched.drain()
    np.testing.assert_array_equal(stream.result(), plain[: cut + 1])
    assert stream.finish_reason == "eos"
    assert not sched.swap_pending and metrics.totals["weight_swaps_total"] == 1
    pipeline = sched.status()["decode_pipeline"]
    assert pipeline["unread"] is False and pipeline["tokens_dropped"] == 1
    np.testing.assert_array_equal(
        sched.generate(prompt, max_new_tokens=5),
        oracle(module, variables_b, prompt, 5),
    )


def test_speculation_bound_keeps_nothing_unread():
    """With a draft bound the host must see every token to choose the
    next window: the window schedule and its plain fallback near the
    token limit both read at once."""
    from tests.serving.test_speculative import make_spec, zero_tail_pair

    teacher, draft = zero_tail_pair()
    module, params, state, variables = teacher
    engine = make_engine(
        module, params, state, slots=2, seq_buckets=(8, 16), kv_capacity=24
    )
    engine.warmup()
    sched, metrics = make_sched(engine, speculative=make_spec(engine, draft, k=3))
    rng = np.random.default_rng(2)
    prompts = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (4, 16, 9)  # the second falls back to plain steps
    ]
    streams = [
        sched.submit(p, max_new_tokens=b) for p, b in zip(prompts, (10, 20, 10))
    ]
    pumps = 0
    while sched._has_work():
        sched._pump()
        pumps += 1
        assert sched._unread is None
    assert pumps >= 3
    for p, s in zip(prompts, streams):
        n = len(s.result())
        np.testing.assert_array_equal(s.result(), oracle(module, variables, p, n))
    assert streams[1].finish_reason == "capacity"
    assert sched.status()["decode_pipeline"] == {
        "unread": False, "steps_in_flight": 0, "tokens_dropped": 0,
    }
    assert metrics.totals["steps_in_flight_total"] == 0


def test_the_closing_event_counts_what_the_pipeline_did(lm, warm_engine):
    """``in_flight`` and ``dropped`` on ``sched_iteration_end`` add up to
    the running counts, every decode dispatch span holds one boundary
    event, the pipeline's drain has a leaf of its own, and no span
    encloses another."""
    module, _, _, variables = lm
    prompt = np.arange(1, 7, dtype=np.int32)
    plain = oracle(module, variables, prompt, 10)
    cut = fresh_index(plain, 2, 9)
    prior = trace.get_tracer()
    trace.install(trace.Tracer(8192))
    try:
        sched, _ = make_sched(warm_engine)
        a = sched.submit(prompt, max_new_tokens=10, eos_token=int(plain[cut]))
        b = sched.submit(prompt[:4], max_new_tokens=7)
        sched.drain()
        records = trace.get_tracer().snapshot()
    finally:
        trace.install(prior)
    np.testing.assert_array_equal(a.result(), plain[: cut + 1])
    assert len(b.result()) == 7
    ends = [r["attrs"] for r in records if r["name"] == "sched_iteration_end"]
    pipeline = sched.status()["decode_pipeline"]
    assert all(set(e) >= {"in_flight", "dropped"} for e in ends)
    assert all(e["in_flight"] in (0, 1) for e in ends)
    assert sum(e["in_flight"] for e in ends) == pipeline["steps_in_flight"] > 0
    assert sum(e["dropped"] for e in ends) == pipeline["tokens_dropped"] == 1
    spans = [r for r in records if r["phase"] == "X"]
    dispatches = [r for r in spans if r["name"] == "decode_dispatch"]
    events = [
        r for r in records
        if r["name"] == "dispatch_enqueued" and r["attrs"]["program"] == "decode_step"
    ]
    assert len(events) == len(dispatches) >= 6
    for span in dispatches:
        inside = [
            e for e in events
            if span["ts_ns"] <= e["ts_ns"] <= span["ts_ns"] + span["dur_ns"]
        ]
        assert len(inside) == 1
    # the last step of all is read with nothing launched after it
    readbacks = [r for r in spans if r["name"] == "decode_readback"]
    assert 1 <= len(readbacks) <= 2
    assert all(r["step"] is not None for r in readbacks)
    assert overlapping_spans(records) == []
