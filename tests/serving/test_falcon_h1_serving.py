"""A model with a state-space mixer beside attention through the decode
engine, the page pool and the scheduler, at a tiny size on the CPU,
float32, against the plain reference (``benchmarks/reference/
falcon_h1.py``): the second kind of per-sequence state, a fixed block a
slot beside the paged K/V rows (docs/DESIGN.md §27)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "models")
)
import falcon_h1_tiny as tiny  # noqa: E402

from zookeeper_tpu.core import configure  # noqa: E402
from zookeeper_tpu.observability import trace  # noqa: E402
from zookeeper_tpu.serving.decode import DecodeEngine, DecodeScheduler  # noqa: E402

pytestmark = pytest.mark.serving

reference = tiny.load_reference()
LAYERS = tiny.FIELDS["num_layers"]


@pytest.fixture(scope="module")
def built():
    return tiny.build()


def make_engine(module, params, *, slots=3, seq_buckets=(16, 64), **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots, "seq_buckets": tuple(seq_buckets),
            "kv_capacity": tiny.POSITIONS, "page_size": 4,
            "prefix_cache": False, "decode_attention": "reference",
            **conf,
        },
        name="engine",
    )
    return engine.bind(module, params, {})


def make_scheduler(engine):
    sched = DecodeScheduler()
    configure(sched, {}, name="sched")
    sched.bind(engine)
    return sched


def reference_logits(params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, tiny.MODEL, jnp.asarray(tokens)))


def slot_state(engine, slot):
    return [
        (np.asarray(layer["ssm"][slot]), np.asarray(layer["conv"][slot]))
        for layer in engine._cache
    ]


def test_prefill_then_decode_through_pool_and_slot_state(built):
    """(b) Prefill through the engine's program, then 12 decode steps
    through the pool and the slot's state: every step's logits against
    the reference's one pass over the prompt and the decoded tokens."""
    module, params = built
    # two prompts in a prefill group of three: one padding row, whose
    # slot id lies past the slots and whose state is written nowhere
    engine = make_engine(module, params, prefill_buckets=(1, 3))
    engine.warmup()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, tiny.VOCAB, size=n).astype(np.int32) for n in (11, 37)]
    slots = [2, 0]
    for slot, prompt in zip(slots, prompts):
        assert engine.admit_slot(slot, prompt) is not None
    first = engine.prefill(prompts, slots)
    sequences = [list(p) + [int(t)] for p, t in zip(prompts, first)]
    for prompt, token in zip(prompts, first):
        assert token == reference_logits(params, prompt)[-1].argmax()
    tokens = np.zeros(3, np.int32)
    lengths = np.zeros(3, np.int32)
    for _ in range(12):
        for slot, seq in zip(slots, sequences):
            tokens[slot], lengths[slot] = seq[-1], len(seq) - 1
            assert engine.ensure_rows(slot, len(seq))
        # the step's logits, from the engine's own cache (not donated)...
        logits, _ = module.apply(
            {"params": params}, jnp.asarray(tokens), jnp.asarray(lengths),
            engine._cache, engine.page_pool.operand(),
            method="decode_step_paged",
        )
        # ...and the step itself, through the compiled program
        nxt = engine.decode(tokens, lengths)
        for slot, seq in zip(slots, sequences):
            want = reference_logits(params, np.asarray(seq, np.int32))[-1]
            np.testing.assert_allclose(logits[slot], want, atol=1e-4, rtol=2e-4)
            assert nxt[slot] == np.argmax(logits[slot])
            seq.append(int(nxt[slot]))
    assert engine.compile_count == len(engine._compiled_cache)
    assert engine.recompiles_detected == 0


def test_one_prompt_in_two_buckets_leaves_the_same_slot_state(built):
    """(c) Padding does not advance the recurrence: a prompt of 13 tokens
    prefilled in the bucket of 16 and in the bucket of 64 leaves the same
    state and convolution rows at its slot and the same next logits."""
    module, params = built
    prompt = np.random.default_rng(3).integers(0, tiny.VOCAB, size=13).astype(np.int32)
    seen = []
    for buckets in ((16, 64), (64,)):
        engine = make_engine(module, params, seq_buckets=buckets)
        assert engine.admit_slot(1, prompt) is not None
        first = engine.prefill([prompt], [1])
        tokens = np.zeros(3, np.int32)
        lengths = np.zeros(3, np.int32)
        tokens[1], lengths[1] = first[0], len(prompt)
        assert engine.ensure_rows(1, len(prompt) + 1)
        logits, _ = module.apply(
            {"params": params}, jnp.asarray(tokens), jnp.asarray(lengths),
            engine._cache, engine.page_pool.operand(),
            method="decode_step_paged",
        )
        seen.append((int(first[0]), slot_state(engine, 1), np.asarray(logits[1])))
        # the other slots' blocks were not written
        for slot in (0, 2):
            for ssm, conv in slot_state(engine, slot):
                assert not ssm.any() and not conv.any()
    (tok_a, state_a, logits_a), (tok_b, state_b, logits_b) = seen
    assert tok_a == tok_b
    for (ssm_a, conv_a), (ssm_b, conv_b) in zip(state_a, state_b):
        assert ssm_a.any() and conv_a.any()
        np.testing.assert_allclose(ssm_a, ssm_b, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(conv_a, conv_b, atol=1e-6)
    np.testing.assert_allclose(logits_a, logits_b, atol=1e-5, rtol=1e-5)


def test_a_reused_slot_keeps_nothing_of_its_last_tenant(built):
    """(d) Request B served in the one slot after request A is B served
    alone: admission overwrites the slot's state and rows."""
    module, params = built
    rng = np.random.default_rng(5)
    a = rng.integers(0, tiny.VOCAB, size=40).astype(np.int32)
    b = rng.integers(0, tiny.VOCAB, size=9).astype(np.int32)

    def serve(requests):
        engine = make_engine(module, params, slots=1)
        sched = make_scheduler(engine)
        out = [sched.submit(p, max_new_tokens=14).result(timeout=600) for p in requests]
        assert engine.page_pool.leak_check() == 0
        return out[-1], slot_state(engine, 0)

    after_a, state_after = serve([a, b])
    alone, state_alone = serve([b])
    np.testing.assert_array_equal(after_a, alone)
    for (ssm_x, conv_x), (ssm_y, conv_y) in zip(state_after, state_alone):
        np.testing.assert_array_equal(ssm_x, ssm_y)
        np.testing.assert_array_equal(conv_x, conv_y)
    # ...and B's tokens are the reference's own, by logits
    logits = reference_logits(params, np.concatenate([b, alone]))
    at = np.arange(len(b) - 1, len(b) + len(alone) - 1)
    assert float((logits[at].max(-1) - logits[at, alone]).max()) < 5e-4


def _refuse_prefix_cache(module, params):
    make_engine(module, params, prefix_cache=True)


def _refuse_chunked_prefill(module, params):
    make_engine(module, params, prefill_chunk_tokens=8)


def _refuse_speculation(module, params):
    from zookeeper_tpu.serving.decode.speculative import SpeculativeDecoding

    spec = SpeculativeDecoding()
    configure(spec, {"enabled": True, "k": 2}, name="spec")
    spec.bind(make_engine(module, params), module, params, {})


def _refuse_verify_program(module, params):
    make_engine(module, params).warmup_verify(3)


def _refuse_page_handoff(module, params):
    make_engine(module, params).warmup_transfer()


def _refuse_adoption(module, params):
    make_engine(module, params).page_pool.adopt_slot(0, 2)


@pytest.mark.parametrize(
    "attempt,error,message",
    [
        (_refuse_prefix_cache, ValueError, "prefix_cache=true is not implemented for a model with recurrent"),
        (_refuse_chunked_prefill, ValueError, "prefill_chunk_tokens > 0 is not implemented for a model with recurrent"),
        (_refuse_speculation, ValueError, "speculative decoding with a teacher that has recurrent"),
        (_refuse_verify_program, NotImplementedError, "speculative draft or verify is not implemented"),
        (_refuse_page_handoff, NotImplementedError, "page transfer moves pages"),
        (_refuse_adoption, NotImplementedError, "page handoff into a pool with recurrent state"),
    ],
    ids=["prefix-cache", "chunked-prefill", "speculation", "verify", "handoff", "adopt-slot"],
)
def test_what_recurrent_state_refuses_at_bind(built, attempt, error, message):
    """(f) Each by its message, which names the mechanism."""
    with pytest.raises(error, match=message):
        attempt(*built)


def test_the_cache_counts_and_records_the_slot_state(built):
    """``kv_cache_nbytes`` and the gauge count the state; while tracing,
    ``ssm_state_placed`` at bind, ``ssm_state_reset`` a prefill dispatch
    and ``decode_ssm_slots`` a decode dispatch say what was written."""
    from zookeeper_tpu.observability.registry import default_registry

    module, params = built
    tracer = trace.enable()
    try:
        engine = make_engine(module, params)
        sched = make_scheduler(engine)
        prompt = np.arange(9, dtype=np.int32)
        sched.submit(prompt, max_new_tokens=5).result(timeout=600)
        records = tracer.snapshot()
    finally:
        trace.disable()
    heads, p, n = 4, 8, 16
    channels = heads * p + 2 * 2 * n
    state = LAYERS * 3 * heads * p * n * 4
    conv = LAYERS * 3 * 3 * channels * 4
    held = sum(
        int(leaf.nbytes) for layer in engine._cache for leaf in layer.values()
    )
    assert engine.kv_cache_nbytes == held
    assert default_registry().gauge("zk_decode_kv_bytes").value == held
    (placed,) = [r for r in records if r["name"] == "ssm_state_placed"]
    assert placed["attrs"] == {
        "layers": LAYERS, "slots": 3, "bytes_ssm": state, "bytes_conv": conv,
    }
    resets = [r for r in records if r["name"] == "ssm_state_reset"]
    assert [r["attrs"] for r in resets] == [{"slots": 1}]
    steps = [r for r in records if r["name"] == "decode_ssm_slots"]
    dispatches = [r for r in records if r["name"] == "decode_dispatch"]
    assert len(steps) == len(dispatches) == 4
    assert all(r["attrs"] == {"slots_advanced": 3, "slots_live": 1} for r in steps)


def test_a_model_without_the_mixer_keeps_its_cache_and_programs(built):
    """The pool of a plain model has no slot leaves, records none of the
    events and its prefill takes no slot ids."""
    from zookeeper_tpu.models.transformer import TransformerLM

    model = TransformerLM()
    configure(model, {"num_layers": 1, "d_model": 32, "num_heads": 2, "attention": "dense"})
    module = model.build((32,), 64)
    params, _ = model.initialize(module, (32,), seed=0)
    tracer = trace.enable()
    try:
        engine = DecodeEngine()
        configure(engine, {"slots": 2, "seq_buckets": (8,), "prefix_cache": False}, name="e")
        engine.bind(module, params, {})
        sched = make_scheduler(engine)
        sched.submit(np.arange(5, dtype=np.int32), max_new_tokens=3).result(timeout=600)
        names = {r["name"] for r in tracer.snapshot()}
    finally:
        trace.disable()
    assert sorted(engine._cache[0]) == ["k", "v"]
    assert not names & {"ssm_state_placed", "ssm_state_reset", "decode_ssm_slots"}
