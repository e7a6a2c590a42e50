"""A model whose layers keep state by kind through the decode engine, the
page pool and the scheduler, at a tiny size on the CPU, float32, against
the plain reference (``benchmarks/reference/solar_open2.py``): K/V rows
for the attention layer alone, a block a slot for each linear-attention
layer alone (docs/DESIGN.md §28)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "models")
)
import solar_open2_tiny as tiny  # noqa: E402

from zookeeper_tpu.core import configure  # noqa: E402
from zookeeper_tpu.observability import trace  # noqa: E402
from zookeeper_tpu.serving.decode import DecodeEngine, DecodeScheduler  # noqa: E402

pytestmark = pytest.mark.serving

reference = tiny.load_reference()
KINDS = tiny.FIELDS["layer_types"]
SLOTS, PAGE = 3, 4


@pytest.fixture(scope="module")
def built():
    return tiny.build()


def make_engine(module, params, *, seq_buckets=(16, 64), **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": SLOTS, "seq_buckets": tuple(seq_buckets),
            "kv_capacity": tiny.POSITIONS, "page_size": PAGE,
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


def test_the_pool_holds_rows_and_blocks_by_layer_kind(built):
    """No K/V rows for a KDA layer, no block for the attention layer, and
    the bytes the engine reports are the bytes the tree holds."""
    module, params = built
    engine = make_engine(module, params)
    heads, hd, inner = 3, 16, 48
    pages = SLOTS * tiny.POSITIONS // PAGE
    for kind, layer in zip(KINDS, engine._cache):
        if kind == "kda":
            assert sorted(layer) == ["kda", "kda_conv"]
            assert layer["kda"].shape == (SLOTS, heads, hd, hd)
            assert layer["kda"].dtype == jnp.float32
            assert layer["kda_conv"].shape == (SLOTS, 3, 3 * inner)
        else:
            assert sorted(layer) == ["k", "v"]
            assert layer["k"].shape[:3] == (pages, 1, PAGE)
    rows = 2 * pages * PAGE * 128 * 4  # one layer; 2 kv heads x 16 pad to 128
    blocks = 3 * SLOTS * (heads * hd * hd + 3 * 3 * inner) * 4
    held = sum(int(leaf.nbytes) for layer in engine._cache for leaf in layer.values())
    assert engine.kv_cache_nbytes == held == rows + blocks
    assert module.attention_layers == (True, False, False, False)
    assert [sorted(d) for d in module.slot_state_spec()] == [
        [], ["kda", "kda_conv"], ["kda", "kda_conv"], ["kda", "kda_conv"],
    ]


def test_prefill_then_decode_through_pool_and_blocks(built):
    """Prefill through the engine's program, then decode steps through
    the pool and the slots' blocks, slots admitted and released out of
    order: every step's logits against the reference's one pass over the
    prompt and the decoded tokens."""
    module, params = built
    engine = make_engine(module, params, prefill_buckets=(1, 3))
    engine.warmup()
    rng = np.random.default_rng(7)
    tokens = np.zeros(SLOTS, np.int32)
    lengths = np.zeros(SLOTS, np.int32)
    live = {}

    def admit(slot, n):
        prompt = rng.integers(0, tiny.VOCAB, size=n).astype(np.int32)
        assert engine.admit_slot(slot, prompt) is not None
        return slot, prompt

    def prefill(admitted):
        first = engine.prefill([p for _, p in admitted], [s for s, _ in admitted])
        for (slot, prompt), token in zip(admitted, first):
            assert token == reference_logits(params, prompt)[-1].argmax()
            live[slot] = list(prompt) + [int(token)]

    def step():
        for slot, seq in live.items():
            tokens[slot], lengths[slot] = seq[-1], len(seq) - 1
            assert engine.ensure_rows(slot, len(seq))
        logits, _ = module.apply(
            {"params": params}, jnp.asarray(tokens), jnp.asarray(lengths),
            engine._cache, engine.page_pool.operand(),
            method="decode_step_paged",
        )
        nxt = engine.decode(tokens, lengths)
        for slot, seq in live.items():
            want = reference_logits(params, np.asarray(seq, np.int32))[-1]
            np.testing.assert_allclose(logits[slot], want, atol=2e-4, rtol=5e-4)
            assert nxt[slot] == np.argmax(logits[slot])
            seq.append(int(nxt[slot]))

    # two prompts in a group of three (one padding row), slots 2 and 0
    prefill([admit(2, 11), admit(0, 37)])
    for _ in range(5):
        step()
    # slot 2 leaves, slot 1 joins, then slot 2 is taken again by a prompt
    # shorter than its last tenant's: nothing of the old block may stay
    engine.release_slot(2)
    del live[2]
    prefill([admit(1, 23)])
    for _ in range(3):
        step()
    prefill([admit(2, 6)])
    for _ in range(5):
        step()
    assert engine.compile_count == len(engine._compiled_cache)
    assert engine.recompiles_detected == 0


def test_the_scheduler_serves_what_the_reference_would(built):
    """More requests than slots through the scheduler: every served token
    is the reference's own choice at its position."""
    module, params = built
    sched = make_scheduler(make_engine(module, params))
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(0, tiny.VOCAB, size=n).astype(np.int32)
        for n in (9, 30, 17, 5, 44)
    ]
    streams = [sched.submit(p, max_new_tokens=7) for p in prompts]
    for prompt, stream in zip(prompts, streams):
        served = stream.result(timeout=600)
        full = np.concatenate([prompt, served]).astype(np.int32)
        want = reference_logits(params, full)[len(prompt) - 1 : -1].argmax(-1)
        np.testing.assert_array_equal(served, want)


def _refuse_prefix_cache(module, params):
    make_engine(module, params, prefix_cache=True)


def _refuse_chunked_prefill(module, params):
    make_engine(module, params, prefill_chunk_tokens=16)


def _refuse_verify_program(module, params):
    make_engine(module, params)._verify_compiled(2)


def _refuse_page_handoff(module, params):
    make_engine(module, params).transfer_width()


@pytest.mark.parametrize(
    "attempt,error,message",
    [
        (_refuse_prefix_cache, ValueError, "prefix_cache=true is not implemented for a model with recurrent"),
        (_refuse_chunked_prefill, ValueError, "prefill_chunk_tokens > 0 is not implemented for a model with recurrent"),
        (_refuse_verify_program, NotImplementedError, "speculative draft or verify is not implemented"),
        (_refuse_page_handoff, NotImplementedError, "page transfer moves pages"),
    ],
    ids=["prefix-cache", "chunked-prefill", "verify", "handoff"],
)
def test_what_recurrent_state_refuses_at_bind(built, attempt, error, message):
    """The four refusals stand for a model whose blocks belong to some
    layers only, each by its message, which names the mechanism."""
    with pytest.raises(error, match=message):
        attempt(*built)


def test_the_counters_say_what_was_held_and_advanced(built):
    """While tracing: ``kda_state_placed`` at bind (three layers),
    ``kda_state_reset`` a prefill, ``decode_kda_slots`` and
    ``moe_held_choices`` a dispatch; the held experts' counts are the
    held experts' alone."""
    module, params = built
    tracer = trace.enable()
    try:
        engine = make_engine(module, params)
        sched = make_scheduler(engine)
        sched.submit(np.arange(9, dtype=np.int32), max_new_tokens=5).result(timeout=600)
        records = tracer.snapshot()
    finally:
        trace.disable()
    heads, hd, inner = 3, 16, 48
    (placed,) = [r for r in records if r["name"] == "kda_state_placed"]
    assert placed["attrs"] == {
        "layers": 3, "slots": SLOTS,
        "bytes_kda": 3 * SLOTS * heads * hd * hd * 4,
        "bytes_kda_conv": 3 * SLOTS * 3 * 3 * inner * 4,
    }
    resets = [r for r in records if r["name"] == "kda_state_reset"]
    assert [r["attrs"] for r in resets] == [{"slots": 1}]
    steps = [r for r in records if r["name"] == "decode_kda_slots"]
    dispatches = [r for r in records if r["name"] == "decode_dispatch"]
    assert len(steps) == len(dispatches) == 4
    assert all(r["attrs"] == {"slots_advanced": SLOTS, "slots_live": 1} for r in steps)
    held = [r["attrs"] for r in records if r["name"] == "moe_held_choices"]
    loads = [r["attrs"] for r in records if r["name"] == "moe_tokens_per_expert"]
    assert len(held) == len(loads) == 5
    for choices, load in zip(held, loads):
        counts = np.asarray(load["counts"])
        assert counts.shape == (4, tiny.HELD)
        rows = 16 if choices["program"] == "prefill" else SLOTS
        assert choices["choices_routed"] == rows * 4 * 4
        assert choices["choices_held"] == counts.sum() <= choices["choices_routed"]
        assert choices["tokens_per_expert_max"] == counts.max()
    assert not {r["name"] for r in records} & {"ssm_state_placed", "decode_ssm_slots"}
