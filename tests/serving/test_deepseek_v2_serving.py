"""Latent attention through the decode engine, the page pool, the prefix
cache and the scheduler, at a tiny size on the CPU, float32, against the
plain reference (``benchmarks/reference/deepseek_v2.py``): one latent row
a token a layer in the pool (docs/DESIGN.md §29), warm-prefix extends with
copy-on-write, and what is refused at bind."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "models")
)
import deepseek_v2_tiny as tiny  # noqa: E402

from zookeeper_tpu.core import configure  # noqa: E402
from zookeeper_tpu.observability import trace  # noqa: E402
from zookeeper_tpu.ops import mla  # noqa: E402
from zookeeper_tpu.serving.decode import DecodeEngine, DecodeScheduler  # noqa: E402

pytestmark = pytest.mark.serving

reference = tiny.load_reference()
SLOTS, PAGE = 3, 8


@pytest.fixture(scope="module")
def built():
    return tiny.build()


def make_engine(module, params, **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": SLOTS, "seq_buckets": (64,), "extend_buckets": (16, 32),
            "kv_capacity": tiny.POSITIONS, "page_size": PAGE,
            "prefix_cache": True, "decode_attention": "pallas", **conf,
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


def test_served_through_a_shared_prefix_with_copy_on_write(built):
    """Five requests behind one 21-token prefix (off the page of 8, so a
    warm admission copies the divergent page), their bodies 3-14 tokens:
    the first prefills cold, the rest extend from the cache; every served
    token is the reference's own choice, or within 1e-4 of it (float32,
    sums in another order), and the pool holds one latent row a token."""
    module, params = built
    engine = make_engine(module, params)
    assert engine.decode_attention_flavor == "pallas_mla"
    assert [sorted(layer) for layer in engine._cache] == [["c"]] * 3
    sched = make_scheduler(engine)
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, tiny.VOCAB, size=21)
    prompts = [
        np.concatenate([prefix, rng.integers(0, tiny.VOCAB, size=n)]).astype(np.int32)
        for n in (3, 14, 9, 5, 11)
    ]
    served = [sched.generate(prompts[0], max_new_tokens=6)]
    streams = [sched.submit(p, max_new_tokens=6) for p in prompts[1:]]
    served += [s.result(timeout=600) for s in streams]
    assert sum(s.shared_tokens for s in streams) >= 4 * 16
    status = engine.pool_status()
    assert status["cow_pages"] >= 1 and status["leaked"] == 0
    for prompt, out in zip(prompts, served):
        full = np.concatenate([prompt, out]).astype(np.int32)
        logits = reference_logits(params, full)[len(prompt) - 1 : -1]
        gap = logits.max(-1) - logits[np.arange(len(out)), out]
        assert float(gap.max()) < 1e-4, gap


def test_chunked_prefill_falls_out_of_the_extend_program(built):
    """Prompts of 20-45 tokens appended in chunks of at most 16 through
    the extend program, no prefix cache: every served token is the
    reference's own choice, or within 1e-4 of it."""
    module, params = built
    engine = make_engine(module, params, prefix_cache=False, prefill_chunk_tokens=16)
    sched = make_scheduler(engine)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, tiny.VOCAB, size=n).astype(np.int32) for n in (20, 45, 33)]
    streams = [sched.submit(p, max_new_tokens=5) for p in prompts]
    for prompt, stream in zip(prompts, streams):
        out = stream.result(timeout=600)
        full = np.concatenate([prompt, out]).astype(np.int32)
        logits = reference_logits(params, full)[len(prompt) - 1 : -1]
        gap = logits.max(-1) - logits[np.arange(len(out)), out]
        assert float(gap.max()) < 1e-4, gap


def test_copy_on_write_copies_a_latent_page(built):
    """The page copy moves every layer's latent rows of one page and
    nothing else."""
    module, params = built
    engine = make_engine(module, params)
    cache = tuple(
        {"c": jax.random.normal(jax.random.PRNGKey(i), layer["c"].shape)}
        for i, layer in enumerate(engine._cache)
    )
    object.__setattr__(engine, "_cache", cache)
    before = [np.asarray(layer["c"]) for layer in cache]
    engine.copy_page(5, 2)
    for old, layer in zip(before, engine._cache):
        new = np.asarray(layer["c"])
        np.testing.assert_array_equal(new[2], old[5])
        np.testing.assert_array_equal(np.delete(new, 2, 0), np.delete(old, 2, 0))


def test_a_hit_past_the_widest_extend_is_admitted_cold(built):
    """A prompt that shares 4 tokens and then runs 40 more than the widest
    extend (32) is prefilled cold into pages of its own."""
    module, params = built
    engine = make_engine(module, params)
    rng = np.random.default_rng(3)
    a = rng.integers(0, tiny.VOCAB, size=20).astype(np.int32)
    b = np.concatenate([a[:16], rng.integers(0, tiny.VOCAB, size=40)]).astype(np.int32)
    assert engine.admit_slot(0, a)["shared_tokens"] == 0
    engine.insert_prefix(0, a)
    assert engine.admit_slot(1, b)["shared_tokens"] == 0
    c = np.concatenate([a[:16], rng.integers(0, tiny.VOCAB, size=8)]).astype(np.int32)
    assert engine.admit_slot(2, c)["shared_tokens"] == 16


def test_the_counters_say_what_the_steps_read(built):
    """While tracing: ``decode_mla_rows`` a decode dispatch (rows read
    over every layer; those on pages another live slot reads too; those
    the cascade reads through a shared item, and the rows it moves) and
    ``extend_mla_rows`` an extend, and the bind gauge of a latent row's
    bytes (40 values padded to 128 lanes, float32)."""
    from zookeeper_tpu.observability.registry import default_registry

    module, params = built
    tracer = trace.enable()
    try:
        engine = make_engine(module, params)
        sched = make_scheduler(engine)
        prefix = np.arange(24, dtype=np.int32)
        streams = [
            sched.submit(np.concatenate([prefix, tail]).astype(np.int32), max_new_tokens=4)
            for tail in ([1, 2], [3, 4, 5])
        ]
        for stream in streams:
            stream.result(timeout=600)
        # then one alone, on a prompt of its own
        sched.generate(np.arange(100, 112, dtype=np.int32), max_new_tokens=3)
        records = tracer.snapshot()
    finally:
        trace.disable()
    reads = [r["attrs"] for r in records if r["name"] == "decode_mla_rows"]
    extends = [r["attrs"] for r in records if r["name"] == "extend_mla_rows"]
    assert extends == [{"rows_prefix": 24, "rows_new": 3}]
    assert reads and all(0 <= a["rows_shared"] <= a["rows_read"] for a in reads)
    # a step with both live: each reads the prefix's three whole pages
    # (24 rows) in 3 layers, and no other page is read twice
    assert {a["rows_shared"] for a in reads} <= {0, 2 * 24 * 3}
    assert 2 * 24 * 3 in {a["rows_shared"] for a in reads}
    assert all(
        0 <= a["rows_cascaded"] <= a["rows_shared"] <= a["rows_read"] for a in reads
    )
    # three slots make two members enough: where both hold the prefix the
    # kernel reads its three pages once through a shared item, not twice;
    # a step with one live slot reads every row of its own
    assert mla.mla_cascade_need(SLOTS, 4, 128, 32, 4) == 2
    for a in reads:
        assert a["rows_cascaded"] == a["rows_shared"]
        assert a["rows_fetched"] == a["rows_read"] - a["rows_shared"] // 2
    both = [a for a in reads if a["rows_shared"]]
    assert both and all(a["rows_fetched"] < a["rows_read"] for a in both)
    alone = [a for a in reads if a["rows_shared"] == 0]
    assert alone and all(
        a["rows_fetched"] == a["rows_read"] and a["rows_cascaded"] == 0 for a in alone
    )
    gauge = default_registry().gauge("zk_decode_latent_row_bytes", help="")
    assert gauge.value == 128 * 4


def _refuse_int8(module, params):
    make_engine(module, params, kv_quant="int8")


def _refuse_verify_program(module, params):
    make_engine(module, params)._verify_compiled(2)


def _refuse_page_handoff(module, params):
    make_engine(module, params).transfer_width


def _refuse_mesh(module, params):
    from zookeeper_tpu.parallel.partitioner import DataParallelPartitioner

    engine = DecodeEngine()
    configure(
        engine, {"slots": 4, "seq_buckets": (64,), "kv_capacity": tiny.POSITIONS},
        name="engine",
    )
    partitioner = DataParallelPartitioner()
    configure(partitioner, {}, name="partitioner")
    engine.bind(module, params, {}, partitioner=partitioner)


@pytest.mark.parametrize(
    "attempt,error,message",
    [
        (_refuse_int8, ValueError, "kv_quant='int8' is not implemented for latent attention"),
        (_refuse_verify_program, NotImplementedError, "speculative decoding is not implemented for latent"),
        (_refuse_page_handoff, NotImplementedError, "page handoff is not implemented for latent"),
        (_refuse_mesh, ValueError, "latent attention \\(MLA\\) is served on one device"),
    ],
    ids=["int8", "verify", "handoff", "mesh"],
)
def test_what_latent_attention_refuses(built, attempt, error, message):
    """Each refusal names the mechanism."""
    with pytest.raises(error, match=message):
        attempt(*built)
