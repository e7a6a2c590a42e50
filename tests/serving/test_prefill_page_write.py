"""The cold prefill program writes its K/V a page at a time
(``models.transformer._pool_write_pages``, docs/DESIGN.md §20) and every
other program a row at a time. Through the engine and the scheduler, at a
tiny size on the CPU: requests served by an engine as it ships emit the
tokens of an engine whose cold prefill writes the same pages by rows, for
a model of each shape the benchmark serves (one page group; window layers
with a group of their own, whose pages behind the window are released; a
recurrent block a slot beside the rows; K/V rows in one layer of four),
with a bucket that is not whole pages and slots taken again by shorter
prompts; and the engine counts which rows went which way."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "models")
)
import falcon_h1_tiny  # noqa: E402
import solar_open2_tiny  # noqa: E402

from zookeeper_tpu.core import configure  # noqa: E402
from zookeeper_tpu.models import transformer  # noqa: E402
from zookeeper_tpu.models.transformer import TransformerLM, greedy_decode  # noqa: E402
from zookeeper_tpu.observability import trace  # noqa: E402
from zookeeper_tpu.serving.decode import DecodeEngine, DecodeScheduler  # noqa: E402

pytestmark = pytest.mark.serving

VOCAB, POSITIONS, PAGE = 512, 96, 4


def plain(**fields):
    model = TransformerLM()
    configure(model, {"attention": "dense", **fields})
    module = model.build((POSITIONS,), VOCAB)
    params, _ = model.initialize(module, (POSITIONS,), seed=3)
    # the initializer's embedding (0.02) leaves the logits flat
    return module, dict(params, embed=params["embed"] * 25.0)


def gpt2_shaped():
    return plain(num_layers=2, d_model=64, num_heads=4)


def mellum2_shaped():
    return plain(
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, head_dim=32,
        positions="rope", layer_types=["window", "window", "window", "full"],
        window=16, mlp="moe", num_experts=8, experts_per_token=2,
        expert_dim=32, tie_embeddings=False,
    )


MODELS = {
    "gpt2": gpt2_shaped,
    "mellum2": mellum2_shaped,
    "falcon_h1": falcon_h1_tiny.build,
    "solar_open2": solar_open2_tiny.build,
}


def make_engine(module, params, **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": 3, "seq_buckets": (18, 64), "kv_capacity": POSITIONS,
            "page_size": PAGE, "prefix_cache": False,
            "decode_attention": "reference", **conf,
        },
        name="engine",
    )
    return engine.bind(module, params, {})


def serve(engine, prompts, new_tokens):
    sched = DecodeScheduler()
    configure(sched, {}, name="sched")
    sched.bind(engine)
    streams = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, new_tokens)]
    sched.drain()
    return [np.asarray(s.result()) for s in streams]


def write_pages_by_rows(layer, rows, pages):
    """``_pool_write_pages``' pages through the row write: what the cold
    prefill wrote until PR 34, and besides it the rows past a prompt's end
    in its last page, which no reader sees."""
    ps = layer["k"].shape[2]
    j = jnp.arange(rows["k"].shape[1])
    return transformer._pool_write_rows(
        layer, rows, pages[:, j // ps],
        jnp.broadcast_to(j % ps, (pages.shape[0], j.shape[0])),
    )


@pytest.mark.parametrize("shape", sorted(MODELS))
def test_cold_prefill_by_pages_serves_the_row_writes_tokens(shape, monkeypatch):
    module, params = MODELS[shape]()
    rng = np.random.default_rng(34)
    # Seven requests over three slots: slots are taken again, a 40-token
    # tenant's pages by a 5-token prompt's; 17 and 18 fill the 18-row
    # bucket (four and a half pages), 40 and 61 pass the toy window of 16.
    lengths = (40, 17, 5, 61, 18, 3, 33)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in lengths]
    new_tokens = [int(n) for n in rng.integers(3, 9, size=len(prompts))]

    engine = make_engine(module, params)
    got = serve(engine, prompts, new_tokens)
    assert engine.pool_status()["kv_page_write_share"] == 1.0
    assert engine.pool_status()["leaked"] == 0

    monkeypatch.setattr(transformer, "_pool_write_pages", write_pages_by_rows)
    want = serve(make_engine(module, params), prompts, new_tokens)
    for g, w, n in zip(got, want, new_tokens):
        assert g.shape == (n,)
        np.testing.assert_array_equal(g, w)
    if shape == "gpt2":  # the oracle reads no cache at all
        for p, g, n in zip(prompts, got, new_tokens):
            full = np.asarray(
                greedy_decode(module, {"params": params}, p[None], n)
            )
            np.testing.assert_array_equal(g, full[0, p.shape[0]:])


def test_kv_page_write_share_falls_with_warm_prefixes_and_chunks():
    """Cold prompts count as written by page; a warm prefix's suffix and a
    prefill chunk go through the extend program a row at a time; the
    dispatch spans say which."""
    module, params = gpt2_shaped()
    engine = make_engine(module, params, prefix_cache=True)
    assert engine.pool_status()["kv_page_write_share"] == 0.0  # nothing yet
    rng = np.random.default_rng(5)
    first = rng.integers(1, VOCAB, size=40).astype(np.int32)
    second = np.concatenate(
        [first[:24], rng.integers(1, VOCAB, size=9).astype(np.int32)]
    )
    tracer = trace.enable()
    try:
        serve(engine, [first], [2])
        assert engine.pool_status()["kv_page_write_share"] == 1.0
        serve(engine, [second], [2])
        records = tracer.snapshot()
    finally:
        trace.disable()
    (cold,) = [r for r in records if r["name"] == "prefill_dispatch"]
    (warm,) = [r for r in records if r["name"] == "prefill_warm_dispatch"]
    assert cold["attrs"]["kv_write"] == "pages"
    assert warm["attrs"]["kv_write"] == "rows"
    # 24 shared tokens are six whole pages of four: the suffix is 9 rows
    assert engine.pool_status()["kv_page_write_share"] == round(40 / 49, 4)

    chunked = make_engine(module, params, prefill_chunk_tokens=16)
    serve(chunked, [first], [2])
    assert chunked.pool_status()["kv_page_write_share"] == 0.0
