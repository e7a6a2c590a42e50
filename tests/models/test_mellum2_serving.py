"""The Mellum2-shaped model (grouped heads, rotary/YaRN positions, window
layers beside full ones, sparse experts, an untied head) at a small size on
the CPU, seeded random weights, against the plain reference
(``mellum2_reference.py``): the full pass, and prefill followed by paged
decode through ``LMServingConfig`` / ``DecodeScheduler`` / ``PagePool``
with the window shorter than the sequences, so that window pages are
released while the requests run."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import mellum2_reference as reference  # noqa: E402

from zookeeper_tpu import configure  # noqa: E402
from zookeeper_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    rope_inv_freq,
)
from zookeeper_tpu.serving import LMServingConfig  # noqa: E402

VOCAB, POSITIONS, WINDOW = 512, 96, 16

#: The reference's view of the model: the public config's keys.
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "num_hidden_layers": 4, "vocab_size": VOCAB,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": WINDOW, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
}

#: The program's view: TransformerLM's fields.
FIELDS = {
    "num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 32, "positions": "rope", "rope_theta": 500000.0,
    "yarn_factor": 16.0, "yarn_original_len": 32, "yarn_beta_fast": 32.0,
    "yarn_beta_slow": 1.0, "layer_types": ["window", "window", "window", "full"],
    "window": WINDOW, "mlp": "moe", "num_experts": 8, "experts_per_token": 2,
    "expert_dim": 32, "tie_embeddings": False, "attention": "dense",
}


@pytest.fixture(scope="module")
def built():
    model = TransformerLM()
    configure(model, FIELDS)
    module = model.build((POSITIONS,), VOCAB)
    params, _ = model.initialize(module, (POSITIONS,), seed=3)
    # the initializer's embedding (0.02) leaves the logits flat: spread
    # them so that a wrong position or a wrong expert moves an argmax
    params = dict(params, embed=params["embed"] * 25.0)
    return module, params


def test_yarn_table_is_the_closed_form():
    """Dimension by dimension against the published formula, at the
    published sizes (head 128, theta 500000, factor 16 from 8192)."""
    import math

    inv, factor = rope_inv_freq(128, 500000.0, (16.0, 8192, 32.0, 1.0))
    assert factor == pytest.approx(1.2772588722239782, rel=1e-12)

    def d(b):
        return 128 * math.log(8192 / (2 * math.pi * b)) / (2 * math.log(500000.0))

    low, high = max(math.floor(d(32)), 0), min(math.ceil(d(1)), 127)
    assert (low, high) == (18, 35)
    for i in range(64):
        plain = 500000.0 ** (-2 * i / 128)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain * (1 - ramp) + plain / 16 * ramp
        # float32 table of a float64 closed form: one rounding
        assert float(inv[i]) == pytest.approx(want, rel=1e-6)
    plain, one = rope_inv_freq(128, 500000.0)
    assert one == 1.0
    np.testing.assert_allclose(plain[:19], inv[:19], rtol=1e-7)
    np.testing.assert_allclose(plain[35:] / 16, inv[35:], rtol=1e-6)
    # the reference builds the same table from the config's keys
    ref = reference.rope_tables(
        dict(MODEL, head_dim=128, rope_parameters={
            "full_attention": dict(
                MODEL["rope_parameters"]["full_attention"],
                original_max_position_embeddings=8192,
            ),
            "sliding_attention": MODEL["rope_parameters"]["sliding_attention"],
        })
    )
    np.testing.assert_allclose(ref["full"][0], inv, rtol=1e-6)
    np.testing.assert_allclose(ref["window"][0], plain, rtol=1e-6)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_full_pass_matches_the_reference(built, attention):
    module, params = built
    module = module.clone(attention=attention)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, POSITIONS), 0, VOCAB)
    got = module.apply({"params": params}, tokens)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.forward(params, MODEL, t) for t in tokens])
    # float32 on both sides; the program's matmuls run at the CPU's
    # default precision, the reference's at the highest, and the flash
    # kernel reassociates its sums: 1e-4 of logits that spread over +-4
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # ...and float8 in the place of float32 is far outside that
    with jax.default_matmul_precision("highest"):
        low = reference.forward(params, MODEL, tokens[0], lowp=True)
    assert float(jnp.max(jnp.abs(low - want[0]))) > 50 * 2e-4


def _service(params, **engine):
    box = {}

    from zookeeper_tpu import component

    @component
    class Seeded(TransformerLM):
        def initialize(self, module, input_shape, seed=0):
            box["module"] = module
            return params, {}

    service = LMServingConfig()
    conf = {
        "model": Seeded, "seq_len": POSITIONS, "vocab_size": VOCAB,
        "requests": 0, "verbose": False,
        "engine.kv_layout": "paged", "engine.page_size": 4,
        "engine.slots": 3, "engine.kv_capacity": POSITIONS,
        "engine.seq_buckets": [16, 64], "engine.prefix_cache": False,
        "engine.decode_attention": "reference",
    }
    conf.update({f"model.{k}": v for k, v in FIELDS.items()})
    conf.update({f"engine.{k}": v for k, v in engine.items()})
    configure(service, conf)
    return service


@pytest.mark.parametrize("decode_attention", ["reference", "pallas"])
def test_prefill_then_paged_decode_matches_the_reference(built, decode_attention):
    """Greedy tokens served through the scheduler are the reference's
    own choice wherever the reference's best logit leads by more than
    the tolerance, and the served token's reference logit is never
    further than the tolerance below the best: the comparison the
    benchmark makes on the chip (``served_logit_gap``), here in float32.
    Prompts of 9-60 tokens and 30 new ones: every request outgrows the
    window of 16 and releases window pages while it runs."""
    _, params = built
    service = _service(params, decode_attention=decode_attention)
    engine, scheduler = service.build_service()
    try:
        pool = engine.page_pool
        assert pool.window_group is not None
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(0, VOCAB, size=n).astype(np.int32)
            for n in (9, 33, 60, 17, 40)
        ]
        streams = [scheduler.submit(p, max_new_tokens=30) for p in prompts]
        served = [s.result(timeout=600) for s in streams]
        assert pool.window_group.released_behind > 0
        assert pool.leak_check() == 0
        assert pool.used_pages == 0
        assert len(pool.window_group._free) == pool.window_group.num_pages
    finally:
        service._teardown_service(suppress=True)

    for prompt, out in zip(prompts, served):
        assert len(out) == 30
        full = jnp.asarray(np.concatenate([prompt, out]))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(reference.forward(params, MODEL, full))
        at = np.arange(len(prompt) - 1, len(full) - 1)
        best = logits[at].max(axis=-1)
        got = logits[at, np.asarray(out)]
        # float32 program against float32 reference: default against
        # highest matmul precision and the cache's reassociation, as in
        # the full pass; a served token may differ from the reference's
        # choice only inside a near-tie of that width
        assert float((best - got).max()) < 5e-4


@pytest.mark.parametrize("decode_attention", ["pallas", "reference"])
def test_decode_kv_blocks_event_counts_both_layer_groups(built, decode_attention):
    """While tracing, every decode dispatch through the pool kernel
    records what the kernel's page fetch does at the dispatch's lengths,
    the full and the window group each counted once, by the kernel's own
    arithmetic; the reference path records nothing."""
    from zookeeper_tpu import ops
    from zookeeper_tpu.observability import trace

    _, params = built
    service = _service(params, decode_attention=decode_attention)
    engine, scheduler = service.build_service()
    tracer = trace.enable()
    try:
        prompt = np.arange(40, dtype=np.int32) % VOCAB
        scheduler.submit(prompt, max_new_tokens=6).result(timeout=600)
        records = tracer.snapshot()
        tracer.clear()
        lengths = np.array([0, 45, 17], np.int32)
        engine._note_kv_blocks(lengths)
        noted = tracer.snapshot()
    finally:
        trace.disable()
        service._teardown_service(suppress=True)
    blocks = [r for r in records if r["name"] == "decode_kv_blocks"]
    dispatches = [r for r in records if r["name"] == "decode_dispatch"]
    if decode_attention == "reference":
        assert not blocks and not noted and dispatches
        return
    assert len(blocks) == len(dispatches) > 0
    for record in blocks:
        attrs = record["attrs"]
        assert 0 < attrs["work_items"] <= attrs["pages_live"]
        assert attrs["pages_live"] <= attrs["pages_block_capacity"]
    # the page size of 4 and rows of 2 heads x 32: both groups' blocks
    # hold a slot's whole band, one work item a slot and group
    ps, max_pages = 4, POSITIONS // 4
    want = np.zeros(3, np.int64)
    for window in (None, WINDOW):
        n = ops.pool_decode_block_pages(
            ps, ops.kv_row_width(FIELDS["num_kv_heads"], FIELDS["head_dim"]),
            4, max_pages, window,
        )
        want += ops.pool_decode_work(
            lengths, page_size=ps, max_pages=max_pages, block_pages=n,
            window=window,
        )
    (record,) = noted
    assert record["name"] == "decode_kv_blocks"
    assert list(record["attrs"].values()) == [int(n) for n in want]
    assert record["attrs"]["work_items"] == 2 * len(lengths)


def test_window_layers_run_as_full_attention_are_caught(built):
    """The planted fault of the benchmark's check: the same weights with
    every layer full attend further back than the published model, and
    the comparison above fails by orders of magnitude."""
    _, params = built
    model = TransformerLM()
    configure(model, dict(FIELDS, layer_types=["full"]))
    module = model.build((POSITIONS,), VOCAB)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, POSITIONS), 0, VOCAB)
    got = module.apply({"params": params}, tokens)[0]
    with jax.default_matmul_precision("highest"):
        want = reference.forward(params, MODEL, tokens[0])
    # (a full layer also takes the YaRN table where a window layer takes
    # the plain one, so the two part ways from the second position on)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=2e-4)
    assert float(jnp.max(jnp.abs(got[WINDOW:] - want[WINDOW:]))) > 0.05


def test_engine_refuses_what_it_cannot_serve(built):
    _, params = built
    with pytest.raises(ValueError, match="only KV layout"):
        _service(params, kv_layout="slots").build_service()
    with pytest.raises(ValueError, match="prefix_cache=true is not implemented"):
        _service(params, prefix_cache=True).build_service()
    with pytest.raises(ValueError, match="prefill_chunk_tokens > 0 is not implemented"):
        _service(params, prefill_chunk_tokens=16).build_service()


def test_moe_load_is_sown_per_layer(built):
    module, params = built
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 24), 0, VOCAB)
    _, sown = module.apply({"params": params}, tokens, mutable=["moe_load"])
    for i in range(4):
        load = sown["moe_load"][f"block{i}"]["tokens_per_expert"]
        assert load.shape == (8,)
        assert int(load.sum()) == 24 * 2  # every token, its two experts
