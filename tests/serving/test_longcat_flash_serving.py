"""LongCat-Flash's double layer through the decode engine, the latent page
pool and the scheduler, at a tiny size on the CPU, float32, against the
plain reference (``benchmarks/reference/longcat_flash.py``): the shortcut
MoE's sum carried from the first sub-layer to the second in the prefill,
decode and extend programs, the identity experts' counter, and what is
refused at bind."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "models")
)
import longcat_flash_tiny as tiny  # noqa: E402

from zookeeper_tpu.core import configure  # noqa: E402
from zookeeper_tpu.observability import trace  # noqa: E402
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
            "slots": SLOTS, "seq_buckets": (32, 64), "kv_capacity": tiny.POSITIONS,
            "page_size": PAGE, "prefix_cache": False, "decode_attention": "pallas",
            **conf,
        },
        name="engine",
    )
    return engine.bind(module, params, {})


def test_served_tokens_are_the_references_and_the_counters_add_up(built):
    """Four unshared prompts of 9-50 tokens, 6 tokens each, on 3 slots,
    traced: every served token is the reference's own choice or within
    1e-4 of it (float32, sums in another order); a dispatch's identity
    choices and held choices are parts of its routed ones, and the
    identity experts take some."""
    module, params = built
    tracer = trace.enable()
    try:
        engine = make_engine(module, params)
        assert engine.decode_attention_flavor == "pallas_mla"
        assert [sorted(layer) for layer in engine._cache] == [["c"]] * 4
        sched = DecodeScheduler()
        configure(sched, {}, name="sched")
        sched.bind(engine)
        rng = np.random.default_rng(4)
        prompts = [
            rng.integers(0, tiny.VOCAB, size=n).astype(np.int32) for n in (9, 50, 23, 31)
        ]
        streams = [sched.submit(p, max_new_tokens=6) for p in prompts]
        served = [s.result(timeout=600) for s in streams]
        records = tracer.snapshot()
    finally:
        trace.disable()
    for prompt, out in zip(prompts, served):
        full = np.concatenate([prompt, out]).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(reference.forward(params, tiny.MODEL, jnp.asarray(full)))
        logits = logits[len(prompt) - 1 : -1]
        gap = logits.max(-1) - logits[np.arange(len(out)), out]
        assert float(gap.max()) < 1e-4, gap
    zero = [r["attrs"] for r in records if r["name"] == "moe_zero_choices"]
    held = [r["attrs"] for r in records if r["name"] == "moe_held_choices"]
    loads = [r["attrs"] for r in records if r["name"] == "moe_tokens_per_expert"]
    assert zero and len(zero) == len(loads)
    assert not held  # the tiny model holds every real expert
    for z, load in zip(zero, loads):
        counts = np.asarray(load["counts"])
        assert counts.shape == (2, tiny.REAL)  # 2 expert sub-layers
        assert 0 < z["choices_zero"] and z["choices_zero"] + counts.sum() == z["choices_routed"]


def test_a_held_share_counts_its_choices_apart_from_the_identity_ones(built):
    """Holding experts 2-5 of 8: the held choices, the identity choices
    and the choices of the absent experts make up the routed ones."""
    module, params = built
    f, d = tiny.FIELDS["expert_dim"], tiny.FIELDS["d_model"]
    share = dict(params)
    for i in (0, 2):
        block = dict(params[f"block{i}"])
        for name, width in (("experts_gate", f), ("experts_up", f), ("experts_down", d)):
            block[name] = block[name][:, 2 * width : 6 * width]
        share[f"block{i}"] = block
    tracer = trace.enable()
    try:
        engine = make_engine(module.clone(held_experts=(2, 4)), share)
        sched = DecodeScheduler()
        configure(sched, {}, name="sched")
        sched.bind(engine)
        sched.generate(np.arange(40, 60, dtype=np.int32), max_new_tokens=5)
        records = tracer.snapshot()
    finally:
        trace.disable()
    zero = [r["attrs"] for r in records if r["name"] == "moe_zero_choices"]
    held = [r["attrs"] for r in records if r["name"] == "moe_held_choices"]
    assert zero and len(zero) == len(held)
    for z, h in zip(zero, held):
        assert z["choices_routed"] == h["choices_routed"]
        assert z["choices_zero"] + h["choices_held"] < h["choices_routed"]


def _refuse_verify_program(module, params):
    make_engine(module, params)._verify_compiled(2)


def _refuse_page_handoff(module, params):
    make_engine(module, params).transfer_width


def _refuse_int8(module, params):
    make_engine(module, params, kv_quant="int8")


@pytest.mark.parametrize(
    "attempt,error,message",
    [
        (_refuse_int8, ValueError, "kv_quant='int8' is not implemented for latent attention"),
        (_refuse_verify_program, NotImplementedError, "speculative decoding is not implemented for latent"),
        (_refuse_page_handoff, NotImplementedError, "page handoff is not implemented for latent"),
    ],
    ids=["int8", "verify", "handoff"],
)
def test_what_the_double_layer_refuses(built, attempt, error, message):
    """Each refusal names the mechanism (the latent pool's)."""
    with pytest.raises(error, match=message):
        attempt(*built)
