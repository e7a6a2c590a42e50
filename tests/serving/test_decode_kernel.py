"""Engine-level pool-decode-kernel certification (docs/DESIGN.md §17):
the ``decode_attention="pallas"`` decode_step program must be
TOKEN-EXACT against the reference flavor through the real
continuous-batching path (mid-stream slot refill included), degrade to
the reference on unsupported geometry, publish the HBM-accounting
gauges, and survive the donated-cache crash-recovery leg with the
kernel selected.

The reference engine IS the oracle here: its own token parity against
the full-context ``greedy_decode`` is pinned by
tests/serving/test_decode_engine.py, so kernel == reference composes
into kernel == full-context oracle without paying a second
greedy-recompute sweep. All CPU (interpret-mode kernel), synchronous
scheduler.
"""

import logging

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving import WorkerCrashedError
from zookeeper_tpu.serving.decode import DecodeEngine

from tests.serving.test_decode_engine import VOCAB, build_lm, make_scheduler

pytestmark = pytest.mark.serving


def kernel_engine(module, params, state, *, flavor, slots=2,
                  kv_capacity=64, **conf):
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": slots,
            "seq_buckets": (8, 16),
            "kv_capacity": kv_capacity,
            "decode_attention": flavor,
            "prefix_cache": False,
            **conf,
        },
        name=f"kengine_{flavor}",
    )
    engine.bind(module, params, state)
    return engine


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    # > slots so later admissions REFILL freed slots mid-traffic: the
    # kernel then decodes over caches whose rows past ``lengths`` hold
    # the previous occupant's K/V — the garbage-masking leg, live.
    return [
        rng.integers(1, VOCAB, size=int(rng.integers(1, 16))).astype(
            np.int32
        )
        for _ in range(7)
    ]


def serve(engine, prompts, new_tokens=8):
    sched = make_scheduler(engine, max_new_tokens=new_tokens)
    streams = [sched.submit(p) for p in prompts]
    sched.drain()
    return [s.result() for s in streams]


def test_kernel_engine_token_exact_vs_reference_with_refill(lm, prompts):
    module, params, state, _ = lm
    ref_engine = kernel_engine(module, params, state, flavor="reference")
    pal_engine = kernel_engine(module, params, state, flavor="pallas")
    assert pal_engine.decode_attention_flavor == "pallas"
    ref_warm = ref_engine.warmup()
    pal_warm = pal_engine.warmup()
    ref_out = serve(ref_engine, prompts)
    pal_out = serve(pal_engine, prompts)
    for a, b in zip(ref_out, pal_out):
        np.testing.assert_array_equal(a, b)
    # Slot refill happened (7 requests, 2 slots) with zero recompiles
    # on either flavor — the compile-free steady state holds with the
    # kernel program in the cache.
    assert ref_engine.compile_count == ref_warm
    assert pal_engine.compile_count == pal_warm


def test_unsupported_geometry_auto_degrades_explicit_pallas_raises(
    caplog, monkeypatch
):
    """head_dim 60/3 = 20 is off the kernel's lane quantum. ``auto``
    (resolving to the kernel, as on a TPU backend) must WARN, resolve
    the reference flavor, and still serve token-identically to an
    explicit reference engine; an explicit ``pallas`` that cannot be
    honoured must raise at bind — never serve the reference under the
    kernel's name."""
    import jax

    module, params, state, _ = build_lm(d_model=60, num_heads=3)
    with pytest.raises(ValueError, match="cannot be honoured"):
        kernel_engine(module, params, state, flavor="pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with caplog.at_level(logging.WARNING):
        engine = kernel_engine(module, params, state, flavor="auto")
    assert engine.decode_attention_flavor == "reference"
    assert any(
        "decode_attention='auto'" in r.message for r in caplog.records
    )
    engine.warmup()
    ref = kernel_engine(module, params, state, flavor="reference")
    ref.warmup()
    p = np.arange(1, 9, dtype=np.int32)
    np.testing.assert_array_equal(
        make_scheduler(engine, max_new_tokens=6).generate(p),
        make_scheduler(ref, max_new_tokens=6).generate(p),
    )


def test_module_level_override_logits_pinned(lm):
    """decode_step_paged's ``attention_override`` seam at the module
    level: kernel logits within documented-ULP of the reference trace
    and argmax token-exact (the tolerance contract of
    tests/ops/test_pool_attention.py, composed through the whole block
    stack)."""
    import jax.numpy as jnp

    from zookeeper_tpu.ops import (
        pool_decode_attention,
        pool_paged_decode_attention,
    )
    from zookeeper_tpu.serving.decode import allocate_page_pool

    module, params, state, variables = lm
    slots, page_size, max_pages = 2, 16, 4
    cache = allocate_page_pool(
        module.num_layers, slots * max_pages, page_size, 4, 8, jnp.float32
    )
    # Slot 1's pages first, slot 0's after: any table serves.
    table = jnp.asarray([[4, 5, 6, 7], [0, 1, 2, 3]], jnp.int32)
    tokens = jnp.asarray([3, 41], jnp.int32)
    lengths = jnp.asarray([0, 17], jnp.int32)
    ref_logits, ref_cache = module.apply(
        variables, tokens, lengths, cache, table,
        method="decode_step_paged",
        attention_override=pool_decode_attention,
    )
    # No override is the reference.
    default_logits, _ = module.apply(
        variables, tokens, lengths, cache, table,
        method="decode_step_paged",
    )
    np.testing.assert_array_equal(
        np.asarray(default_logits), np.asarray(ref_logits)
    )
    pal_logits, pal_cache = module.apply(
        variables, tokens, lengths, cache, table,
        method="decode_step_paged",
        attention_override=pool_paged_decode_attention,
    )
    np.testing.assert_allclose(
        np.asarray(pal_logits), np.asarray(ref_logits), atol=1e-4, rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.argmax(np.asarray(pal_logits), -1),
        np.argmax(np.asarray(ref_logits), -1),
    )
    # The cache WRITE path is shared (outside the attention flavor):
    # layer 0's written rows are bit-identical (its input residual
    # stream precedes any attention); deeper layers inherit the
    # previous layer's attention ULPs and agree to the same tolerance.
    np.testing.assert_array_equal(
        np.asarray(ref_cache[0]["k"]), np.asarray(pal_cache[0]["k"])
    )
    for r, p in zip(ref_cache, pal_cache):
        np.testing.assert_allclose(
            np.asarray(r["k"]), np.asarray(p["k"]), atol=1e-5, rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(r["v"]), np.asarray(p["v"]), atol=1e-5, rtol=1e-5
        )


def test_decode_attention_field_validated(lm):
    module, params, state, _ = lm
    with pytest.raises(ValueError, match="decode_attention"):
        kernel_engine(module, params, state, flavor="typo")


def test_kernel_engine_publishes_hbm_gauges(lm, prompts):
    from zookeeper_tpu.observability.registry import default_registry

    module, params, state, _ = lm
    engine = kernel_engine(module, params, state, flavor="pallas")
    engine.warmup()
    reg = default_registry()
    # Bind-time: provisioned KV bytes exported (the PR-9 accounting
    # gap); the PER-ENGINE mbu is exactly the -1-unknown sentinel
    # before this engine's first dispatch. It has no registry series
    # (PR 24: static cost-analysis bytes, read by nothing).
    assert reg.gauge("zk_decode_kv_bytes").value == float(
        engine.kv_cache_nbytes
    )
    assert "zk_decode_mbu" not in {inst.name for inst in reg.collect()}
    assert engine.decode_mbu == -1.0
    serve(engine, prompts[:3], new_tokens=4)
    mbu = engine.decode_mbu
    assert mbu == -1.0 or mbu >= 0.0
    sched = make_scheduler(engine)
    status = sched.status()
    assert status["kv_cache_bytes"] == engine.kv_cache_nbytes
    assert status["kv_bytes_per_slot"] == engine.kv_cache_nbytes // 2
    assert status["decode_attention"] == "pallas"
    assert "decode_mbu" in status


@pytest.mark.chaos
def test_crash_recovery_with_kernel_selected(lm, prompts):
    """The donated-cache ``_reset_cache`` leg with the kernel program
    live: an injected scheduler crash fails streams cleanly, and a
    resubmit on the restarted scheduler serves from the reallocated
    cache — token-identical to the reference flavor, zero recompiles."""
    module, params, state, _ = lm
    engine = kernel_engine(module, params, state, flavor="pallas")
    warm = engine.warmup()
    sched = make_scheduler(engine, max_new_tokens=6)
    p = np.arange(1, 8, dtype=np.int32)
    with faults.injected(FaultPlan(decode_worker_crash=1)):
        stream = sched.submit(p)
        with pytest.raises(WorkerCrashedError):
            stream.result()
    got = sched.generate(p)  # restarted scheduler, fresh zeroed cache
    ref = kernel_engine(module, params, state, flavor="reference")
    ref.warmup()
    want = make_scheduler(ref, max_new_tokens=6).generate(p)
    np.testing.assert_array_equal(got, want)
    assert engine.compile_count == warm
