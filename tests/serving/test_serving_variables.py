"""What the decode engine holds of a model's variables
(``TransformerLMModule.serving_variables``; ``DecodeEngine.
_place_variables``): a matmul kernel the programs would cast to the
compute dtype on every call is held cast, once; everything read in
float32 is held as bound. The values every matmul sees are the same
roundings either way, so the claim is an EQUALITY: every traced method's
logits bit for bit, every served token, with the tree as given and with
the tree as held. Two shapes: GPT-2's, and one with grouped heads, rotary
positions, sparse experts and a head of its own. All CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.models.transformer import (
    TransformerLM,
    TransformerLMModule,
)
from zookeeper_tpu.observability import trace
from zookeeper_tpu.serving.decode import DecodeEngine, allocate_page_pool

from tests.serving.test_decode_engine import make_scheduler

pytestmark = pytest.mark.serving

VOCAB, POSITIONS, PAGE = 61, 64, 4

SHAPES = {
    "gpt2": {"num_layers": 2, "d_model": 32, "num_heads": 4},
    "experts_untied_head": {
        "num_layers": 2, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "positions": "rope", "mlp": "moe", "num_experts": 4,
        "experts_per_token": 2, "expert_dim": 16, "tie_embeddings": False,
    },
}

#: By a leaf's own name: what the engine holds in the compute dtype, and
#: what it holds as bound. A leaf of neither list fails the tests here.
CAST = {"kernel", "experts_gate", "experts_up", "experts_down", "head"}
KEPT = {"scale", "embed", "pos", "router"}
#: Not a parameter: the experts' load counts ride the model state.
STATE = {"tokens_per_expert"}


def build(shape, compute="bfloat16", params="float32"):
    model = TransformerLM()
    configure(
        model,
        {**SHAPES[shape], "attention": "dense", "compute_dtype": compute,
         "param_dtype": params},
        name="lm",
    )
    module = model.build((POSITIONS,), VOCAB)
    weights, state = model.initialize(module, (POSITIONS,), seed=5)
    return module, weights, state


def bound_engine(module, weights, state, name):
    engine = DecodeEngine()
    configure(
        engine,
        {"slots": 2, "seq_buckets": (8, 16), "kv_capacity": POSITIONS,
         "page_size": PAGE},
        name=f"engine_{name}",
    )
    return engine.bind(module, weights, state)


def named_leaves(tree):
    return [
        (str(getattr(path[-1], "key", path[-1])), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    ]


@pytest.fixture
def as_given(monkeypatch):
    """Inside: an engine holds the tree as it was given, as the parent
    commit's did."""

    def hold():
        monkeypatch.setattr(
            TransformerLMModule, "serving_leaf", lambda self, path, leaf: leaf
        )

    return hold


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_traced_methods_bit_equal_given_and_held(shape):
    module, weights, _ = build(shape)
    given = {"params": weights}
    held = module.serving_variables(given)
    assert any(
        a.dtype != b.dtype
        for a, b in zip(jax.tree.leaves(given), jax.tree.leaves(held))
    )
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(1, VOCAB, (2, 16)), jnp.int32)
    lengths = jnp.asarray([16, 11], jnp.int32)
    cache = allocate_page_pool(
        module.num_layers, 16, PAGE, module.kv_heads, module.head_dim,
        jnp.bfloat16,
    )
    table = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)

    @jax.jit
    def run(variables):
        first, kv = module.apply(variables, tokens, lengths, method="prefill")
        wide, filled = module.apply(
            variables, tokens[:, :8], jnp.zeros(2, jnp.int32), cache, table,
            method="decode_verify_paged",
        )
        one, after = module.apply(
            variables, tokens[:, 8], jnp.full(2, 8, jnp.int32), filled,
            table, method="decode_step_paged",
        )
        return first, kv, wide, one, after

    for a, b in zip(jax.tree.leaves(run(given)), jax.tree.leaves(run(held))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_engine_serves_the_tokens_the_given_tree_serves(shape, as_given):
    """Cold prefill, a warm extend over the cached prefix and a run of
    decode steps: the tokens of an engine that holds the tree as given."""
    module, weights, state = build(shape)
    rng = np.random.default_rng(11)
    first = rng.integers(1, VOCAB, 13).astype(np.int32)
    second = np.concatenate(
        [first[:9], rng.integers(1, VOCAB, 5).astype(np.int32)]
    )

    def serve(name):
        engine = bound_engine(module, weights, state, name)
        engine.warmup()
        sched = make_scheduler(engine, max_new_tokens=10)
        out = [sched.generate(p) for p in (first, second)]
        assert all(len(set(tokens.tolist())) > 1 for tokens in out)
        assert engine.page_pool.prefix_hit_rate > 0  # the extend ran
        kernel = engine._variables["params"]["block0"]["qkv"]["kernel"]
        return out, kernel.dtype

    held, held_dtype = serve("held")
    as_given()
    given, given_dtype = serve("given")
    assert (held_dtype, given_dtype) == (jnp.bfloat16, jnp.float32)
    for a, b in zip(held, given):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_what_is_cast_and_what_is_not(shape):
    module, weights, state = build(shape)
    given = {"params": weights, **dict(state or {})}
    held = module.serving_variables(given)
    engine = bound_engine(module, weights, state, "dtypes")
    names = set()
    for (name, g), (_, h), (_, e) in zip(
        named_leaves(given), named_leaves(held),
        named_leaves(engine._variables),
    ):
        names.add(name)
        assert name in CAST | KEPT | STATE, name
        assert h.dtype == e.dtype == (
            jnp.bfloat16 if name in CAST else g.dtype
        ), name
        assert (h is g) == (name not in CAST), name
        np.testing.assert_array_equal(
            np.asarray(e), np.asarray(g.astype(e.dtype))
        )
    want = {"kernel", "scale", "embed"} | (
        {"pos"} if shape == "gpt2"
        else {"head", "router", "experts_gate", "experts_up", "experts_down"}
        | STATE
    )
    assert names == want


@pytest.mark.parametrize("compute, params", [
    ("bfloat16", "bfloat16"),
    ("float32", "float32"),
    ("float32", "bfloat16"),  # a cast would widen: the program's to do
])
def test_nothing_to_cast_is_the_given_tree(compute, params):
    module, weights, state = build("experts_untied_head", compute, params)
    given = {"params": weights, **dict(state or {})}
    held = module.serving_variables(given)
    for g, h in zip(jax.tree.leaves(given), jax.tree.leaves(held)):
        assert h is g
    engine = bound_engine(module, weights, state, "same")
    cache = engine._cache
    ints = jax.ShapeDtypeStruct((2,), np.int32)
    table = jax.ShapeDtypeStruct((2, POSITIONS // PAGE), np.int32)

    def lowered(variables):
        def step(variables, cache, tokens, lengths, table):
            return module.apply(
                variables, tokens, lengths, cache, table,
                method="decode_step_paged",
            )

        return jax.jit(step).lower(
            variables, cache, ints, ints, table
        ).as_text()

    assert lowered(engine._variables) == lowered(given)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_placement_event_adds_up(shape):
    module, weights, state = build(shape)
    tracer = trace.enable()
    try:
        engine = bound_engine(module, weights, state, "event")
        (event,) = [
            r for r in tracer.snapshot()
            if r["name"] == "decode_variables_placed"
        ]
    finally:
        trace.disable()
    assert event["phase"] == "i"
    cast = [g for name, g in named_leaves(weights) if name in CAST]
    attrs = event["attrs"]
    assert attrs["leaves_cast"] == len(cast) > 0
    assert attrs["bytes_bound"] == sum(
        g.nbytes for g in jax.tree.leaves((weights, state))
    )
    assert attrs["bytes_held"] == sum(
        h.nbytes for h in jax.tree.leaves(engine._variables)
    )
    assert attrs["bytes_bound"] - attrs["bytes_held"] == sum(
        g.size * 2 for g in cast
    )


def test_teardown_gives_the_device_its_memory_back():
    """The service's teardown drops the held weights and the page pool
    though a stream (and through it the scheduler and the engine) is
    still referenced: what runs next on the chip finds the memory."""
    import gc
    import weakref

    from zookeeper_tpu.serving import LMServingConfig

    svc = LMServingConfig()
    configure(
        svc,
        {
            "model.num_layers": 2, "model.d_model": 32, "model.num_heads": 4,
            "model.attention": "dense", "model.compute_dtype": "bfloat16",
            "seq_len": POSITIONS, "vocab_size": VOCAB, "engine.slots": 2,
            "engine.seq_buckets": (8,), "requests": 0, "verbose": False,
        },
        name="svc_release",
    )
    engine, scheduler = svc.build_service()
    try:
        stream = scheduler.submit(
            np.arange(1, 6, dtype=np.int32), max_new_tokens=3
        )
        assert len(stream.result(timeout=600)) == 3
        held = [
            weakref.ref(leaf)
            for leaf in jax.tree.leaves((engine._variables, engine._cache))
        ]
        # the kernels are the engine's own copies; nothing else holds them
        assert engine._variables["params"]["block0"]["up"]["kernel"].dtype == (
            jnp.bfloat16
        )
    finally:
        svc._teardown_service(suppress=True)
    gc.collect()
    assert engine._variables is None and engine._cache is None
    assert stream is not None and engine.compile_count > 0  # host state stays
    kernels_and_pool = [ref for ref in held if ref() is None]
    # every pool leaf (2 layers x k, v) and every cast kernel (2 x 4) is gone
    assert len(kernels_and_pool) >= 4 + 8
