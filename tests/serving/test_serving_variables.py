"""What the decode engine holds of a model's variables
(``TransformerLMModule.serving_variables``; ``DecodeEngine.
_place_variables``): a matmul kernel the programs would cast to the
compute dtype on every call is held cast, once; a table whose rows the
programs gather is held with rows of whole 128-lane tiles (zeros behind
``d_model``, which nothing reads), beside the table as bound where a tied
head multiplies it; everything else is held as bound. The values every
matmul and every sum sees are the same either way, so the claim is an
EQUALITY: every traced method's logits bit for bit, every served token,
with the tree as given and with the tree as held. GPT-2's shape at three
widths (32 and 96: ragged rows; 128: whole tiles, the tables are the
bound arrays), and one with grouped heads, rotary positions, sparse
experts and a head of its own. All CPU."""

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
    "gpt2_96": {"num_layers": 2, "d_model": 96, "num_heads": 4},
    "gpt2_whole_tiles": {"num_layers": 2, "d_model": 128, "num_heads": 4},
    "experts_untied_head": {
        "num_layers": 2, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "positions": "rope", "mlp": "moe", "num_experts": 4,
        "experts_per_token": 2, "expert_dim": 16, "tie_embeddings": False,
    },
}

#: By a leaf's own name: what the engine holds in the compute dtype, and
#: what it holds as bound. A leaf of neither list fails the tests here.
CAST = {"kernel", "experts_gate", "experts_up", "experts_down", "head"}
KEPT = {"scale", "router"}
#: Held with rows of whole lane tiles (the bound array where they are).
TABLES = {"embed", "pos"}
#: Not a parameter: the experts' load counts ride the model state.
STATE = {"tokens_per_expert"}


def build(shape, compute="bfloat16", params="float32", **overrides):
    model = TransformerLM()
    configure(
        model,
        {**SHAPES[shape], "attention": "dense", "compute_dtype": compute,
         "param_dtype": params, **overrides},
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


def by_path(tree):
    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def tied_and_ragged(module):
    return module.tie_embeddings and module.d_model % 128 != 0


@pytest.fixture
def as_given(monkeypatch):
    """Inside: an engine holds the tree as it was given, as the parent
    commit's did."""

    def hold():
        monkeypatch.setattr(
            TransformerLMModule, "serving_leaf", lambda self, path, leaf: leaf
        )
        monkeypatch.setattr(
            TransformerLMModule, "serving_tree", lambda self, tree: tree
        )

    return hold


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_traced_methods_bit_equal_given_and_held(shape):
    module, weights, _ = build(shape)
    given = {"params": weights}
    held = module.serving_variables(given)
    held_at = by_path(held)
    assert any(
        leaf.dtype != held_at[path].dtype
        for path, leaf in by_path(given).items()
    )
    assert (held["params"]["embed"].shape != weights["embed"].shape) == (
        module.d_model % 128 != 0
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
    given_at, held_at, engine_at = map(
        by_path, (given, held, engine._variables)
    )
    assert list(held_at) == list(engine_at)
    width, rows = module.d_model, module.table_row_width
    assert rows % 128 == 0 and 0 <= rows - width < 128
    names = set()
    for path, h in held_at.items():
        name = path.split("'")[-2]
        e = engine_at[path]
        if name == "tied_head":
            # the head's home: the table as bound, the same array
            assert tied_and_ragged(module) and h is weights["embed"]
            np.testing.assert_array_equal(np.asarray(e), np.asarray(h))
            continue
        g = given_at[path]
        names.add(name)
        assert name in CAST | KEPT | TABLES | STATE, name
        assert h.dtype == e.dtype == (
            jnp.bfloat16 if name in CAST else g.dtype
        ), name
        if name in TABLES:
            assert h.shape == e.shape == (g.shape[0], rows)
            assert (h is g) == (rows == width)
            for table in (h, e):
                np.testing.assert_array_equal(
                    np.asarray(table[:, :width]), np.asarray(g)
                )
                assert not np.asarray(table[:, width:]).any()
            continue
        assert (h is g) == (name not in CAST), name
        np.testing.assert_array_equal(
            np.asarray(e), np.asarray(g.astype(e.dtype))
        )
    assert ("['params']['tied_head']" in held_at) == tied_and_ragged(module)
    want = {"kernel", "scale", "embed"} | (
        {"pos"} if shape.startswith("gpt2")
        else {"head", "router", "experts_gate", "experts_up", "experts_down"}
        | STATE
    )
    assert names == want


@pytest.mark.parametrize("shape, compute, params", [
    ("experts_untied_head", "bfloat16", "bfloat16"),
    ("experts_untied_head", "float32", "float32"),
    # a cast would widen: the program's to do
    ("experts_untied_head", "float32", "bfloat16"),
    ("gpt2_whole_tiles", "float32", "float32"),
])
def test_nothing_to_cast_or_pad_is_the_given_tree(shape, compute, params):
    """At a width of whole tiles (``d_model`` 128) with nothing to cast,
    the held tree is the given one, array for array, a tied head's table
    included, and the decode step lowers to the same text."""
    module, weights, state = build(shape, compute, params, d_model=128)
    given = {"params": weights, **dict(state or {})}
    assert module.serving_tree(given) is given
    held = module.serving_variables(given)
    assert jax.tree.structure(held) == jax.tree.structure(given)
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


@pytest.mark.parametrize("config", ["mellum2_8l", "falcon_h1_34b_4l"])
def test_whole_tile_widths_of_the_benchmark_keep_the_bound_table(config):
    """At the widths of the benchmark's two configurations whose rows
    are whole tiles already (2304 = 18 x 128, 5120 = 40 x 128) the rule
    returns the bound ``embed`` array itself, tied or not, and names no
    second home."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", config + ".json")) as f:
        width = json.load(f)["program"]["model.d_model"]
    assert width % 128 == 0
    for tied in (True, False):
        module = TransformerLMModule(
            vocab_size=VOCAB, num_layers=1, d_model=width, num_heads=4,
            mlp_ratio=4, attention="dense", max_seq_len=POSITIONS,
            dtype=jnp.bfloat16, tie_embeddings=tied,
        )
        variables = {
            "params": {
                "embed": jnp.zeros((VOCAB, width), jnp.bfloat16),
                "pos": jnp.zeros((POSITIONS, width), jnp.bfloat16),
            }
        }
        assert module.table_row_width == width
        assert module.serving_tree(variables) is variables
        held = module.serving_variables(variables)
        assert held["params"]["embed"] is variables["params"]["embed"]
        assert held["params"]["pos"] is variables["params"]["pos"]
        assert set(held["params"]) == {"embed", "pos"}


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
    ragged = module.d_model % 128 != 0
    tables = [g for name, g in named_leaves(weights) if name in TABLES]
    attrs = event["attrs"]
    assert attrs["leaves_cast"] == len(cast) > 0
    assert attrs["leaves_padded"] == (len(tables) if ragged else 0)
    assert attrs["bytes_bound"] == sum(
        g.nbytes for g in jax.tree.leaves((weights, state))
    )
    assert attrs["bytes_held"] == sum(
        h.nbytes for h in jax.tree.leaves(engine._variables)
    )
    padding = sum(
        g.shape[0] * (module.table_row_width - module.d_model) * g.itemsize
        for g in tables
    )
    second_home = weights["embed"].nbytes if tied_and_ragged(module) else 0
    assert attrs["bytes_held"] - attrs["bytes_bound"] == (
        padding + second_home - sum(g.size * 2 for g in cast)
    )


def test_padded_tables_live_where_the_programs_take_them():
    """Under a partitioner that reads shapes (auto FSDP shards a leaf's
    largest divisible dimension) the padded tables want another place
    than the bound ones (``pos`` ``[64, 32]`` shards its rows, ``[64,
    128]`` its columns): the engine puts the HELD tree where the rules
    place a tree of its shapes, which is what its programs are compiled
    for, and serves the single device's tokens."""
    from zookeeper_tpu.parallel.partitioner import FsdpPartitioner

    module, weights, state = build("gpt2", "float32")
    part = FsdpPartitioner()
    configure(
        part, {"min_weight_size": 256}, name="fsdp_part"
    )
    part.setup()
    prompt = np.arange(1, 12, dtype=np.int32)

    def serve(**bind):
        engine = DecodeEngine()
        configure(
            engine,
            {"slots": 8, "seq_buckets": (16,), "kv_capacity": POSITIONS,
             "page_size": PAGE, "prefix_cache": False},
            name="engine_fsdp",
        )
        engine.bind(module, weights, state, **bind)
        engine.warmup()
        return engine, make_scheduler(engine, max_new_tokens=6).generate(prompt)

    engine, tokens = serve(partitioner=part)
    held = engine._variables["params"]
    wanted = part.variables_sharding(engine._variables)["params"]
    bound = part.variables_sharding({"params": weights})["params"]
    assert held["pos"].shape == (POSITIONS, 128)
    assert bound["pos"].spec != wanted["pos"].spec
    for name in ("embed", "pos", "tied_head"):
        assert held[name].sharding.is_equivalent_to(wanted[name], 2), name
    np.testing.assert_array_equal(tokens, serve()[1])


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
        # and so are the tables with padded rows
        assert engine._variables["params"]["embed"].shape == (VOCAB, 128)
        padded_embed = weakref.ref(engine._variables["params"]["embed"])
    finally:
        svc._teardown_service(suppress=True)
    gc.collect()
    assert engine._variables is None and engine._cache is None
    assert stream is not None and engine.compile_count > 0  # host state stays
    kernels_and_pool = [ref for ref in held if ref() is None]
    # every pool leaf (2 layers x k, v), every cast kernel (2 x 4) and
    # both padded tables (d_model 32: rows of 128) are gone
    assert len(kernels_and_pool) >= 4 + 8 + 2
    assert padded_embed() is None
