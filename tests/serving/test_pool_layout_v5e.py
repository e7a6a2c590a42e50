"""The paged decode path compiled for a described (not attached) TPU
v5e at GPT-2 XL's widths, as the benchmark's serving cells run it
(3072 pages x 16 rows, 25 heads of 64, bfloat16, 48 slots): the chip's
own compiler must accept the pool kernel, and no program may copy a
whole leaf of the donated page pool (docs/DESIGN.md §20; before PR 25
every one held four such copies a layer, 110 ms a call on the chip).
One layer: the layers are alike. No time comes from here."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from zookeeper_tpu import ops
from zookeeper_tpu.models.transformer import (
    TransformerLMModule,
    _pool_write_rows,
)
from zookeeper_tpu.observability.hlo import (
    count_copies_of_size,
    count_row_scatters_of_size,
)
from zookeeper_tpu.serving.decode.pages import allocate_page_pool

PAGES, PAGE_SIZE, HEADS, HEAD_DIM, SLOTS, MAX_PAGES = 3072, 16, 25, 64, 48, 64


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shaped(one_chip):
    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree,
        )

    return shaped


@pytest.fixture(scope="module")
def module():
    return TransformerLMModule(
        vocab_size=50257, num_layers=1, d_model=HEADS * HEAD_DIM,
        num_heads=HEADS, mlp_ratio=4, attention="flash", max_seq_len=1024,
        dtype=jnp.bfloat16,
    )


def pool(quant="none"):
    return jax.eval_shape(
        lambda: allocate_page_pool(
            1, PAGES, PAGE_SIZE, HEADS, HEAD_DIM, jnp.bfloat16, quant=quant
        )
    )


def ints(*shape):
    return jax.ShapeDtypeStruct(shape, np.int32)


def whole_leaf_copies(compiled, cache):
    sizes = {int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(cache)}
    return count_copies_of_size(compiled.as_text(), sizes)


def test_row_width_of_the_cell():
    assert ops.kv_row_width(HEADS, HEAD_DIM) == 1664
    assert pool()[0]["k"].shape == (PAGES, 1, PAGE_SIZE, 1664)


@pytest.mark.parametrize("method, width", [
    ("decode_step_paged", None),
    ("decode_verify_paged", 128),
])
def test_model_step_holds_no_pool_sized_copy(shaped, module, method, width):
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )
    cache = pool()
    kwargs = {}
    if method == "decode_step_paged":
        rows, tokens = SLOTS, ints(SLOTS)
        kwargs["attention_override"] = partial(
            ops.pool_paged_decode_attention, interpret=False
        )
    else:
        rows, tokens = 1, ints(1, width)

    def step(variables, cache, tokens, lengths, table):
        logits, new_cache = module.apply(
            variables, tokens, lengths, cache, table, method=method, **kwargs
        )
        return new_cache, jnp.argmax(logits, axis=-1)

    compiled = jax.jit(step, donate_argnums=1).lower(
        *shaped((variables, cache, tokens, ints(rows), ints(rows, MAX_PAGES)))
    ).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (method == "decode_step_paged")
    assert whole_leaf_copies(compiled, cache) == 0


@pytest.fixture(scope="module")
def lowered_decode_step(shaped, module):
    """``lowered(variables)``: the decode step through the pool kernel,
    lowered for the one described chip at the cell's shapes."""

    def step(variables, cache, tokens, lengths, table):
        logits, new_cache = module.apply(
            variables, tokens, lengths, cache, table,
            method="decode_step_paged",
            attention_override=partial(
                ops.pool_paged_decode_attention, interpret=False
            ),
        )
        return new_cache, jnp.argmax(logits, axis=-1)

    def lowered(variables):
        return jax.jit(step, donate_argnums=1).lower(
            *shaped(
                (variables, pool(), ints(SLOTS), ints(SLOTS),
                 ints(SLOTS, MAX_PAGES))
            )
        )

    return lowered


@pytest.fixture(scope="module")
def bound_and_held(module):
    """The variables' avals as ``bind`` is given them (float32) and as
    the engine holds them (``serving_variables``)."""
    bound = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )
    return bound, jax.eval_shape(module.serving_variables, bound)


def test_decode_step_reads_no_float32_kernel(lowered_decode_step, bound_and_held):
    """The decode step compiled for the avals the engine HOLDS (matmul
    kernels in bfloat16) has no float32 array of a kernel's shape
    anywhere, so no operand of one and no convert from one; compiled for
    the tree as bound it has them in its entry layout (4 bytes an
    element across HBM on every call: 3.6 of the cell's 6.2 ms a step
    before PR 30). The tables stay float32 on both sides: as bound with
    rows of 1600, as held with rows of 13 whole tiles for the gather
    beside the bound token table for the tied head."""
    d = HEADS * HEAD_DIM
    kernels = [
        f"f32[{rows},{cols}]"
        for rows, cols in ((d, 3 * d), (d, d), (d, 4 * d), (4 * d, d))
    ]
    as_bound, as_held = (
        lowered_decode_step(variables).compile().as_text()
        for variables in bound_and_held
    )
    for kernel in kernels:
        assert kernel in as_bound
        assert kernel not in as_held
        assert kernel.replace("f32", "bf16") in as_held
    rows = ops.kv_row_width(1, d)
    assert (d, rows) == (1600, 1664)
    for table in (f"f32[50257,{d}]", f"f32[1024,{d}]"):
        assert table in as_bound
    for table in (f"f32[50257,{rows}]", f"f32[1024,{rows}]"):
        assert table in as_held and table not in as_bound
    assert f"f32[50257,{d}]" in as_held  # the tied head's home
    assert f"f32[1024,{d}]" not in as_held


@pytest.fixture(scope="module")
def compiled_text(shaped, module, lowered_decode_step, bound_and_held):
    """``compiled_text(program, tree)``: the optimised HLO of the decode
    step, a 1,024-token cold prefill or a 128-token extend at the
    cell's widths, over the variables ``"bound"`` or ``"held"``."""
    from functools import lru_cache

    def prefill(variables, tokens, lengths):
        logits, kv = module.apply(variables, tokens, lengths, method="prefill")
        return jnp.argmax(logits, axis=-1), kv

    def extend(variables, cache, tokens, lengths, table, valid):
        logits, new_cache = module.apply(
            variables, tokens, lengths, cache, table, valid=valid,
            method="decode_verify_paged",
        )
        return new_cache, jnp.argmax(logits[:, -1], axis=-1)

    @lru_cache(maxsize=None)
    def text(program, tree):
        variables = bound_and_held[("bound", "held").index(tree)]
        if program == "decode_step":
            lowered = lowered_decode_step(variables)
        elif program == "prefill":
            lowered = jax.jit(prefill).lower(
                *shaped((variables, ints(1, 1024), ints(1)))
            )
        else:
            lowered = jax.jit(extend, donate_argnums=1).lower(
                *shaped(
                    (variables, pool(), ints(1, 128), ints(1),
                     ints(1, MAX_PAGES), ints(1))
                )
            )
        return lowered.compile().as_text()

    return text


#: Elements of the token table and of the position table, as bound and
#: with rows of whole tiles.
TOKEN_TABLE = (50257 * 1600, 50257 * 1664)
POSITION_TABLE = (1024 * 1600, 1024 * 1664)


@pytest.mark.parametrize("program", ["decode_step", "prefill", "extend"])
def test_no_program_re_lays_the_held_token_table(compiled_text, program):
    """Over the tree the engine HOLDS (tables with rows of 13 whole
    tiles) no program copies or transposes an array as large as the
    token table; the decode step none as large as the position table
    either (in a 1,024-token prefill an activation has that size)."""
    text = compiled_text(program, "held")
    assert "f32[50257,1664]{1,0:T(8,128)}" in text  # rows contiguous
    assert count_copies_of_size(text, TOKEN_TABLE) == 0
    if program == "decode_step":
        assert count_copies_of_size(text, TOKEN_TABLE + POSITION_TABLE) == 0


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_bound_token_table_is_re_laid_on_every_call(compiled_text, program):
    """The witness that the count sees the fault: over the tree AS
    BOUND the chip's compiler gives ``f32[50257,1600]`` the layout that
    wastes fewest tile bytes, ``{0,1}`` (1600 is 12.5 tiles), in which
    a row is not contiguous, and copies all 322 MB row-major before the
    gather, in every program, on every call (1 ms of the cell's 3.9 ms
    decode step before the engine held the table padded); the decode
    step copies the position table the same way."""
    text = compiled_text(program, "bound")
    assert "f32[50257,1600]{0,1:T(8,128)}" in text
    tokens = count_copies_of_size(text, TOKEN_TABLE)
    assert tokens >= 1
    if program == "decode_step":
        assert count_copies_of_size(text, TOKEN_TABLE + POSITION_TABLE) > tokens


def test_decode_step_options_are_the_compilers(lowered_decode_step, bound_and_held):
    """What the engine compiles its decode step with on a TPU
    (``_DECODE_STEP_TPU_OPTIONS``) is an option the chip's compiler
    knows, and it does what the engine wants of it: over the held
    (bfloat16) kernels the step has sliced weight prefetches without
    it and none with it."""
    from zookeeper_tpu.serving.decode.engine import _DECODE_STEP_TPU_OPTIONS

    lowered = lowered_decode_step(bound_and_held[1])
    assert "slice-start" in lowered.compile().as_text()
    with_options = lowered.compile(
        compiler_options=_DECODE_STEP_TPU_OPTIONS
    ).as_text()
    assert "slice-start" not in with_options
    assert "tpu_custom_call" in with_options


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_prefill_write_holds_no_pool_sized_copy(shaped, quant):
    cache = pool(quant)
    lead = (1, 1024)
    rows = {
        name: jax.ShapeDtypeStruct(lead + (HEADS, HEAD_DIM), jnp.bfloat16)
        for name in ("k", "v")
    }

    def write(layer, rows, pages, offsets):
        return _pool_write_rows(layer, rows, pages, offsets)

    compiled = jax.jit(write, donate_argnums=0).lower(
        *shaped((cache[0], rows, ints(*lead), ints(*lead)))
    ).compile()
    # int8: the float32 scale arrays ([3072, 1, 16, 25], a sixteenth of
    # the pool's bytes) are still held transposed and copied around
    # their scatter; the int8 rows are not.
    rows_only = [{"k": cache[0]["k"], "v": cache[0]["v"]}]
    assert whole_leaf_copies(compiled, rows_only) == 0


@pytest.fixture(scope="module")
def engine_programs(shaped):
    """``text(program)``: the engine's own ``prefill_fn`` (a cold
    1,024-token prefill) or ``extend_fn`` (a 128-token warm suffix), as
    an engine bound at the cell's pool, widths and buckets (one layer, a
    small vocabulary: neither is in the write) builds it, compiled for
    the one described chip; and the sizes of its pool's leaves."""
    from functools import lru_cache

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models.transformer import TransformerLM
    from zookeeper_tpu.serving.decode import DecodeEngine

    model = TransformerLM()
    configure(
        model,
        {
            "num_layers": 1, "d_model": HEADS * HEAD_DIM, "num_heads": HEADS,
            "mlp_ratio": 4, "compute_dtype": "bfloat16", "attention": "flash",
        },
    )
    module = model.build((1024,), 512)
    params, _ = model.initialize(module, (1024,), seed=0)
    engine = DecodeEngine()
    configure(
        engine,
        {
            "slots": SLOTS, "page_size": PAGE_SIZE, "kv_capacity": 1024,
            "seq_buckets": (128, 512, 1024), "prefix_cache": True,
            "decode_attention": "reference",
        },
        name="engine",
    )
    engine.bind(module, params, {})
    leaves = {int(np.prod(x.shape)) for x in jax.tree.leaves(engine._cache)}
    assert leaves == {PAGES * PAGE_SIZE * 1664}
    built = {}

    def keep(key, fn, example, **_):
        built[key] = (fn, example)

    object.__setattr__(engine, "_aot", keep)  # build, do not compile here

    @lru_cache(maxsize=None)
    def text(program):
        if program == "prefill":
            engine._prefill_compiled(1, 1024)
        else:
            engine._extend_compiled(1, 128)
        fn, example = built.popitem()[1]
        return jax.jit(fn, donate_argnums=1).lower(
            *shaped(example)
        ).compile().as_text()

    return text, leaves


def test_cold_prefill_program_writes_the_pool_by_page(engine_programs):
    """``prefill_fn`` as the engine builds it at the cell's shapes: no
    scatter over a pool leaf whose window is one row (until PR 34 it held
    two, of 1,024 indices each: 7.5 of a prefill's 20 ms on the chip), a
    scatter a leaf whose window is a page, and no copy of a leaf; the
    extend program, whose window starts anywhere in a page, keeps the row
    write."""
    text, leaves = engine_programs
    prefill = text("prefill")
    assert count_row_scatters_of_size(prefill, leaves) == 0
    by_page = [
        line for line in prefill.splitlines()
        if " scatter(" in line and "update_window_dims={1,2}" in line
    ]
    assert len(by_page) == 2
    assert all(f"bf16[{PAGES},{PAGE_SIZE},1664]" in line for line in by_page)
    assert count_copies_of_size(prefill, leaves) == 0
    extend = text("extend")
    assert count_row_scatters_of_size(extend, leaves) == 2
    assert count_copies_of_size(extend, leaves) == 0


def kernel_scoped_vmem_requests(compiled):
    """Bytes of scoped VMEM each Pallas custom call of a compiled program
    asks the chip for (its ``vmem_limit_bytes``)."""
    import re

    return [
        int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)[1])
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_pool_kernel_fetches_its_own_pages(shaped, quant):
    """The kernel alone at the serving cells' shape (48 slots x 64
    pages, rows 1664 wide, the lane path): Mosaic lowers the page
    copies into the double-buffered block, the program holds ONE custom
    call that asks for less scoped VMEM than the package ever requests
    of a v5e core, and no pool leaf is copied on the way in."""
    from zookeeper_tpu.ops.blocks import _VMEM_LIMIT_CAP

    cache = pool(quant)[0]
    block = ops.pool_decode_block_pages(
        PAGE_SIZE, 1664, cache["k"].dtype.itemsize, MAX_PAGES
    )
    assert block * PAGE_SIZE in (128, 256)  # whole 128-key sub-blocks

    def attend(q, layer, table, lengths):
        return ops.pool_paged_decode_attention(
            q, layer["k"], layer["v"], table, lengths,
            k_scale=layer.get("k_scale"), v_scale=layer.get("v_scale"),
            interpret=False,
        )

    q = jax.ShapeDtypeStruct((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(attend).lower(
        *shaped((q, cache, ints(SLOTS, MAX_PAGES), ints(SLOTS)))
    ).compile()
    (request,) = kernel_scoped_vmem_requests(compiled)
    assert request <= _VMEM_LIMIT_CAP
    assert whole_leaf_copies(compiled, [cache["k"]]) == 0


def test_int8_pool_kernel_compiles(shaped):
    cache = pool("int8")[0]

    def attend(q, layer, table, lengths):
        return ops.pool_paged_decode_attention(
            q, layer["k"], layer["v"], table, lengths,
            k_scale=layer["k_scale"], v_scale=layer["v_scale"],
            interpret=False,
        )

    q = jax.ShapeDtypeStruct((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(attend).lower(
        *shaped((q, cache, ints(SLOTS, MAX_PAGES), ints(SLOTS)))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert whole_leaf_copies(compiled, [cache["k"]]) == 0

