"""The kernels of the ``mellum2_8l`` cell compiled for a described (not
attached) TPU v5e at the published widths (32 query heads over 4
key/value heads of 128, bfloat16, 64 slots, pages of 16, a window of
1,024): the chip's own compiler must accept the grouped pool kernel with
and without a window, the banded, grouped flash forward, and the experts'
grouped matmul at a decode step's and a prefill's rows. No time comes from
here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from zookeeper_tpu import ops

SLOTS, HEADS, KV_HEADS, HEAD_DIM, PAGE, MAX_PAGES, WINDOW = 64, 32, 4, 128, 16, 512, 1024


@pytest.fixture(scope="module")
def shaped():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return shaped


@pytest.mark.parametrize(
    "pages,window",
    [(SLOTS * MAX_PAGES, None), (SLOTS * (WINDOW // PAGE + 2), WINDOW)],
    ids=["full-group", "window-group"],
)
def test_grouped_pool_kernel_compiles(shaped, pages, window):
    def attend(q, k, v, table, lengths):
        return ops.pool_paged_decode_attention(
            q, k, v, table, lengths, kv_heads=KV_HEADS, window=window,
            interpret=False,
        )

    pool = shaped((pages, 1, PAGE, KV_HEADS * HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(attend).lower(
        shaped((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        shaped((SLOTS, MAX_PAGES), np.int32), shaped((SLOTS,), np.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "pages,window",
    [(SLOTS * MAX_PAGES, None), (SLOTS * (WINDOW // PAGE + 2), WINDOW)],
    ids=["full-group", "window-group"],
)
def test_grouped_pool_kernel_fetches_its_own_pages(shaped, pages, window):
    """The matmul path at the cell's shape (64 slots, rows 512 wide):
    a work item of the derived block of pages (whole 128-key
    sub-blocks, never more than a window's band spans) lowers through
    Mosaic as ONE custom call whose scoped-VMEM request is under what
    the package ever asks of a v5e core, and no pool is copied on the
    way in."""
    from tests.serving.test_pool_layout_v5e import kernel_scoped_vmem_requests
    from zookeeper_tpu.observability.hlo import count_copies_of_size
    from zookeeper_tpu.ops.blocks import _VMEM_LIMIT_CAP

    width = KV_HEADS * HEAD_DIM
    block = ops.pool_decode_block_pages(PAGE, width, 2, MAX_PAGES, window)
    assert block * PAGE % 128 == 0 and block <= MAX_PAGES
    if window:
        assert block <= window // PAGE + 1

    def attend(q, k, v, table, lengths):
        return ops.pool_paged_decode_attention(
            q, k, v, table, lengths, kv_heads=KV_HEADS, window=window,
            interpret=False,
        )

    pool = shaped((pages, 1, PAGE, width), jnp.bfloat16)
    compiled = jax.jit(attend).lower(
        shaped((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        shaped((SLOTS, MAX_PAGES), np.int32), shaped((SLOTS,), np.int32),
    ).compile()
    (request,) = kernel_scoped_vmem_requests(compiled)
    assert request <= _VMEM_LIMIT_CAP
    assert count_copies_of_size(
        compiled.as_text(), {pages * PAGE * width}
    ) == 0


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "banded"])
def test_grouped_flash_forward_compiles(shaped, window):
    def attend(q, k, v):
        return ops.flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )

    s = 3584
    compiled = jax.jit(attend).lower(
        shaped((1, s, HEADS, HEAD_DIM), jnp.bfloat16),
        shaped((1, s, KV_HEADS, HEAD_DIM), jnp.bfloat16),
        shaped((1, s, KV_HEADS, HEAD_DIM), jnp.bfloat16),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "rows,tm", [(SLOTS * 8, 128), (7168 * 8, 256)], ids=["decode", "prefill"]
)
@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)], ids=["up", "down"])
def test_expert_grouped_matmul_compiles(shaped, rows, tm, k, n):
    """A row tile against one expert's whole block fits VMEM, and no
    operand is copied on the way in (64 experts side by side)."""
    from zookeeper_tpu.ops.moe import _gmm

    compiled = jax.jit(
        lambda a, b, s: _gmm(a, b, s, out_dtype=jnp.bfloat16, tm=tm)
    ).lower(
        shaped((rows, k), jnp.bfloat16), shaped((k, 64 * n), jnp.bfloat16),
        shaped((64,), np.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{k},{64 * n}]" not in "".join(
        line for line in text.splitlines() if " copy(" in line
    )


# -- the kernels of the ``falcon_h1_34b_4l`` cell (PR 31) ---------------------
# 20 query heads over 4 key/value heads of 128 (a group of 5), 128 slots of
# 2,048 tokens; a state-space mixer of 32 heads of 128 with a state of 256
# in 2 groups, chunks of 128. In this file because one process loads the
# TPU's compiler (the guide's section 2).

F_SLOTS, F_HEADS, F_MAX_PAGES = 128, 20, 128
SSM_HEADS, SSM_P, SSM_N, SSM_GROUPS, SSM_CHUNK = 32, 128, 256, 2, 128


def _state_touching(text, needle):
    """The entry computation's instructions (not parameters) whose HLO
    text holds ``needle``: what reads or writes an array of that shape."""
    return [
        line.split(" = ", 1)[1] for line in text.splitlines()
        if " = " in line and needle in line and "parameter(" not in line
        and line.lstrip().startswith(("%", "ROOT %"))
        and " fused_computation" not in line
    ]


def test_group_of_five_pool_kernel_compiles(shaped):
    def attend(q, k, v, table, lengths):
        return ops.pool_paged_decode_attention(
            q, k, v, table, lengths, kv_heads=KV_HEADS, interpret=False
        )

    assert ops.decode_attention_supported(KV_HEADS, HEAD_DIM)
    pool = shaped((F_SLOTS * F_MAX_PAGES, 1, PAGE, KV_HEADS * HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(attend).lower(
        shaped((F_SLOTS, 1, F_HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        shaped((F_SLOTS, F_MAX_PAGES), np.int32), shaped((F_SLOTS,), np.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s", [128, 1024])
def test_group_of_five_flash_forward_compiles(shaped, s):
    def attend(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, interpret=False)

    compiled = jax.jit(attend).lower(
        shaped((1, s, F_HEADS, HEAD_DIM), jnp.bfloat16),
        shaped((1, s, KV_HEADS, HEAD_DIM), jnp.bfloat16),
        shaped((1, s, KV_HEADS, HEAD_DIM), jnp.bfloat16),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssm_decode_update_is_one_fusion_in_place_that_the_reader_finds(shaped):
    """128 slots' float32 state through the one-token update, left to
    XLA: ONE fusion reads and writes the state, the donated state aliased
    to the output with no copy of it on the way, and the start of that
    fusion's HLO text (what the device trace keeps of an op:
    ``zkbench/tracereduce.py:short_name``) holds the state's shape, which
    is what ``ssm_decode_roofline`` finds it by."""
    import json
    import os

    from zookeeper_tpu.observability.hlo import count_copies_of_size
    from zookeeper_tpu.ops import ssm

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "layer_metrics", "ssm_decode_roofline.json")) as f:
        (needle,) = json.load(f)["params"]["match"]
    state = (F_SLOTS, SSM_HEADS, SSM_P, SSM_N)
    assert needle == "f32[%d,%d,%d,%d]" % state
    compiled = jax.jit(ssm.ssm_decode_update, donate_argnums=0).lower(
        shaped(state, jnp.float32),
        shaped((F_SLOTS, SSM_HEADS, SSM_P), jnp.bfloat16),
        shaped((F_SLOTS, SSM_HEADS), jnp.float32),
        shaped((SSM_HEADS,), jnp.float32),
        shaped((F_SLOTS, SSM_GROUPS, SSM_N), jnp.bfloat16),
        shaped((F_SLOTS, SSM_GROUPS, SSM_N), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    touching = _state_touching(text, needle)
    entry = [t for t in touching if " fusion(" in t and "calls=" in t]
    assert len(entry) == 1 and needle in entry[0][:240], touching
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * int(np.prod(state))
    assert count_copies_of_size(text, {int(np.prod(state))}) == 0


@pytest.mark.parametrize("s", [128, 1024])
def test_ssm_chunk_scan_compiles_under_its_own_name(shaped, s):
    from zookeeper_tpu.ops import ssm

    compiled = jax.jit(
        lambda x, dt, A, B, C: ssm.ssm_chunk_scan(
            x, dt, A, B, C, chunk=SSM_CHUNK, interpret=False
        )
    ).lower(
        shaped((1, s, SSM_HEADS, SSM_P), jnp.bfloat16),
        shaped((1, s, SSM_HEADS), jnp.float32),
        shaped((SSM_HEADS,), jnp.float32),
        shaped((1, s, SSM_GROUPS, SSM_N), jnp.bfloat16),
        shaped((1, s, SSM_GROUPS, SSM_N), jnp.bfloat16),
    ).compile()
    calls = [
        line for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line
    ]
    assert len(calls) == 1 and calls[0].strip().startswith("%_ssm_chunk_scan")


# -- the kernels of the ``solar_open2_ep8_4l`` cell (PR 33) -------------------
# Gated delta-rule linear attention (KDA): 64 heads whose keys and values
# are 128 wide, a float32 state [128, 128] a head, chunks of 64, 128 slots;
# 40 held experts of width 1280 on 4096, 8 choices a token over 320.

K_SLOTS, KDA_HEADS, KDA_P, KDA_CHUNK = 128, 64, 128, 64


def test_kda_decode_update_is_in_place_and_the_reader_finds_it(shaped):
    """128 slots' float32 state through the one-token update, left to XLA:
    the donated state is aliased to the output with no copy of it on the
    way, at most two fusions touch it (one reads what the old state gives
    the key and the query, one scales and corrects it), and the start of
    each one's HLO text (what the device trace keeps of an op) holds the
    state's shape, which is what ``kda_decode_roofline`` finds them by:
    the metric's ``match`` string and the configuration's slots and state
    sizes are held together here."""
    import json
    import os

    from zookeeper_tpu.observability.hlo import count_copies_of_size
    from zookeeper_tpu.ops import kda

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "layer_metrics", "kda_decode_roofline.json")) as f:
        (needle,) = json.load(f)["params"]["match"]
    with open(os.path.join(root, "benchmarks", "configs", "solar_open2_ep8_4l.json")) as f:
        config = json.load(f)
    linear = config["model"]["linear_attn_config"]
    state = (
        config["program"]["engine.slots"], linear["num_heads"],
        linear["head_dim"], linear["head_dim"],
    )
    assert state == (K_SLOTS, KDA_HEADS, KDA_P, KDA_P)
    assert needle == "f32[%d,%d,%d,%d]" % state
    vector = shaped(state[:3], jnp.bfloat16)
    compiled = jax.jit(kda.kda_decode_update, donate_argnums=0).lower(
        shaped(state, jnp.float32), vector, vector, vector,
        shaped(state[:3], jnp.float32), shaped(state[:2], jnp.float32),
    ).compile()
    text = compiled.as_text()
    entry = [
        t for t in _state_touching(text, needle)
        if " fusion(" in t and "calls=" in t
    ]
    assert 1 <= len(entry) <= 2, entry
    assert all(needle in t[:240] for t in entry), entry
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * int(np.prod(state))
    assert count_copies_of_size(text, {int(np.prod(state))}) == 0


@pytest.mark.parametrize("s", [768, 6144])
def test_kda_chunk_scan_compiles_under_its_own_name(shaped, s):
    from zookeeper_tpu.ops import kda

    heads = shaped((1, s, KDA_HEADS, KDA_P), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, g, beta, lengths: kda.kda_chunk_scan(
            q, k, v, g, beta, chunk=KDA_CHUNK, lengths=lengths, interpret=False
        )
    ).lower(
        heads, heads, heads, shaped((1, s, KDA_HEADS, KDA_P), jnp.float32),
        shaped((1, s, KDA_HEADS), jnp.float32), shaped((1,), np.int32),
    ).compile()
    calls = [
        line for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line
    ]
    assert len(calls) == 1 and calls[0].strip().startswith("%_kda_chunk_scan")


@pytest.mark.parametrize(
    "rows,tm", [(K_SLOTS * 8, 128), (6144 * 8, 256)], ids=["decode", "prefill"]
)
@pytest.mark.parametrize("k,n", [(4096, 1280), (1280, 4096)], ids=["up", "down"])
def test_held_expert_grouped_matmul_compiles(shaped, rows, tm, k, n):
    """The chip's 40 held experts at a decode step's and the largest
    prefill's routed pairs: one expert's whole block (10 MB) beside a row
    tile overflows VMEM, so the block is taken in two column tiles."""
    from zookeeper_tpu.ops.moe import _column_tile, _gmm

    tn = _column_tile(k, n, 2)
    assert tn == n // 2 and _column_tile(2304, 896, 2) == 896
    compiled = jax.jit(
        lambda a, b, s: _gmm(a, b, s, out_dtype=jnp.bfloat16, tm=tm, tn=tn)
    ).lower(
        shaped((rows, k), jnp.bfloat16), shaped((k, 40 * n), jnp.bfloat16),
        shaped((40,), np.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{k},{40 * n}]" not in "".join(
        line for line in text.splitlines() if " copy(" in line
    )
