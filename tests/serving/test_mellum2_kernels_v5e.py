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
