"""Unit tests for the shared VMEM-aware block policies (ops/blocks.py).

The flash / resid policies moved here from attention.py and
binary_compute.py in docs/DESIGN.md §21 with behavior pinned by their
pre-existing tests (test_ring_attention.py, test_pack_residuals.py; the
pool decode kernel's by test_pool_attention.py); this file covers the re-export identity (the
historical import sites must resolve to the SAME objects, not copies),
the pure-shape-arithmetic contract, and the new §21 binary policies.
"""

from zookeeper_tpu.ops import blocks


# -- re-export identity ------------------------------------------------------


def test_attention_reexports_are_the_blocks_objects():
    """attention.py re-exports the moved policies unchanged: same
    function OBJECTS, so a policy fix lands everywhere at once and the
    historical import sites (bench.py, tests) cannot drift."""
    from zookeeper_tpu.ops import attention

    assert attention._default_flash_blocks is blocks._default_flash_blocks
    assert attention._flash_bwd_vmem_estimate is blocks._flash_bwd_vmem_estimate
    assert (
        attention._pool_decode_block_pages is blocks._pool_decode_block_pages
    )
    assert (
        attention._pool_decode_vmem_estimate
        is blocks._pool_decode_vmem_estimate
    )
    assert attention._FLASH_VMEM_BUDGET == blocks._FLASH_VMEM_BUDGET


def test_binary_compute_imports_are_the_blocks_objects():
    from zookeeper_tpu.ops import binary_compute

    assert binary_compute._resid_blocks is blocks._resid_blocks
    assert binary_compute._round_up is blocks._round_up
    assert binary_compute._divisor_at_most is blocks._divisor_at_most
    assert binary_compute._RESID_BLOCK_BYTES == blocks._RESID_BLOCK_BYTES


def test_blocks_module_is_jax_free():
    """The module contract: pure shape arithmetic, importable without a
    backend (tools and tests size grids without touching jax)."""
    import importlib
    import sys

    assert "jax" not in blocks.__dict__
    # Source-level check too: no lazy import hiding in a function body.
    import inspect

    src = inspect.getsource(blocks)
    assert "import jax" not in src
    # And it must be importable fresh without jax already loaded having
    # polluted sys.modules is not checkable here; the dict check above
    # plus the source check pin the intent.
    importlib.reload(sys.modules["zookeeper_tpu.ops.blocks"])


# -- shared helpers ----------------------------------------------------------


def test_round_up_and_divisor_at_most():
    assert blocks._round_up(1, 8) == 8
    assert blocks._round_up(8, 8) == 8
    assert blocks._round_up(9, 8) == 16
    assert blocks._divisor_at_most(48, 16) == 16
    assert blocks._divisor_at_most(48, 15) == 12
    assert blocks._divisor_at_most(7, 4) == 1  # prime: falls to 1


# -- flash / decode / resid (moved verbatim; spot-pin the headline cases) ----


def test_flash_policy_headline_cases():
    # Sweep winner at the LM leg's pinned config.
    assert blocks._default_flash_blocks(8192, None, None) == (1024, 1024)
    # Awkward length falls back (padding waste > 1/8 at big blocks).
    assert blocks._default_flash_blocks(1100, None, None)[0] <= 128
    # Explicit blocks pass through untouched.
    assert blocks._default_flash_blocks(4096, 256, 512) == (256, 512)


def test_pool_decode_policy_headline_cases():
    # gpt2_xl_24l: 53 KB pages (16 rows of 1664 bf16 lanes) get the
    # floor of two 128-key sub-blocks; mellum2_8l's 16 KB pages fill
    # the 512 KB block; a window's band caps the block at its span.
    assert blocks._pool_decode_block_pages(16, 1664, 2, 64) == 16
    assert blocks._pool_decode_block_pages(16, 512, 2, 512) == 32
    assert blocks._pool_decode_block_pages(16, 512, 2, 5) == 5
    assert blocks._pool_decode_block_pages(16, 512, 2, 0) == 1


def test_resid_blocks_divide_and_fit_budget():
    for h, w, c, itemsize in [(7, 9, 64, 1), (32, 32, 512, 4), (1, 1, 3, 2)]:
        bh, bw = blocks._resid_blocks(h, w, c, itemsize)
        assert h % bh == 0 and w % bw == 0
        assert 32 * c * itemsize * bh * bw <= max(
            blocks._RESID_BLOCK_BYTES, 32 * c * itemsize
        )


# -- §21 binary policies -----------------------------------------------------


def test_binary_gemm_blocks_legal_floor_and_budget():
    """Every auto selection is Mosaic-legal (output dims multiples of
    128 — lane floor; word axis 8 or 16) and inside the VMEM budget."""
    for m, n, kw in [
        (1, 1, 1), (130, 72, 3), (8192, 512, 144), (512, 4096, 16),
        (100000, 128, 8), (128, 100000, 8),
    ]:
        bm, bn, bkw = blocks._default_binary_gemm_blocks(m, n, kw)
        assert bm % 128 == 0 and bn % 128 == 0
        assert bkw in (8, 16)
        assert (
            blocks._binary_gemm_vmem_estimate(bm, bn, bkw)
            <= blocks._BINARY_GEMM_VMEM_BUDGET
        )


def test_binary_gemm_blocks_promote_only_on_big_divisible_axes():
    # Small problem: stays at the 128x128 floor.
    assert blocks._default_binary_gemm_blocks(130, 72, 16) == (128, 128, 16)
    # Large divisible axes promote (padding waste 0 < 1/8); m is
    # promoted first, and n follows as far as the budget allows (at the
    # 8-word depth both fit; at 16 the xor intermediate pins n to 128).
    assert blocks._default_binary_gemm_blocks(8192, 4096, 8) == (512, 256, 8)
    bm, bn, _ = blocks._default_binary_gemm_blocks(8192, 4096, 16)
    assert bm == 512 and bn == 128
    # Awkward axis just past a big block does NOT promote (waste > 1/8).
    bm, _, _ = blocks._default_binary_gemm_blocks(520, 128, 16)
    assert bm == 128


def test_binary_conv_block_n_floor_cap_and_budget():
    # Never below the 128-lane floor, never above 512 / padded co.
    assert blocks._default_binary_conv_block_n(16, 8, 64) == 128
    assert blocks._default_binary_conv_block_n(7, 1, 4096) == 512
    # A huge per-tap intermediate demotes by halving but stops at 128.
    bn = blocks._default_binary_conv_block_n(224, 144, 512)
    assert bn >= 128 and bn % 128 == 0
    assert (
        224 * 144 * bn * 4 <= blocks._BINARY_CONV_VMEM_BUDGET or bn == 128
    )


def test_pack_rows_block_aligned_and_bounded():
    for k, itemsize in [(32, 4), (4608, 4), (4608, 2), (10**6, 4), (32, 1)]:
        rows = blocks._default_pack_rows_block(k, itemsize)
        # 32-aligned: a multiple of every dtype's sublane tile.
        assert rows % 32 == 0
        assert 32 <= rows <= 256
    # Bigger K -> fewer rows (budget-bound), floored at 32.
    assert blocks._default_pack_rows_block(10**6) == 32
    assert blocks._default_pack_rows_block(32) == 256


# -- the scoped-VMEM rule ----------------------------------------------------


def test_vmem_limit_rule_bounds():
    """``vmem_limit_bytes``: never below Mosaic's own default (a small
    kernel compiles as it did with no limit passed), never above the
    cap, and half again the estimate in between."""
    assert blocks.vmem_limit_bytes(0) == blocks._VMEM_DEFAULT_LIMIT
    assert blocks.vmem_limit_bytes(8 << 20) == blocks._VMEM_DEFAULT_LIMIT
    assert blocks.vmem_limit_bytes(26 << 20) == 39 << 20
    assert blocks.vmem_limit_bytes(1 << 40) == blocks._VMEM_LIMIT_CAP
    assert blocks._FLASH_VMEM_BUDGET * 3 // 2 == blocks._VMEM_LIMIT_CAP


def test_no_policy_returns_a_block_past_the_limit_its_call_passes():
    """Every auto block choice's estimate is under the ``vmem_limit_bytes``
    the pallas_call passes for it — the policy and the compiler limit
    are one rule. (Each policy has a smallest legal block it takes
    without a check; the sweep stays where that floor itself fits.)"""
    limit = blocks.vmem_limit_bytes

    for s in (128, 999, 2048, 8192, 65536):
        for d in (8, 64, 128, 256, 1024, 2048):
            for itemsize in (2, 4):
                bq, bk = blocks._default_flash_blocks(
                    s, None, None, head_dim=d, itemsize=itemsize
                )
                est = blocks._flash_bwd_vmem_estimate(bq, bk, d, itemsize)
                assert est <= limit(est), (s, d, itemsize)

    for page_size in (8, 16, 128):
        for width in (128, 512, 1664, 8192):
            for itemsize in (1, 2, 4):
                for span in (1, 66, 512):
                    pages = blocks._pool_decode_block_pages(
                        page_size, width, itemsize, span
                    )
                    est = blocks._pool_decode_vmem_estimate(
                        pages * page_size, width, itemsize
                    )
                    assert est <= limit(est), (page_size, width, itemsize)

    for m, n, kw in [(1, 1, 1), (8192, 512, 144), (100000, 4096, 16)]:
        est = blocks._binary_gemm_vmem_estimate(
            *blocks._default_binary_gemm_blocks(m, n, kw)
        )
        assert est <= limit(est), (m, n, kw)

    for wo, ciw, co, kw in [(7, 16, 512, 3), (56, 2, 64, 3), (224, 144, 512, 7)]:
        bn = blocks._default_binary_conv_block_n(wo, ciw, co)
        est = blocks._binary_conv_vmem_estimate(wo, wo + kw - 1, ciw, kw, bn)
        assert est <= limit(est), (wo, ciw, co)

    for k, itemsize in [(32, 4), (2304, 2), (9216, 2), (65536, 4)]:
        rows = blocks._default_pack_rows_block(k, itemsize)
        est = blocks._pack_rows_vmem_estimate(rows, k, itemsize)
        assert est <= limit(est), (k, itemsize)

    for h, w, c, itemsize in [(7, 9, 64, 1), (32, 32, 512, 4), (56, 56, 64, 2)]:
        est = blocks._resid_vmem_estimate(
            *blocks._resid_blocks(h, w, c, itemsize), c, itemsize
        )
        assert est <= limit(est), (h, w, c)
