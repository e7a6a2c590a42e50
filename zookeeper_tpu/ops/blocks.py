"""Shared VMEM-aware auto block policies for the Pallas kernels.

Every kernel family in the repo sizes its grid blocks the same way: pick
the LARGEST aligned candidate whose working set fits a VMEM budget and
whose padding waste stays bounded, then let explicit caller overrides
pass through untouched. This module is the single home of those
policies: ``_default_flash_blocks`` (flash attention),
``_pool_decode_block_pages`` (the pool decode kernel),
``_resid_blocks`` (1-bit residual pack/unpack) and the binary
xnor-popcount GEMM / conv-as-gemm policies of docs/DESIGN.md §21;
attention.py and binary_compute.py re-export the ones they use.

Pure shape arithmetic only: nothing here imports jax, so the policies
are usable from tests and tools without pulling in a backend.
"""

__all__ = [
    "vmem_limit_bytes",
    "_FLASH_VMEM_BUDGET",
    "_RESID_BLOCK_BYTES",
    "_BINARY_GEMM_VMEM_BUDGET",
    "_BINARY_CONV_VMEM_BUDGET",
    "_BINARY_PACK_BLOCK_BYTES",
    "_PACK_CHUNK",
    "_PACKED_WEIGHT_SCRATCH_BUDGET",
    "_round_up",
    "_divisor_at_most",
    "_flash_bwd_vmem_estimate",
    "_default_flash_blocks",
    "_pool_decode_vmem_estimate",
    "_POOL_BLOCK_BYTES",
    "_pool_decode_block_pages",
    "_resid_blocks",
    "_resid_vmem_estimate",
    "_binary_conv_vmem_estimate",
    "_pack_rows_vmem_estimate",
    "_packed_weight_vmem_estimate",
    "_binary_gemm_vmem_estimate",
    "_default_binary_gemm_blocks",
    "_default_binary_conv_block_n",
    "_default_pack_rows_block",
]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _divisor_at_most(n: int, cap: int) -> int:
    for d in range(max(1, min(cap, n)), 0, -1):
        if n % d == 0:
            return d
    return 1


# -- the scoped-VMEM rule ---------------------------------------------------

_MIB = 1024 * 1024

#: Mosaic's default scoped-VMEM limit on a v5e core. A pallas_call that
#: passes no limit is held to it: the v5e compiler refused the flash
#: backward at head_dim 256 fp32 with "Scoped allocation with size
#: 21.96M and limit 16.00M exceeded scoped vmem limit".
_VMEM_DEFAULT_LIMIT = 16 * _MIB

#: The largest limit any call in the package requests: three quarters
#: of the v5e core's 128 MiB, the rest left to XLA's own fusions.
_VMEM_LIMIT_CAP = 96 * _MIB


def vmem_limit_bytes(estimate: int) -> int:
    """The ``vmem_limit_bytes`` every pallas_call in the package hands
    to Mosaic, sized from the SAME per-grid-step estimate its block
    policy budgets with: half again the estimate (the estimates count
    tiles and named intermediates, not compiler temporaries), never
    below Mosaic's own default and never above ``_VMEM_LIMIT_CAP``. The
    policies budget against ``_FLASH_VMEM_BUDGET`` = cap / 1.5, so a
    block a policy admits always gets a limit above its estimate;
    explicit caller blocks past the cap meet Mosaic's error."""
    want = _round_up(int(estimate) * 3 // 2, _MIB)
    return min(_VMEM_LIMIT_CAP, max(_VMEM_DEFAULT_LIMIT, want))


# -- flash attention (forward/backward + pool kernels) ----------------------

#: VMEM the auto flash-block policy budgets for one backward grid step
#: (bytes). The backward kernels are the binding residency: three
#: (block_q, block_k) fp32 intermediates (scores, P, dS) plus the
#: double-buffered (block, head_dim) input tiles and fp32 accumulators.
#: 64 MiB is the largest estimate ``vmem_limit_bytes`` can still give
#: its half-again headroom under ``_VMEM_LIMIT_CAP``; it keeps block
#: 1024 at head_dim 64 (~14 MiB) in and demotes only extreme head dims.
_FLASH_VMEM_BUDGET = _VMEM_LIMIT_CAP * 2 // 3


def _flash_bwd_vmem_estimate(block_q, block_k, head_dim, itemsize):
    """Rough bytes one backward grid step keeps resident in VMEM: the
    three fp32 (bq, bk) intermediates + six (block, d) input tiles at
    the operand dtype, double-buffered by the Mosaic pipeline, + two
    fp32 (block, d) accumulators."""
    blk = max(block_q, block_k)
    intermediates = 3 * block_q * block_k * 4
    tiles = 2 * 6 * blk * head_dim * itemsize
    accumulators = 2 * blk * head_dim * 4
    return intermediates + tiles + accumulators


def _default_flash_blocks(s, block_q, block_k, head_dim=None, itemsize=4):
    """Auto block size: the LARGEST aligned candidate whose padding
    waste stays under 1/8 of the sequence AND whose backward working
    set fits the VMEM budget. Large blocks amortize the sequential
    grid iteration (the sweep winner at every measured power-of-two
    length — ``git show 34de816:sweep_r07/flash_bwd_timing.py``: 22.7
    -> 5.26 ms/step at s=8192 going 128 -> 1024), but a big block on an
    awkward length would round the padded sequence up to the block
    multiple (s=1100 at block 1024 pads to 2048 — 86% wasted rows), so
    awkward lengths
    fall back toward 128; and at head dims well above 64 the backward's
    (block, d) tiles grow until a 1024 block exceeds VMEM — a loud
    Mosaic compile failure if selected, so ``head_dim``-aware candidates
    demote to the largest block that fits (``_flash_bwd_vmem_estimate``
    against ``_FLASH_VMEM_BUDGET``). ``head_dim=None`` skips the VMEM
    filter (padding-only policy, the pre-head_dim behavior); explicit
    ``block_q``/``block_k`` always pass through untouched. Sequences at
    or below a block are a single tile (clamped 16-aligned by
    ``_flash_dims``)."""
    if block_q is None or block_k is None:
        auto = 128
        for blk in (1024, 512, 256, 128):
            pad = -(-s // blk) * blk - s
            if pad * 8 > s:
                continue
            if (
                head_dim is not None
                and blk > 128
                and _flash_bwd_vmem_estimate(blk, blk, head_dim, itemsize)
                > _FLASH_VMEM_BUDGET
            ):
                continue
            auto = blk
            break
        if block_q is None:
            block_q = auto
        if block_k is None:
            block_k = auto
    return block_q, block_k


# -- pool decode attention -------------------------------------------------


def _pool_decode_vmem_estimate(block_rows, row_width, itemsize):
    """Rough bytes one pool-kernel work item keeps resident: the two
    buffers of ``block_rows`` K and V rows at the pool dtype (the one
    computed on and the one the next item's pages land in), the fp32
    intermediates of one 128-key sub-block of one 128-lane column, and
    the three lane-dense accumulators."""
    tiles = 2 * 2 * block_rows * row_width * itemsize
    intermediates = 8 * min(block_rows, 128) * 128 * 4
    accumulators = 3 * row_width * 4
    return tiles + intermediates + accumulators


#: Bytes of one pool's pages a work item of the pool decode kernel
#: fetches into one buffer: the block derivation's constant. A few
#: hundred KB keeps a work item's fixed cost (two pipelined operands,
#: the copies' wait, the softmax state's read-modify-write) small beside
#: its copies, and two buffers of K and V far inside the scoped VMEM.
#: Measured on the v5e at 256 KB, 512 KB and 1 MB (PERF.md, PR 27 and
#: PR 28: ``mellum2_8l``'s full layer 0.73, 0.56, 0.58 ms a call).
_POOL_BLOCK_BYTES = 512 * 1024


def _pool_decode_block_pages(page_size, row_width, itemsize, span):
    """Pages one work item of the pool decode kernel fetches: as many
    as ``_POOL_BLOCK_BYTES`` hold, in whole 128-key sub-blocks (the
    kernel's arithmetic runs 128 keys at a time) and never fewer than
    two of them (wide rows: a work item's fixed cost once every 256
    keys at the most); never more than the ``span`` of pages a slot's
    band can touch; inside the VMEM budget."""
    sub = max(1, 128 // page_size)
    pages = _POOL_BLOCK_BYTES // (page_size * row_width * itemsize)
    pages = max(pages - pages % sub, 2 * sub)
    pages = min(pages, max(1, span))
    while pages > 1 and _pool_decode_vmem_estimate(
        pages * page_size, row_width, itemsize
    ) > _FLASH_VMEM_BUDGET:
        pages //= 2
    return int(pages)


# -- 1-bit residual pack/unpack ---------------------------------------------

#: VMEM budget per block (input side) for the residual kernels.
_RESID_BLOCK_BYTES = 2 * 1024 * 1024


def _resid_vmem_estimate(bh, bw, c, itemsize):
    """Rough bytes one residual-kernel grid step keeps resident: the
    32-deep float block on the input AND the output side (the fused
    mask-multiply has both) plus the word block, double-buffered."""
    deep = 32 * bh * bw * c * itemsize
    return 2 * (2 * deep + bh * bw * c * 4)


def _resid_blocks(h: int, w: int, c: int, itemsize: int):
    """(bh, bw): spatial block dims dividing (h, w) with the 32-deep
    input block inside the VMEM budget."""
    per_row = 32 * c * itemsize
    bw = _divisor_at_most(w, max(1, _RESID_BLOCK_BYTES // per_row))
    bh = _divisor_at_most(h, max(1, _RESID_BLOCK_BYTES // (per_row * bw)))
    return bh, bw


# -- binary xnor-popcount kernels (docs/DESIGN.md §21) ----------------------

#: VMEM budget for one fused xnor GEMM grid step. The binding residency
#: is the [block_kw, block_m, block_n] int32 xor intermediate (the VPU
#: popcount reduces it immediately, but Mosaic materializes the
#: broadcast); 8 MiB keeps the default 16x128x128 step (~1 MiB) and a
#: 512x128 block comfortably in while leaving headroom for the
#: double-buffered word tiles on 16 MiB-class parts.
_BINARY_GEMM_VMEM_BUDGET = 8 * 1024 * 1024

#: VMEM budget for the conv-as-gemm xor intermediate
#: ([wo, ciw, block_n] int32 per kw tap). Tighter than the GEMM budget
#: because the full output row stays resident in scratch as well.
_BINARY_CONV_VMEM_BUDGET = 4 * 1024 * 1024

#: Input-side VMEM budget per sign+pack block (same figure as the
#: residual kernels — both are streaming 1-bit compressors).
_BINARY_PACK_BLOCK_BYTES = _RESID_BLOCK_BYTES


def _binary_gemm_vmem_estimate(block_m, block_n, block_kw):
    """Rough bytes one fused xnor-GEMM grid step keeps resident: the
    int32 xor broadcast, the double-buffered packed word tiles, the
    int32 mismatch accumulator, and the fp32 output block."""
    intermediate = block_kw * block_m * block_n * 4
    tiles = 2 * block_kw * (block_m + block_n) * 4
    accumulators = 2 * block_m * block_n * 4
    return intermediate + tiles + accumulators


def _default_binary_gemm_blocks(m, n, kw):
    """Auto blocks for the fused xnor-popcount GEMM: start from the
    Mosaic-legal floor (128x128 output block, ``_MXU_WORDS``-deep word
    axis) and promote each output dim to the largest candidate whose
    padding waste stays under 1/8 of the axis and whose working set
    fits the budget — the ``_default_flash_blocks`` discipline on a
    two-dim output grid. The word axis is never promoted past 16: K is
    the streamed (innermost, revisiting-output) grid dim, so deeper
    blocks only grow the xor intermediate without saving HBM reads."""
    block_kw = 16 if kw >= 16 else 8
    block_m, block_n = 128, 128
    for blk in (512, 256):
        if (-(-m // blk) * blk - m) * 8 > max(m, 1):
            continue
        if _binary_gemm_vmem_estimate(blk, block_n, block_kw) \
                > _BINARY_GEMM_VMEM_BUDGET:
            continue
        block_m = blk
        break
    for blk in (512, 256):
        if (-(-n // blk) * blk - n) * 8 > max(n, 1):
            continue
        if _binary_gemm_vmem_estimate(block_m, blk, block_kw) \
                > _BINARY_GEMM_VMEM_BUDGET:
            continue
        block_n = blk
        break
    return block_m, block_n, block_kw


def _default_binary_conv_block_n(wo, ciw, co):
    """Output-channel block for the conv-as-gemm kernel: the largest
    multiple of 128 (capped at 512 / the padded channel count) whose
    per-tap xor intermediate ``[wo, ciw, block_n]`` fits the conv
    budget, demoted by halving — never below the 128-lane floor."""
    bn = min(512, _round_up(co, 128))
    while bn > 128 and wo * ciw * bn * 4 > _BINARY_CONV_VMEM_BUDGET:
        bn //= 2
    return bn


def _binary_conv_vmem_estimate(wo, wp, ciw, kw, block_n):
    """Rough bytes one conv-as-gemm grid step keeps resident: the per-tap
    xor broadcast and its popcount copy, the double-buffered packed row
    (its few words pad to a 128-lane tile), weight and output blocks,
    and the int32 accumulator."""
    intermediate = 2 * wo * ciw * block_n * 4
    tiles = 2 * (wp * _round_up(ciw, 128) + kw * ciw * block_n) * 4
    accumulators = 3 * wo * block_n * 4
    return intermediate + tiles + accumulators


#: Input lanes one sign+pack MXU contraction covers (128 output words).
_PACK_CHUNK = 4096


def _pack_rows_vmem_estimate(block_m, k, itemsize):
    """Rough bytes one sign+pack grid step keeps resident: the
    double-buffered float block, its fp32 widening and bf16 sign bits,
    the two resident selector matrices and the word block."""
    kc = min(k, _PACK_CHUNK)
    block = block_m * k
    selectors = 2 * 2 * kc * _round_up(kc // 32, 128) * 2
    words = 2 * block_m * _round_up(k // 32, 128) * 4
    return 2 * block * itemsize + block * (4 + 2) + selectors + 2 * words


#: VMEM the packed-weight MXU GEMM gives one n column's unpacked int8
#: weight slabs; past it the kernel decodes every step instead.
_PACKED_WEIGHT_SCRATCH_BUDGET = 4 * 1024 * 1024


def _packed_weight_vmem_estimate(block_m, block_n, block_kw, slots):
    """Rough bytes one packed-weight GEMM grid step keeps resident: the
    unpacked int8 slab scratch, the [block_kw, 32, block_n] bit-decode
    intermediates, and the double-buffered A / packed-B / output
    blocks."""
    bk = block_kw * 32
    scratch = slots * bk * block_n
    decode = 3 * bk * block_n * 4
    tiles = 2 * (block_m * bk + block_kw * block_n * 4 + block_m * block_n * 4)
    return scratch + decode + tiles


def _default_pack_rows_block(k, itemsize=4):
    """Row block for the fused sign+pack kernel: the input block is
    ``[block_m, k]`` (full packed axis per step), so rows are sized to
    the pack budget and floored/aligned to 32 — a multiple of every
    dtype's sublane tile (fp32 8, bf16 16, int8 32), capped at 256
    because the kernel is bandwidth-bound past one VPU-saturating
    block."""
    rows = _BINARY_PACK_BLOCK_BYTES // max(1, k * itemsize)
    return max(32, min(256, rows // 32 * 32))
