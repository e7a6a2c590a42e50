"""Binary compute paths: bit-packing, Pallas packed kernels, int8 MXU.

The TPU-native answer to larq-compute-engine's native binary kernels
(SURVEY.md §2.4). Executable paths for a binary (+-1 x +-1) matmul/conv,
chosen by what the hardware rewards:

1. **float/bf16 MXU** (default): XLA's conv/matmul on +-1.0 values — on
   TPU the MXU is so much faster than the VPU that this is already the
   best *training* path.
2. **int8 MXU** (``int8_matmul``/``int8_conv``): +-1 as int8 with int32
   accumulation — MXU int8 peak is 2x bf16, same accuracy (values exactly
   representable).
3. **Packed-weight MXU Pallas kernel** (``packed_weight_matmul``): weights
   live bit-packed in HBM (32x smaller), each tile is unpacked to int8
   inside VMEM, and the contraction still runs on the MXU. This is the
   TPU-first redesign of LCE's bit-packed kernels: in the HBM-bound regime
   (small-batch inference, where weight reads dominate) it cuts weight
   bandwidth 32x *without* giving up the systolic array. Bit-exact vs the
   float path (0 and +-1 are exact in int8/int32).
4. **XNOR-popcount VPU Pallas kernel** (``xnor_matmul``): both operands
   bit-packed, ``out = K - 2*popcount(a XOR b)`` on the VPU over int32
   lanes. The faithful LCE-style bit-serial kernel — 32x compression on
   BOTH operands; loses to the MXU paths when FLOP-bound (BASELINE.md
   measures the crossover). K-tiled with an in-output accumulator, so
   VMEM stays bounded at any K.

Convolutions decompose into per-tap GEMMs (``sum over (dy,dx) of
shifted_x @ W[dy,dx]``) instead of materializing im2col patches: a 3x3
im2col would write 9x the activation bytes to HBM, which is exactly the
traffic the packed path is trying to save.

Gradient story (SURVEY.md §7 "hard parts"): every binary conv/matmul op
here equals the float conv on its +-1/0 domain, so each gets a
``jax.custom_vjp`` whose backward is the float conv's VJP on the saved
quantized operands — STE quantizer gradients compose outside, and the ops
stay shard-transparent under pjit.
"""

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zookeeper_tpu.ops.attention import _mosaic_params
from zookeeper_tpu.ops.blocks import (  # noqa: F401  (re-exports)
    _PACK_CHUNK,
    _PACKED_WEIGHT_SCRATCH_BUDGET,
    _RESID_BLOCK_BYTES,
    _binary_conv_vmem_estimate,
    _binary_gemm_vmem_estimate,
    _default_binary_conv_block_n,
    _default_binary_gemm_blocks,
    _default_pack_rows_block,
    _divisor_at_most,
    _pack_rows_vmem_estimate,
    _packed_weight_vmem_estimate,
    _resid_blocks,
    _resid_vmem_estimate,
    _round_up,
)

Array = jax.Array

_MXU_WORDS = 16  # K-words per grid step in packed kernels (512 binary K).


# -- bit packing ------------------------------------------------------------


def pack_bits(x: Array, axis: int = -1) -> Array:
    """Pack the sign bits of ``x`` along ``axis`` into int32 words.

    bit=1 encodes x>=0 (+1), bit=0 encodes x<0 (-1); 32 values per lane,
    little-endian within the word. The packed axis length must be a
    multiple of 32 (pad with +1s beforehand; symmetric padding cancels in
    the popcount identity, zero-activation padding cancels in the MXU
    path).
    """
    x = jnp.moveaxis(x, axis, -1)
    k = x.shape[-1]
    if k % 32 != 0:
        raise ValueError(f"Packed axis must be a multiple of 32, got {k}.")
    bits = (x >= 0).astype(jnp.uint32)
    bits = bits.reshape(*x.shape[:-1], k // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(words.astype(jnp.int32), -1, axis)


def unpack_bits(packed: Array, k: int, axis: int = -1) -> Array:
    """Inverse of :func:`pack_bits`: int32 words -> +-1.0 float32."""
    words = jnp.moveaxis(packed, axis, -1).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    values = bits.astype(jnp.float32) * 2.0 - 1.0
    values = values.reshape(*words.shape[:-1], words.shape[-1] * 32)[..., :k]
    return jnp.moveaxis(values, -1, axis)


# _round_up and the block policies live in ops/blocks.py (shared with
# the flash/decode/pool kernels — docs/DESIGN.md §21); imported at the
# top of this module so historical import sites keep working.


# -- batch-packed 1-bit residual kernels (Pallas) ---------------------------
#
# The residual-residency levers (pack_residuals / ste_sign_packed) must
# not COST bandwidth. Two measured dead ends on the way here
# (BASELINE.md round 6):
#
# - jnp 32-way bit pack/unpack materializes [..., 32]-shaped int32
#   intermediates — 4 bytes per BIT, 32x more traffic than the tensor
#   being compressed (north-star step 21.0 -> 37.2 ms);
# - Pallas kernels over a FLATTENED [rows, 4096] view forced XLA to
#   relayout every residual in and out of the flat shape: NHWC tensors
#   are (8, 128)-tiled on the trailing dims, so reshape(-1) is a real
#   copy, and "data formatting" alone cost 21 ms/step (step 49.2 ms).
#
# These kernels therefore pack along the BATCH dimension on the NATIVE
# 4-D layout: batch is the outermost, untiled dim, so no reshape or
# relayout exists anywhere on the path; word [g, h, w, c] takes bit b
# from x[32g + b, h, w, c] — 32 unrolled elementwise VPU ops per block
# over [bh, bw, C] tiles, traffic = one read of the source + one
# 1/32-size write (pack), or the reverse (unpack). The layout is an
# internal storage convention (only these kernels' inverse pairs read
# it), not the pack_bits wire format. Batch pads to a multiple of 32
# (tiny at training batch sizes; correctness-only for small test
# batches).

# _RESID_BLOCK_BYTES (the per-block VMEM budget) moved to ops/blocks.py.


def _resid_interpret(interpret) -> bool:
    """Resolve the interpret flag: explicit wins (the layer's
    ``pallas_interpret`` convention); ``None`` auto-selects interpret
    off-TPU so the quantizer-level entry points (which have no flag to
    thread) still run everywhere."""
    if interpret is not None:
        return interpret
    import jax as _jax

    return _jax.default_backend() != "tpu"


def _to_4d_shape(shape):
    """Normalize a residual shape to [B, H, W, C] with LAYOUT-PRESERVING
    reshapes only: unit dims inserted before the trailing (tiled) dims,
    or leading (untiled) dims merged. Pure shape arithmetic — pack and
    unpack recompute it identically from the original shape."""
    if len(shape) == 4:
        return tuple(shape)
    if len(shape) == 2:  # [B, K] (dense residuals)
        return (shape[0], 1, 1, shape[1])
    if len(shape) == 3:  # [B, W, C] (1-D conv residuals)
        return (shape[0], 1, shape[1], shape[2])
    if len(shape) > 4:  # [B, *spatial, C]: merge leading spatial dims
        from math import prod

        return (shape[0], prod(shape[1:-2]), shape[-2], shape[-1])
    raise ValueError(
        f"1-bit residual packing needs a batched tensor, got shape {shape}."
    )


# _divisor_at_most / _resid_blocks moved to ops/blocks.py.


def _pack_resid_kernel(x_ref, out_ref, *, mask_mode: bool):
    acc = jnp.zeros(out_ref.shape, jnp.int32)
    for b in range(32):
        # fp32 compare: Mosaic has no bf16 vector cmpf on this target
        # (the widen is a free vreg conversion).
        chunk = x_ref[b].astype(jnp.float32)
        if mask_mode:
            bit = jnp.abs(chunk) <= 1.0  # the ste_sign pass-through mask
        else:
            bit = chunk >= 0  # +-1 sign bit
        acc = acc | (bit[None].astype(jnp.int32) << b)
    out_ref[:] = acc


def _unpack_pm1_resid_kernel(w_ref, out_ref, *, dtype):
    w = w_ref[0]
    for b in range(32):
        bit = (w >> b) & 1
        # Arithmetic +-1 decode (b+b-1): no vector integer multiply.
        out_ref[b] = (bit + bit - 1).astype(dtype)


def _mask_mul_resid_kernel(g_ref, w_ref, out_ref):
    w = w_ref[0]
    for b in range(32):
        bit = ((w >> b) & 1).astype(g_ref.dtype)
        out_ref[b] = g_ref[b] * bit


def _pad_batch(x4: Array, pad_value) -> Array:
    b = x4.shape[0]
    bp = _round_up(b, 32)
    if bp == b:
        return x4
    return jnp.pad(
        x4,
        ((0, bp - b), (0, 0), (0, 0), (0, 0)),
        constant_values=pad_value,
    )


def pack_resid(
    x: Array, *, mask_mode: bool = False, interpret: bool = None
) -> Array:
    """Pack a tensor to 1 bit/value along the BATCH dim: the sign bit
    (``mask_mode=False``, exact for strictly-+-1 tensors) or the STE
    pass-through bit ``|x| <= 1`` (``mask_mode=True``). Returns
    [ceil(B/32), H, W, C] int32 words (shape normalized per
    :func:`_to_4d_shape`)."""
    x4 = _pad_batch(x.reshape(_to_4d_shape(x.shape)), 1.0)
    bp, h, w, c = x4.shape
    bh, bw = _resid_blocks(h, w, c, jnp.dtype(x.dtype).itemsize)
    out = pl.pallas_call(
        partial(_pack_resid_kernel, mask_mode=mask_mode),
        out_shape=jax.ShapeDtypeStruct((bp // 32, h, w, c), jnp.int32),
        grid=(bp // 32, h // bh, w // bw),
        in_specs=[
            pl.BlockSpec(
                (32, bh, bw, c),
                lambda i, j, k: (i, j, k, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, bh, bw, c),
            lambda i, j, k: (i, j, k, 0),
            memory_space=pltpu.VMEM,
        ),
        compiler_params=_mosaic_params(
            _resid_vmem_estimate(bh, bw, c, jnp.dtype(x.dtype).itemsize)
        ),
        interpret=_resid_interpret(interpret),
    )(x4)
    return out


def unpack_resid_pm1(words: Array, shape, dtype,
                     interpret: bool = None) -> Array:
    """Inverse of sign-mode :func:`pack_resid`: +-1 values of ``shape``
    in ``dtype`` (bit-exact: +-1 is representable in every float type)."""
    b4, h, w, c = _to_4d_shape(shape)
    bp = _round_up(b4, 32)
    bh, bw = _resid_blocks(h, w, c, jnp.dtype(dtype).itemsize)
    out = pl.pallas_call(
        partial(_unpack_pm1_resid_kernel, dtype=dtype),
        out_shape=jax.ShapeDtypeStruct((bp, h, w, c), dtype),
        grid=(bp // 32, h // bh, w // bw),
        in_specs=[
            pl.BlockSpec(
                (1, bh, bw, c),
                lambda i, j, k: (i, j, k, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (32, bh, bw, c),
            lambda i, j, k: (i, j, k, 0),
            memory_space=pltpu.VMEM,
        ),
        compiler_params=_mosaic_params(
            _resid_vmem_estimate(bh, bw, c, jnp.dtype(dtype).itemsize)
        ),
        interpret=_resid_interpret(interpret),
    )(words)
    return out[:b4].reshape(shape)


def mask_mul_resid(g: Array, words: Array, interpret: bool = None) -> Array:
    """``g * mask`` where ``mask`` is a mask-mode :func:`pack_resid` of a
    tensor shaped like ``g`` — the fused unpack-multiply for the
    ste_sign backward (one read of g + 1/32 of a read for the mask, vs
    a full re-read of the fp input in the unpacked baseline)."""
    g4 = _pad_batch(g.reshape(_to_4d_shape(g.shape)), 0.0)
    bp, h, w, c = g4.shape
    bh, bw = _resid_blocks(h, w, c, jnp.dtype(g.dtype).itemsize)
    out = pl.pallas_call(
        _mask_mul_resid_kernel,
        out_shape=jax.ShapeDtypeStruct((bp, h, w, c), g.dtype),
        grid=(bp // 32, h // bh, w // bw),
        in_specs=[
            pl.BlockSpec(
                (32, bh, bw, c),
                lambda i, j, k: (i, j, k, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bh, bw, c),
                lambda i, j, k: (i, j, k, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (32, bh, bw, c),
            lambda i, j, k: (i, j, k, 0),
            memory_space=pltpu.VMEM,
        ),
        compiler_params=_mosaic_params(
            _resid_vmem_estimate(bh, bw, c, jnp.dtype(g.dtype).itemsize)
        ),
        interpret=_resid_interpret(interpret),
    )(g4, words)
    # Batch is dim 0 in both the original and the normalized shape.
    return out[: g.shape[0]].reshape(g.shape)


# -- XNOR-popcount VPU Pallas GEMM (both operands packed) -------------------


def _popcount32(v: Array) -> Array:
    """Parallel bit-count of int32 lanes (VPU integer ops only).

    Shift-add finish instead of the classic ``* 0x01010101 >> 24`` byte
    sum: Mosaic cannot legalize the vectorized integer multiply."""
    v = v.astype(jnp.uint32)
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    v = v + (v >> 8)
    v = v + (v >> 16)
    return (v & jnp.uint32(0x3F)).astype(jnp.int32)


def _xnor_kernel(a_ref, b_ref, out_ref, *, k_true: int):
    """One (m, n, k) grid step: accumulate XOR-popcount mismatches for a
    K-slab into the output block, finalizing ``K - 2*mismatches`` on the
    last K step. VMEM high-water: the [bkw, bm, bn] xor intermediate —
    bounded by the K tile, not the full K (the round-1 kernel kept full K
    per block and overflowed VMEM at QuickNet's K=4608).

    Both operands arrive K-words-major ([bkw, bm] / [bkw, bn]): Mosaic
    requires lane (last) dims of 128 (or full-array), which the small
    packed-word axis cannot satisfy when K-tiled — so the word axis lives
    in sublanes and bm/bn take the lanes."""
    k = pl.program_id(2)
    a = a_ref[:]  # [bkw, bm] int32 (A packed along K, transposed)
    b = b_ref[:]  # [bkw, bn] int32
    x = jnp.bitwise_xor(a[:, :, None], b[:, None, :])  # [bkw, bm, bn]
    mismatches = jnp.sum(_popcount32(x), axis=0)  # [bm, bn] int32

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += mismatches

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        # k_true - 2*mismatches, multiply-free (Mosaic has no vector
        # integer multiply).
        acc = out_ref[:]
        out_ref[:] = k_true - (acc + acc)


@partial(
    jax.jit,
    static_argnames=("k_true", "block_m", "block_n", "block_kw", "interpret"),
)
def xnor_matmul_packed(
    a_packed: Array,
    b_packed: Array,
    *,
    k_true: int,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = _MXU_WORDS,
    interpret: bool = False,
) -> Array:
    """Binary GEMM on pre-packed operands, K-tiled.

    ``a_packed``: [M, Kw] int32 (packed along K); ``b_packed``: [Kw, N]
    int32 (packed along K, i.e. pack_bits(B, axis=0)). Returns [M, N]
    int32 equal to ``sign(A) @ sign(B)`` counted over ``k_true`` terms.
    K-padding is harmless when both operands pad with the SAME bit value:
    XOR of equal bits contributes no mismatches.
    """
    m, kw = a_packed.shape
    kw2, n = b_packed.shape
    if kw != kw2:
        raise ValueError(f"Packed K mismatch: {kw} vs {kw2}.")
    if not interpret:
        # Mosaic lane/sublane legality (see kernel docstring): lanes (bm,
        # bn) in multiples of 128, word-axis sublanes in multiples of 8 —
        # unless the block covers the full axis.
        block_m = _round_up(block_m, 128)
        block_n = _round_up(block_n, 128)
        block_kw = _round_up(block_kw, 8)
    block_m = min(block_m, _round_up(m, 8))
    block_n = min(block_n, _round_up(n, 128))
    block_kw = min(block_kw, kw)
    mp = _round_up(m, block_m)
    np_ = _round_up(n, block_n)
    kwp = _round_up(kw, block_kw)
    # Row/col padding produces garbage rows sliced away below; K-word
    # padding pads BOTH operands with zero-words (equal bits, no
    # mismatches). A goes in K-words-major (see kernel docstring).
    a_pad = jnp.pad(a_packed.T, ((0, kwp - kw), (0, mp - m)))
    b_pad = jnp.pad(b_packed, ((0, kwp - kw), (0, np_ - n)))

    out = pl.pallas_call(
        partial(_xnor_kernel, k_true=k_true),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        grid=(mp // block_m, np_ // block_n, kwp // block_kw),
        in_specs=[
            pl.BlockSpec(
                (block_kw, block_m),
                lambda i, j, k: (k, i),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (block_kw, block_n),
                lambda i, j, k: (k, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
        ),
        compiler_params=_mosaic_params(
            _binary_gemm_vmem_estimate(block_m, block_n, block_kw)
        ),
        interpret=interpret,
    )(a_pad, b_pad)
    return out[:m, :n]


def xnor_matmul(
    a: Array,
    b: Array,
    *,
    interpret: bool = False,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = _MXU_WORDS,
) -> Array:
    """Binary GEMM of float +-1 operands via bit-packing: [M,K] @ [K,N].

    Packs, runs the VPU popcount kernel, returns float32 (exact
    integers).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"Inner dims mismatch: {k} vs {k2}.")
    k_pad = _round_up(k, 32)
    if k_pad != k:
        # Symmetric +1 padding cancels in K - 2*popcount(xor).
        a = jnp.pad(a, ((0, 0), (0, k_pad - k)), constant_values=1.0)
        b = jnp.pad(b, ((0, k_pad - k), (0, 0)), constant_values=1.0)
    ap = pack_bits(a, axis=-1)
    bp = pack_bits(b, axis=0)
    # k_true stays the ORIGINAL K: the symmetric +1 padding produces
    # matching bits, i.e. zero mismatches, so K - 2*mismatches is exact.
    out = xnor_matmul_packed(
        ap, bp, k_true=k, block_m=block_m, block_n=block_n,
        block_kw=block_kw, interpret=interpret,
    )
    return out.astype(jnp.float32)


# -- fused binary kernels + flavor seam (docs/DESIGN.md §21) ----------------
#
# The paths above compose three XLA-visible stages around the popcount
# GEMM: a 32x-intermediate sign+pack of the activations (pack_bits), the
# kernel launch, and a separate fp32 scale pass over the int32 output.
# The §21 kernels collapse the pipeline: a Pallas sign+pack producer
# writes wire-format words straight from the float activations (one read
# of the source, one 1/32-size write), and the GEMM applies the
# k_true-correction AND the per-output-channel scale in its epilogue, so
# the int32 accumulator never round-trips through HBM. Selection happens
# behind the existing numerics contract via the same flavor seam as
# DecodeEngine.decode_attention: "auto" resolves to the fused kernels on
# TPU and the reference composition off-TPU; interpret mode is a
# numerics vehicle only (the CI certification path), never a perf claim.

#: Binary compute flavors (layer field ``binary_flavor``): "auto" picks
#: the fused Pallas path on TPU and the reference composition off-TPU;
#: explicit values force one side (the A/B lever for the bench leg and
#: the bit-identity certification).
BINARY_FLAVORS = ("auto", "pallas", "reference")


def resolve_binary_flavor(flavor: str) -> str:
    """Resolve a binary-compute flavor to "pallas" or "reference".

    Mirrors ``DecodeEngine.decode_attention``'s seam: "auto" is
    backend-keyed (fused kernels on TPU, reference composition
    elsewhere), explicit flavors pass through, anything else raises
    loudly — a typo must not silently change which kernels serve."""
    if flavor not in BINARY_FLAVORS:
        raise ValueError(
            f"binary_flavor must be one of {BINARY_FLAVORS}, got "
            f"{flavor!r}."
        )
    if flavor != "auto":
        return flavor
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _warn_pallas_fallback(what: str) -> None:
    """Explicit ``flavor="pallas"`` on a path with no fused kernel
    degrades to the reference composition with a warning — the decode
    seam's unsupported-geometry discipline, made audible because the
    caller asked for a specific flavor by name ("auto" degrades
    silently; it never promised the fused path)."""
    import warnings

    warnings.warn(
        f"binary_flavor='pallas' requested but {what} has no fused "
        "Pallas path; running the reference composition (numerics are "
        "identical).",
        stacklevel=3,
    )


def _pack_rows_kernel(x_ref, plo_ref, phi_ref, out_ref):
    """Fused sign+pack of one [bm, kw*32] float block into [bm, kw]
    int32 wire-format words (little-endian bit b of word t is
    ``x[:, 32t+b] >= 0`` — exactly :func:`pack_bits`).

    Gathering 32 adjacent lanes into one is a lane compaction, which
    Mosaic has no vector form for (a stride-32 lane load is refused for
    every dtype), so the compaction runs on the MXU: the 0/1 sign bits
    contract against two constant selector matrices whose row ``32t+b``
    holds ``2^(b % 16)`` in column ``t`` — ``plo`` for bits 0..15,
    ``phi`` for bits 16..31. Each output is a sum of distinct powers of
    two below 2^16, exact in the fp32 accumulator, so
    ``lo | (hi << 16)`` is the word bit for bit. K wider than
    ``_PACK_CHUNK`` lanes is walked in static chunks against the same
    selectors (they are block-diagonal). Traffic: one read of the float
    source, one 1/32-size write — no [..., 32]-shaped HBM intermediates
    (the round-6 lesson at the top of this file)."""
    k = x_ref.shape[1]
    kw = out_ref.shape[1]
    # fp32 compare: Mosaic has no bf16 vector cmpf on this target.
    bits = jnp.where(
        x_ref[:].astype(jnp.float32) >= 0, 1.0, 0.0
    ).astype(jnp.bfloat16)
    for c0 in range(0, k, _PACK_CHUNK):
        width = min(_PACK_CHUNK, k - c0)
        chunk = bits[:, c0 : c0 + width]
        lo = jnp.dot(
            chunk, plo_ref[:width], preferred_element_type=jnp.float32
        ).astype(jnp.int32)
        hi = jnp.dot(
            chunk, phi_ref[:width], preferred_element_type=jnp.float32
        ).astype(jnp.int32)
        w0 = c0 // 32
        words = min(kw - w0, _PACK_CHUNK // 32)
        out_ref[:, w0 : w0 + words] = (lo | (hi << 16))[:, :words]


def _pack_selectors(k: int):
    """The two [kc, 128-padded kc/32] bf16 selector matrices of
    :func:`_pack_rows_kernel` (``kc = min(k, _PACK_CHUNK)``)."""
    import numpy as np

    kc = min(k, _PACK_CHUNK)
    lanes = np.arange(kc)
    bit = lanes % 32
    out = []
    for half in (bit < 16, bit >= 16):
        sel = np.zeros((kc, _round_up(kc // 32, 128)), np.float32)
        sel[lanes[half], lanes[half] // 32] = 2.0 ** (bit[half] % 16)
        out.append(jnp.asarray(sel, jnp.bfloat16))
    return out


def pack_rows_packed(x: Array, *, interpret=None, block_m: int = None) -> Array:
    """Pallas sign+pack: [M, K] floats -> [M, K//32] int32 pack_bits
    words — the fused quantizer producer for the §21 GEMM consumers
    (``ste_sign``'s sign is the packed bit; the quantizer's scale rides
    the weight-side epilogue, so the ±1 floats never round-trip HBM).

    Bit-identical to ``pack_bits(x, axis=-1)`` by construction
    (including NaN -> bit 0 and ±0 -> bit 1: both lower to the same
    ``>= 0`` compare). K must be a multiple of 32; rows pad to the
    block multiple and slice away (garbage rows are computed but
    unread)."""
    m, k = x.shape
    if k % 32 != 0:
        raise ValueError(f"Packed axis must be a multiple of 32, got {k}.")
    kw = k // 32
    itemsize = jnp.dtype(x.dtype).itemsize
    if block_m is None:
        block_m = _default_pack_rows_block(k, itemsize)
    block_m = min(block_m, _round_up(m, 32))
    mp = _round_up(m, block_m)
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    plo, phi = _pack_selectors(k)
    sel_spec = pl.BlockSpec(plo.shape, lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _pack_rows_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, kw), jnp.int32),
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            sel_spec,
            sel_spec,
        ],
        out_specs=pl.BlockSpec((block_m, kw), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=_mosaic_params(
            _pack_rows_vmem_estimate(block_m, k, itemsize)
        ),
        interpret=_resid_interpret(interpret),
    )(x, plo, phi)
    return out[:m]


def _xnor_scaled_kernel(a_ref, b_ref, s_ref, out_ref, acc_ref, *,
                        k_true: int):
    """One (m, n, k) grid step of the fused-epilogue binary GEMM: the
    ``_xnor_kernel`` accumulation into int32 VMEM scratch, with the
    ``k_true``-correction AND the per-output-channel fp32 scale applied
    in the epilogue on the last K step — the int32 accumulator never
    leaves VMEM and no separate XLA scale pass runs over the output.

    Numerics (the §17-style documented-ULP statement, bound ZERO): the
    mismatch count is an exact integer, ``k_true - 2*acc`` stays exact
    in int32, the cast to fp32 is exact for any |dot| <= 2^24 (binary K
    never approaches it), and the single fp32 multiply by the scale is
    the SAME operation in the SAME order as the reference epilogue
    ``acc.astype(float32) * scale`` — so the fused output is
    bit-identical, not merely close."""
    k = pl.program_id(2)
    x = jnp.bitwise_xor(a_ref[:][:, :, None], b_ref[:][:, None, :])
    mismatches = jnp.sum(_popcount32(x), axis=0)  # [bm, bn] int32

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += mismatches

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        acc = acc_ref[:]
        dots = k_true - (acc + acc)  # multiply-free, exact int32
        out_ref[:] = dots.astype(jnp.float32) * s_ref[:]


@partial(
    jax.jit,
    static_argnames=("k_true", "block_m", "block_n", "block_kw", "interpret"),
)
def xnor_matmul_packed_scaled(
    a_packed: Array,
    b_packed: Array,
    scale: Array,
    *,
    k_true: int,
    block_m: int = None,
    block_n: int = None,
    block_kw: int = None,
    interpret: bool = False,
) -> Array:
    """Fused-epilogue binary GEMM: ``sign(A) @ sign(B) * scale`` in one
    kernel, fp32 out.

    Same operand contract as :func:`xnor_matmul_packed` (``a_packed``
    [M, Kw], ``b_packed`` [Kw, N], K-words packed, equal-bit K padding
    cancels) plus a per-output-channel ``scale`` [N] fp32. Blocks
    default to the shared :mod:`ops.blocks` policy; output is
    bit-identical to ``xnor_matmul_packed(...).astype(float32) *
    scale`` (see the kernel docstring for why the bound is zero)."""
    m, kw = a_packed.shape
    kw2, n = b_packed.shape
    if kw != kw2:
        raise ValueError(f"Packed K mismatch: {kw} vs {kw2}.")
    if scale.shape != (n,):
        raise ValueError(
            f"scale must be [{n}] (per output channel), got {scale.shape}."
        )
    auto_m, auto_n, auto_kw = _default_binary_gemm_blocks(m, n, kw)
    block_m = auto_m if block_m is None else block_m
    block_n = auto_n if block_n is None else block_n
    block_kw = auto_kw if block_kw is None else block_kw
    if not interpret:
        # Mosaic lane/sublane legality — same rules as xnor_matmul_packed.
        block_m = _round_up(block_m, 128)
        block_n = _round_up(block_n, 128)
        block_kw = _round_up(block_kw, 8)
    block_m = min(block_m, _round_up(m, 8))
    block_n = min(block_n, _round_up(n, 128))
    block_kw = min(block_kw, kw)
    mp = _round_up(m, block_m)
    np_ = _round_up(n, block_n)
    kwp = _round_up(kw, block_kw)
    a_pad = jnp.pad(a_packed.T, ((0, kwp - kw), (0, mp - m)))
    b_pad = jnp.pad(b_packed, ((0, kwp - kw), (0, np_ - n)))
    s_pad = jnp.pad(
        scale.astype(jnp.float32).reshape(1, n), ((0, 0), (0, np_ - n))
    )

    out = pl.pallas_call(
        partial(_xnor_scaled_kernel, k_true=k_true),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        grid=(mp // block_m, np_ // block_n, kwp // block_kw),
        in_specs=[
            pl.BlockSpec(
                (block_kw, block_m),
                lambda i, j, k: (k, i),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (block_kw, block_n),
                lambda i, j, k: (k, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_n),
                lambda i, j, k: (0, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=_mosaic_params(
            _binary_gemm_vmem_estimate(block_m, block_n, block_kw)
        ),
        interpret=interpret,
    )(a_pad, b_pad, s_pad)
    return out[:m, :n]


# -- Packed-weight MXU Pallas GEMM (weights packed, MXU contraction) --------


def _pw_kernel(a_ref, b_ref, out_ref, w_scratch, *, out_dtype,
               always_decode=False):
    """One (n, m, k) grid step: contract an A block against a +-1 int8
    weight slab held in VMEM scratch, accumulating into the output block.

    The HBM win: ``b_ref`` blocks arrive packed (32x fewer bytes than the
    int8 weights they encode); only the VMEM-resident tile is ever
    unpacked. The SCRATCH win (the round-2 "per-M-block unpack repeats"
    structural loss): the unpack runs only on the FIRST m iteration of
    each (n, k) — ``w_scratch`` holds every unpacked K-slab of the
    current n column, and the remaining m blocks reuse it straight from
    VMEM. Large-M GEMMs amortize the bit-decode across M/block_m blocks
    instead of paying it every time (measured: the decode dominated at
    M = spatial-positions shapes, BASELINE.md round 2)."""
    m = pl.program_id(1)
    k = pl.program_id(2)
    # ``always_decode`` (static): the fallback for K so large that one n
    # column's unpacked slabs exceed the scratch budget — decode every
    # step into the single scratch slot (slot index 0, since the scratch
    # then has one slot) instead of caching per k.
    slot = 0 if always_decode else k

    def _decode():
        bw = b_ref[:].astype(jnp.uint32)  # [bkw, bn] packed words
        shifts = jnp.arange(32, dtype=jnp.uint32)
        bits = (bw[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
        # [bkw, 32, bn] -> [bk, bn]; row r = word r//32, bit r%32 (pack
        # order). Pure arithmetic +-1 decode (b+b-1): Mosaic has no
        # vector integer multiply, and i1 select masks hit relayout
        # limits at this shape.
        bi = bits.astype(jnp.int32)
        w_scratch[slot] = (
            (bi + bi - 1).reshape(-1, bw.shape[-1]).astype(jnp.int8)
        )

    if always_decode:
        _decode()
    else:
        pl.when(m == 0)(_decode)

    a = a_ref[:]  # [bm, bk] int8 (+-1 or 0 from spatial padding)
    # Precision pinned: int8 contraction is exact at any precision, and
    # a global jax_default_matmul_precision="highest" would otherwise tag
    # this dot with an fp32 contract Mosaic cannot honor for int8.
    acc = jax.lax.dot_general(
        a,
        w_scratch[slot],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
        precision=jax.lax.Precision.DEFAULT,
    )

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += acc.astype(out_dtype)


@partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_kw", "interpret"),
)
def packed_weight_matmul(
    a: Array,
    b_packed: Array,
    *,
    block_m: int = 512,
    block_n: int = 512,
    block_kw: int = _MXU_WORDS,
    interpret: bool = False,
) -> Array:
    """GEMM with bit-packed weights: [M, K] (+-1/0 values) @ packed [Kw, N].

    ``a`` may contain zeros (conv zero-padding) — only the WEIGHTS are
    packed, so the result is bit-exact with the float GEMM against the
    unpacked +-1 weights. Returns int32 [M, N].

    Default blocks are 512x512 (capped to the problem below): measured on
    v5e, big blocks cut the grid-step count and amortize the weight
    decode (with the m==0 scratch reuse) — 391 -> ~110 us at the
    M=3136/K=4608/N=512 QuickNet section shape, 8.4 -> 5.2 us at M=784,
    batch-1 unchanged-to-better (BASELINE.md round 5). The unpacked-slab
    scratch costs K_pad x block_n bytes of VMEM; the call auto-lowers
    ``block_n`` to stay inside a ~4 MB budget and, for K so large that
    even block_n=128 exceeds it, falls back to decoding every step
    (the pre-scratch behavior) instead of failing Mosaic allocation.
    """
    m, k = a.shape
    kw, n = b_packed.shape
    if kw * 32 != _round_up(k, 32):
        raise ValueError(
            f"Packed weight K-words {kw} inconsistent with A's K {k}."
        )
    a8 = a.astype(jnp.int8)
    if not interpret:
        # Mosaic legality: int8 sublanes in multiples of 32, lanes in
        # multiples of 128 (the K-tile is a lane dim for A at
        # block_kw*32), unless a block covers its full axis.
        block_m = _round_up(block_m, 32)
        block_n = _round_up(block_n, 128)
        block_kw = _round_up(block_kw, 8)
    block_m = min(block_m, _round_up(m, 32))
    block_n = min(block_n, _round_up(n, 128))
    block_kw = min(block_kw, kw)
    # Scratch VMEM budget (~4 MB): one n column's unpacked slabs are
    # K_pad x block_n int8. Lower block_n first; if even 128 lanes
    # exceed the budget (K in the tens of thousands), keep a single-slot
    # scratch and decode every grid step (always_decode fallback).
    slab_rows = _round_up(kw, block_kw) * 32
    while (
        block_n > 128
        and slab_rows * block_n > _PACKED_WEIGHT_SCRATCH_BUDGET
    ):
        block_n //= 2
    always_decode = slab_rows * block_n > _PACKED_WEIGHT_SCRATCH_BUDGET
    mp = _round_up(m, block_m)
    np_ = _round_up(n, block_n)
    kwp = _round_up(kw, block_kw)
    # A pads K with ZEROS: whatever bits the padded weight words decode to
    # (+-1), 0 * (+-1) contributes nothing — exact.
    a_pad = jnp.pad(a8, ((0, mp - m), (0, kwp * 32 - k)))
    b_pad = jnp.pad(b_packed, ((0, kwp - kw), (0, np_ - n)))

    # Grid order (n, m, k): k innermost so each output block accumulates
    # consecutively; m middle so the per-(n, k) weight unpack (done on
    # m == 0 into scratch) is reused by every later m block of the same
    # n column.
    out = pl.pallas_call(
        partial(
            _pw_kernel, out_dtype=jnp.int32, always_decode=always_decode
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        grid=(np_ // block_n, mp // block_m, kwp // block_kw),
        in_specs=[
            pl.BlockSpec(
                (block_m, block_kw * 32),
                lambda j, i, k: (i, k),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (block_kw, block_n),
                lambda j, i, k: (k, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n), lambda j, i, k: (i, j), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM(
                (
                    1 if always_decode else kwp // block_kw,
                    block_kw * 32,
                    block_n,
                ),
                jnp.int8,
            )
        ],
        compiler_params=_mosaic_params(
            _packed_weight_vmem_estimate(
                block_m, block_n, block_kw,
                1 if always_decode else kwp // block_kw,
            )
        ),
        interpret=interpret,
    )(a_pad, b_pad)
    return out[:m, :n]


# -- packed conv kernels (weights pre-packed per tap) -----------------------


def pack_conv_kernel(q_kernel: Array) -> Tuple[Array, Array]:
    """Pack a quantized HWIO conv kernel for the binary conv paths.

    ``q_kernel`` [kh, kw, ci, co] must be ``sign x per-output-channel
    scale`` (what ``ste_sign``/``approx_sign`` [scale=1] and
    ``magnitude_aware_sign`` [scale=mean|w| per co] produce). Returns
    ``(packed [kh, kw, ceil(ci/32), co] int32, scale [co] float32)``:
    32x weight compression; the scale is re-applied to the integer GEMM
    output.
    """
    kh, kw, ci, co = q_kernel.shape
    scale = jnp.max(jnp.abs(q_kernel), axis=(0, 1, 2)).astype(jnp.float32)
    # Guard all-zero channels (degenerate but possible pre-training).
    safe = jnp.where(scale > 0, scale, 1.0)
    signs = q_kernel / safe  # exactly +-1 by the quantizer contract
    ci_pad = _round_up(ci, 32)
    if ci_pad != ci:
        signs = jnp.pad(
            signs, ((0, 0), (0, 0), (0, ci_pad - ci), (0, 0)),
            constant_values=1.0,
        )
    packed = pack_bits(signs, axis=2)  # [kh, kw, ci_pad/32, co]
    return packed, scale


def _spatial_pad(
    x: Array, kh: int, kw: int, strides: Tuple[int, int], padding: str,
    pad_value: float,
) -> Tuple[Array, int, int]:
    """Pad NHWC input per XLA SAME/VALID semantics; returns (padded, Ho, Wo)."""
    _, h, w, _ = x.shape
    sh, sw = strides
    if padding == "VALID":
        ho = (h - kh) // sh + 1
        wo = (w - kw) // sw + 1
        return x, ho, wo
    if padding != "SAME":
        raise ValueError(f"Unsupported padding {padding!r} (SAME/VALID).")
    ho = -(-h // sh)
    wo = -(-w // sw)
    pad_h = max((ho - 1) * sh + kh - h, 0)
    pad_w = max((wo - 1) * sw + kw - w, 0)
    x = jnp.pad(
        x,
        ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
         (pad_w // 2, pad_w - pad_w // 2), (0, 0)),
        constant_values=pad_value,
    )
    return x, ho, wo


def _conv_gemm_kernel(x_ref, w_ref, s_ref, out_ref, acc_ref, *,
                      kw: int, sw: int, wo: int, ciw: int, k_true: int):
    """One (b, ho, n, kh) grid step of the §21 conv-as-gemm kernel.

    im2col happens in the INDEX MAP, not as a materialized patch tensor:
    the grid's innermost dim walks the kernel rows (dy), and the
    activation BlockSpec picks padded input row ``i*sh + dy`` directly
    (a block of size 1 makes the block index an element offset — the
    §17/§20 indexing trick). Inside the step the kw taps are unrolled
    static strided slices of the resident row, so one [Wp, ciw] word
    row feeds all horizontal taps and each packed weight block streams
    from HBM exactly once per (output row, channel block) — kh reads
    total, vs the kh*kw patch-matrix copies of an XLA im2col.

    Mismatches accumulate in int32 VMEM scratch across the dy steps;
    the last step applies the ``k_true``-correction and per-channel
    scale epilogue (same zero-ULP argument as
    :func:`_xnor_scaled_kernel`)."""
    dy = pl.program_id(3)
    xrow = x_ref[0, 0]  # [Wp, ciw] packed activation row (+1-padded)
    w = w_ref[0]  # [kw*ciw, bn] packed weights for kernel row dy

    @pl.when(dy == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    for dx in range(kw):
        xs = xrow[dx : dx + (wo - 1) * sw + 1 : sw]  # [wo, ciw]
        ws = w[dx * ciw : (dx + 1) * ciw]  # [ciw, bn]
        x = jnp.bitwise_xor(xs[:, :, None], ws[None, :, :])
        acc_ref[:] += jnp.sum(_popcount32(x), axis=1)  # [wo, bn]

    @pl.when(dy == pl.num_programs(3) - 1)
    def _():
        acc = acc_ref[:]
        dots = k_true - (acc + acc)  # multiply-free, exact int32
        out_ref[0, 0] = dots.astype(jnp.float32) * s_ref[:]


def _conv_gemm_popcount(
    x: Array,
    packed: Array,
    scale: Array,
    strides: Tuple[int, int],
    padding: str,
    *,
    ci: int,
    interpret: bool,
    block_n: int = None,
) -> Array:
    """Fused-flavor popcount conv: Pallas sign+pack of the padded input
    (channels packed once, reused by every tap that reads the pixel —
    the patch-free counterpart of the reference path's per-tap
    ``pack_bits`` calls), then the conv-as-gemm kernel.

    Bit-identical to the reference ``_packed_conv_forward`` schedules:
    identical padding semantics (ONE-padded SAME, the documented
    popcount deviation), identical ``k_true = kh*kw*ci`` (the +1
    channel padding matches ``pack_conv_kernel``'s +1 pad bits — zero
    mismatches), and the same int32 -> fp32 -> one-multiply epilogue."""
    kh, kw, ciw, co = packed.shape
    xp, ho, wo = _spatial_pad(x, kh, kw, strides, padding, 1.0)
    sh, sw = strides
    b, hp, wp, _ = xp.shape
    ci_pad = ciw * 32
    if ci_pad != ci:
        xp = jnp.pad(
            xp, ((0, 0), (0, 0), (0, 0), (0, ci_pad - ci)),
            constant_values=1.0,
        )
    # Trailing-dim reshapes are layout-trivial (no relayout copy).
    xq = pack_rows_packed(
        xp.reshape(-1, ci_pad), interpret=interpret
    ).reshape(b, hp, wp, ciw)
    wq = packed.reshape(kh, kw * ciw, co)  # tap-major K, row-sliced by dy
    if block_n is None:
        block_n = _default_binary_conv_block_n(wo, ciw, co)
    np_ = _round_up(co, block_n)
    if np_ != co:
        wq = jnp.pad(wq, ((0, 0), (0, 0), (0, np_ - co)))
    s_pad = jnp.pad(
        scale.astype(jnp.float32).reshape(1, co), ((0, 0), (0, np_ - co))
    )

    out = pl.pallas_call(
        partial(
            _conv_gemm_kernel,
            kw=kw, sw=sw, wo=wo, ciw=ciw, k_true=kh * kw * ci,
        ),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, np_), jnp.float32),
        grid=(b, ho, np_ // block_n, kh),
        in_specs=[
            pl.BlockSpec(
                (1, 1, wp, ciw),
                lambda bi, i, j, dy: (bi, i * sh + dy, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, kw * ciw, block_n),
                lambda bi, i, j, dy: (dy, 0, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_n),
                lambda bi, i, j, dy: (0, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, wo, block_n),
            lambda bi, i, j, dy: (bi, i, 0, j),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[pltpu.VMEM((wo, block_n), jnp.int32)],
        compiler_params=_mosaic_params(
            _binary_conv_vmem_estimate(wo, wp, ciw, kw, block_n)
        ),
        interpret=_resid_interpret(interpret),
    )(xq, wq, s_pad)
    return out[..., :co]


#: Auto tap-fusion threshold: fuse when the tap-major patch matrix
#: ([M, kh*kw*ci_pad] int8-equivalent) stays under this many bytes.
#: Covers the whole latency-critical small-batch inference regime (the
#: only regime where the packed path wins — BASELINE.md) while the
#: training-shape fallback streams taps to bound peak memory.
_FUSE_TAPS_MAX_BYTES = 32 * 2**20


def _packed_conv_forward(
    x: Array,
    packed: Array,
    scale: Array,
    strides: Tuple[int, int],
    padding: str,
    *,
    ci: int,
    use_popcount: bool,
    interpret: bool,
    fuse_taps: bool = None,
    flavor: str = "auto",
) -> Array:
    """Conv against pre-packed weights, as tap GEMMs on a Pallas kernel.

    Two schedules over the ``sum over (dy,dx) of shifted_x @ W[dy,dx]``
    decomposition, chosen by ``fuse_taps`` (default: auto by patch size):

    - **Fused** (small M — the batch-1/low-latency inference regime): the
      kh*kw shifted views concatenate along K into one tap-major patch
      matrix and ONE K-tiled kernel launch contracts all taps. Kernel
      launch overhead stops multiplying by kh*kw — this is what lets the
      conv-level latency approach the GEMM-level packed win (the round-2
      known-gap fix, BASELINE.md).
    - **Per-tap** (large M, training shapes): each tap launches its own
      GEMM so peak memory stays at one [M, ci] slice instead of a
      kh*kw-times-larger patch matrix (im2col traffic is exactly what
      this path exists to avoid at scale).

    Both schedules are bit-identical: the tap-major K layout matches
    ``pack_conv_kernel``'s [kh, kw, ciw, co] word order reshaped to
    [kh*kw*ciw, co], per-tap K-padding included (A pads zeros on the MXU
    path — contributing nothing against any weight bit — and +1s on the
    popcount path, matching the weight pad bits, i.e. zero mismatches).

    ``use_popcount=False``: packed-weight MXU kernel, zero-padding, exact
    vs the float conv. ``use_popcount=True``: both operands packed, VPU
    popcount kernel — spatial padding must then be +-1, so SAME uses
    ONE-padding (the LCE-style fast semantics; documented, and exact for
    VALID).

    ``flavor`` (§21): "pallas" (or "auto" on TPU) routes the popcount
    path to the fused conv-as-gemm kernel (:func:`_conv_gemm_popcount`,
    bit-identical); the MXU path has no fused flavor yet, so an
    explicit "pallas" there warns and degrades to this composition.
    """
    resolved = resolve_binary_flavor(flavor)
    if use_popcount and resolved == "pallas":
        return _conv_gemm_popcount(
            x, packed, scale, tuple(strides), padding,
            ci=ci, interpret=interpret,
        )
    if flavor == "pallas" and not use_popcount:
        _warn_pallas_fallback("the packed-weight MXU conv "
                              "(use_popcount=False)")
    kh, kw, ciw, co = packed.shape
    b, _, _, _ = x.shape
    pad_value = 1.0 if use_popcount else 0.0
    xp, ho, wo = _spatial_pad(x, kh, kw, strides, padding, pad_value)
    sh, sw = strides
    m = b * ho * wo
    ci_pad = ciw * 32

    if fuse_taps is None:
        # The patch matrix materializes in x's dtype before the kernel's
        # int8/packed cast, so the guard must count real bytes.
        itemsize = jnp.dtype(x.dtype).itemsize
        fuse_taps = m * kh * kw * ci_pad * itemsize <= _FUSE_TAPS_MAX_BYTES

    def tap_slice(dy, dx):
        tap = xp[:, dy : dy + (ho - 1) * sh + 1 : sh,
                 dx : dx + (wo - 1) * sw + 1 : sw, :]
        flat = tap.reshape(m, ci)
        if ci_pad != ci:
            flat = jnp.pad(
                flat, ((0, 0), (0, ci_pad - ci)), constant_values=pad_value
            )
        return flat

    if fuse_taps:
        patches = jnp.concatenate(
            [tap_slice(dy, dx) for dy in range(kh) for dx in range(kw)],
            axis=-1,
        )  # [M, kh*kw*ci_pad], tap-major K.
        b_all = packed.reshape(kh * kw * ciw, co)
        if use_popcount:
            ap = pack_bits(patches, axis=-1)  # word-aligned per tap
            acc = xnor_matmul_packed(
                ap, b_all, k_true=kh * kw * ci, interpret=interpret
            )
        else:
            acc = packed_weight_matmul(patches, b_all, interpret=interpret)
    elif use_popcount:
        acc = None
        for dy in range(kh):
            for dx in range(kw):
                ap = pack_bits(tap_slice(dy, dx), axis=-1)
                out = xnor_matmul_packed(
                    ap, packed[dy, dx], k_true=ci, interpret=interpret
                )
                acc = out if acc is None else acc + out
    else:
        acc = None
        for dy in range(kh):
            for dx in range(kw):
                out = packed_weight_matmul(
                    tap_slice(dy, dx), packed[dy, dx], interpret=interpret
                )
                acc = out if acc is None else acc + out
    y = acc.astype(jnp.float32) * scale[None, :]
    return y.reshape(b, ho, wo, co)


def conv_dim_numbers(spatial_rank: int) -> Tuple[str, str, str]:
    """Channels-last dimension-number strings for a given spatial rank
    (1 -> NWC/WIO, 2 -> NHWC/HWIO, 3 -> NDHWC/DHWIO). Channels-last is
    the TPU-native layout: the channel contraction lands on MXU lanes."""
    spatial = {1: "W", 2: "HW", 3: "DHW"}.get(spatial_rank)
    if spatial is None:
        raise ValueError(f"Unsupported spatial rank {spatial_rank} (1/2/3).")
    return (f"N{spatial}C", f"{spatial}IO", f"N{spatial}C")


def _float_conv(x, k, strides, padding, groups=1):
    # Gradient convs follow the model's COMPUTE dtype (x's dtype): the
    # quantized kernel arrives fp32 (latent storage) even in bf16 mixed
    # precision, and promoting the backward to fp32 would run the
    # dgrad/wgrad convs at 1/8th MXU peak — measured 2.9x forward cost
    # instead of the expected ~2x (BASELINE.md round-3 decomposition).
    # The +-1 signs are exact in bf16 (per-channel scales round like any
    # mixed-precision weight); the MXU accumulates in fp32 either way, so
    # this is standard bf16 mixed-precision backward, and fp32 models are
    # untouched (x is fp32 there).
    dtype = x.dtype
    return jax.lax.conv_general_dilated(
        x, k.astype(dtype), window_strides=tuple(strides),
        padding=padding, dimension_numbers=conv_dim_numbers(k.ndim - 2),
        feature_group_count=groups,
    )


def _reference_conv(x, k, strides, padding, use_popcount):
    """The float function each binary conv path equals on its domain —
    including the popcount path's ONE-padded SAME semantics, so VJPs taken
    of this function match the executed forward exactly (jnp.pad's VJP
    slices the interior, handling the border gradient)."""
    if use_popcount and padding == "SAME":
        kh, kw = k.shape[:2]
        xp, _, _ = _spatial_pad(x, kh, kw, tuple(strides), "SAME", 1.0)
        return _float_conv(xp, k, strides, "VALID")
    return _float_conv(x, k, strides, padding)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def xnor_conv(
    x: Array,
    q_kernel: Array,
    strides: Tuple[int, int],
    padding: str,
    use_popcount: bool = False,
    interpret: bool = False,
    flavor: str = "auto",
) -> Array:
    """NHWC binary conv through the Pallas packed kernels.

    ``x`` must be quantized (+-1 values); ``q_kernel`` [kh, kw, ci, co]
    must be sign x per-channel scale (quantizer output). Forward packs the
    weights and runs per-tap packed GEMMs; backward is the float conv's
    VJP on the saved quantized operands (the op IS that function on its
    domain), so STE gradients compose exactly as on the mxu/int8 paths.

    ``use_popcount=False`` (packed-weight MXU kernel) is bit-exact vs the
    float conv incl. SAME zero-padding. ``use_popcount=True`` (bit-serial
    VPU kernel) uses ONE-padding for SAME — exact for VALID, documented
    deviation for SAME.
    """
    ci = x.shape[-1]
    packed, scale = pack_conv_kernel(q_kernel)
    return _packed_conv_forward(
        x, packed, scale, strides, padding,
        ci=ci, use_popcount=use_popcount, interpret=interpret,
        flavor=flavor,
    )


def _xnor_conv_fwd(x, q_kernel, strides, padding, use_popcount, interpret,
                   flavor):
    packed, scale = pack_conv_kernel(q_kernel)
    y = _packed_conv_forward(
        x, packed, scale, strides, padding,
        ci=x.shape[-1], use_popcount=use_popcount, interpret=interpret,
        flavor=flavor,
    )
    return y, (x, q_kernel)


def _xnor_conv_bwd(strides, padding, use_popcount, interpret, flavor, res, g):
    x, q_kernel = res
    _, vjp = jax.vjp(
        lambda xx, kk: _reference_conv(xx, kk, strides, padding, use_popcount),
        x, q_kernel,
    )
    dx, dk = vjp(g.astype(x.dtype))
    return dx.astype(x.dtype), dk.astype(q_kernel.dtype)


xnor_conv.defvjp(_xnor_conv_fwd, _xnor_conv_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _packed_conv_infer_vjp(x, packed, scale, strides, padding, use_popcount,
                           interpret, flavor):
    return _packed_conv_forward(
        x, packed, scale, strides, padding,
        ci=x.shape[-1], use_popcount=use_popcount, interpret=interpret,
        flavor=flavor,
    )


def _packed_infer_fwd(x, packed, scale, strides, padding, use_popcount,
                      interpret, flavor):
    y = _packed_conv_forward(
        x, packed, scale, strides, padding,
        ci=x.shape[-1], use_popcount=use_popcount, interpret=interpret,
        flavor=flavor,
    )
    return y, None


def _packed_infer_bwd(strides, padding, use_popcount, interpret, flavor,
                      res, g):
    raise ValueError(
        "packed_conv_infer is inference-only: packed weights carry no "
        "latent parameters to train. Differentiate the float model "
        "(xnor_conv packs on the fly) and convert with "
        "pack_quantconv_params for deployment."
    )


_packed_conv_infer_vjp.defvjp(_packed_infer_fwd, _packed_infer_bwd)


def packed_conv_infer(
    x: Array,
    packed: Array,
    scale: Array,
    strides: Tuple[int, int],
    padding: str,
    *,
    use_popcount: bool = False,
    interpret: bool = False,
    flavor: str = "auto",
) -> Array:
    """Inference conv from PRE-PACKED weights (32x less weight HBM).

    This is the deployment path: weights never exist unpacked on device.
    INFERENCE-ONLY: differentiating through it raises (a silent zero
    gradient would let a packed model "train" to nothing); quantized
    training uses :func:`xnor_conv`, which packs latent weights on the
    fly. ``flavor`` selects the §21 fused kernels (see
    :func:`resolve_binary_flavor`).
    """
    return _packed_conv_infer_vjp(
        x, packed, scale, strides, padding, use_popcount, interpret, flavor
    )


# -- dense (matmul) binary paths --------------------------------------------


def pack_dense_kernel(q_kernel: Array) -> Tuple[Array, Array]:
    """Pack a quantized dense kernel [K, N] (sign x per-output-channel
    scale) into ``(packed [ceil(K/32), N] int32, scale [N] float32)`` —
    the dense counterpart of :func:`pack_conv_kernel` (32x weight
    compression; the scale re-applies to the integer GEMM output)."""
    k, n = q_kernel.shape
    scale = jnp.max(jnp.abs(q_kernel), axis=0).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, 1.0)
    signs = q_kernel / safe  # exactly +-1 by the quantizer contract
    k_pad = _round_up(k, 32)
    if k_pad != k:
        signs = jnp.pad(signs, ((0, k_pad - k), (0, 0)), constant_values=1.0)
    return pack_bits(signs, axis=0), scale


def _flatten_leading(x: Array) -> Tuple[Array, Tuple[int, ...]]:
    """[..., K] -> ([M, K], leading shape) for the 2-D GEMM kernels."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _packed_dense_forward(
    x: Array, packed: Array, scale: Array, *, k_true: int,
    use_popcount: bool, interpret: bool, flavor: str = "auto",
) -> Array:
    x2, lead = _flatten_leading(x)
    resolved = resolve_binary_flavor(flavor)
    if use_popcount:
        # Both operands packed: K pads with +1s on BOTH sides (matching
        # bits, zero mismatches — exact; requires +-1 inputs, validated
        # by the layer).
        k_pad = _round_up(k_true, 32)
        if k_pad != k_true:
            x2 = jnp.pad(
                x2, ((0, 0), (0, k_pad - k_true)), constant_values=1.0
            )
        if resolved == "pallas":
            # §21 fused path: Pallas sign+pack producer + fused-epilogue
            # GEMM — bit-identical to the composition below (zero-ULP
            # epilogue argument in _xnor_scaled_kernel).
            ap = pack_rows_packed(x2, interpret=interpret)
            y = xnor_matmul_packed_scaled(
                ap, packed, scale, k_true=k_true, interpret=interpret
            )
            return y.reshape(*lead, -1)
        acc = xnor_matmul_packed(
            pack_bits(x2, axis=-1), packed, k_true=k_true,
            interpret=interpret,
        )
    else:
        if flavor == "pallas":
            _warn_pallas_fallback("the packed-weight MXU dense "
                                  "(use_popcount=False)")
        # Weights-only packed: A pads K with ZEROS (contribute nothing
        # against any weight bit — exact for {-1, 0, +1} inputs).
        acc = packed_weight_matmul(x2, packed, interpret=interpret)
    y = acc.astype(jnp.float32) * scale[None, :]
    return y.reshape(*lead, -1)


def _float_dense(x, k):
    dtype = x.dtype  # Backward follows compute dtype (see _float_conv).
    return jnp.dot(x, k.astype(dtype))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def xnor_dense(x: Array, q_kernel: Array, use_popcount: bool = False,
               interpret: bool = False, flavor: str = "auto") -> Array:
    """Binary dense layer [..., K] @ [K, N] through the Pallas packed
    kernels, packing the latent-quantized kernel on the fly (the
    training-compatible path; STE composes via the float-matmul VJP on
    the saved quantized operands, exactly like :func:`xnor_conv`). The
    "pallas" flavor fuses the input-side sign+pack and the scale
    epilogue into the GEMM (§21) — the training-path forward reads sign
    words directly instead of round-tripping ±1 floats through HBM."""
    packed, scale = pack_dense_kernel(q_kernel)
    return _packed_dense_forward(
        x, packed, scale, k_true=q_kernel.shape[0],
        use_popcount=use_popcount, interpret=interpret, flavor=flavor,
    )


def _xnor_dense_fwd(x, q_kernel, use_popcount, interpret, flavor):
    packed, scale = pack_dense_kernel(q_kernel)
    y = _packed_dense_forward(
        x, packed, scale, k_true=q_kernel.shape[0],
        use_popcount=use_popcount, interpret=interpret, flavor=flavor,
    )
    return y, (x, q_kernel)


def _xnor_dense_bwd(use_popcount, interpret, flavor, res, g):
    x, q_kernel = res
    _, vjp = jax.vjp(_float_dense, x, q_kernel)
    dx, dk = vjp(g.astype(x.dtype))
    return dx.astype(x.dtype), dk.astype(q_kernel.dtype)


xnor_dense.defvjp(_xnor_dense_fwd, _xnor_dense_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _packed_dense_infer_vjp(x, packed, scale, k_true, use_popcount,
                            interpret, flavor):
    return _packed_dense_forward(
        x, packed, scale, k_true=k_true, use_popcount=use_popcount,
        interpret=interpret, flavor=flavor,
    )


def _packed_dense_infer_fwd(x, packed, scale, k_true, use_popcount,
                            interpret, flavor):
    return (
        _packed_dense_forward(
            x, packed, scale, k_true=k_true, use_popcount=use_popcount,
            interpret=interpret, flavor=flavor,
        ),
        None,
    )


def _packed_dense_infer_bwd(k_true, use_popcount, interpret, flavor, res, g):
    raise ValueError(
        "packed_dense_infer is inference-only: packed weights carry no "
        "latent parameters to train. Differentiate the float model "
        "(xnor_dense packs on the fly) and convert with "
        "pack_quantconv_params for deployment."
    )


_packed_dense_infer_vjp.defvjp(_packed_dense_infer_fwd,
                               _packed_dense_infer_bwd)


def packed_dense_infer(
    x: Array,
    packed: Array,
    scale: Array,
    k_true: int,
    *,
    use_popcount: bool = False,
    interpret: bool = False,
    flavor: str = "auto",
) -> Array:
    """Inference dense from PRE-PACKED weights (32x less weight HBM) —
    the dense deployment path; differentiating through it raises.
    ``flavor`` selects the §21 fused kernels (see
    :func:`resolve_binary_flavor`)."""
    return _packed_dense_infer_vjp(
        x, packed, scale, k_true, use_popcount, interpret, flavor
    )


def _int8_dense_forward(x_sign, k_sign, scaled):
    if scaled:
        kscale = jnp.max(jnp.abs(k_sign), axis=0)
        safe = jnp.where(kscale > 0, kscale, jnp.ones_like(kscale))
        k8 = jnp.round(k_sign / safe).astype(jnp.int8)
    else:
        k8 = jnp.round(k_sign).astype(jnp.int8)
    x8 = jnp.round(x_sign).astype(jnp.int8)
    x2, lead = _flatten_leading(x8)
    out = jax.lax.dot_general(
        x2, k8, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    out = out.reshape(*lead, -1)
    return out * safe.astype(jnp.float32) if scaled else out


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def int8_dense(x_sign: Array, k_sign: Array, scaled: bool = True) -> Array:
    """Dense layer of quantized operands on the int8 MXU path — the
    dense counterpart of :func:`int8_conv` (exact on {-1, 0, +1} inputs
    x sign-per-channel-scale kernels, float-matmul gradients)."""
    return _int8_dense_forward(x_sign, k_sign, scaled)


def _int8_dense_fwd(x_sign, k_sign, scaled):
    return _int8_dense_forward(x_sign, k_sign, scaled), (x_sign, k_sign)


def _int8_dense_bwd(scaled, res, g):
    x_sign, k_sign = res
    _, vjp = jax.vjp(_float_dense, x_sign, k_sign)
    dx, dk = vjp(g.astype(x_sign.dtype))
    return dx.astype(x_sign.dtype), dk.astype(k_sign.dtype)


int8_dense.defvjp(_int8_dense_fwd, _int8_dense_bwd)


# -- int8 MXU path ----------------------------------------------------------


def int8_matmul(a_sign: Array, b_sign: Array) -> Array:
    """Binary GEMM on the MXU: +-1 as int8, int32 accumulation (2x bf16
    MXU peak; exact on {-1, 0, +1} operands — round, not sign, so a
    literal 0 stays 0, matching :func:`int8_conv`'s contract)."""
    a8 = jnp.round(a_sign).astype(jnp.int8)
    b8 = jnp.round(b_sign).astype(jnp.int8)
    return jax.lax.dot_general(
        a8,
        b8,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)


def _int8_conv_forward(x_sign, k_sign, strides, padding, groups, scaled):
    if scaled:
        # Kernel contract: sign x per-OUTPUT-channel scale (what the
        # sign-family quantizers produce). Dividing by the channel max
        # recovers exact {-1, 0, +1} int8 values — so
        # magnitude_aware_sign kernels run exactly too (the scale
        # re-applies to the int32 sums, ONE rounding instead of the
        # float conv's per-element roundings).
        kscale = jnp.max(jnp.abs(k_sign), axis=tuple(range(k_sign.ndim - 1)))
        safe = jnp.where(kscale > 0, kscale, jnp.ones_like(kscale))
        k8 = jnp.round(k_sign / safe).astype(jnp.int8)
    else:
        # Statically known unscaled ({-1, 0, +1} values): skip the
        # runtime scale extraction (measurable at train-step scale).
        k8 = jnp.round(k_sign).astype(jnp.int8)
    # Inputs are exact small integers by the validated quantizer contract
    # ({-1, 0, +1}); round (not sign) so a literal 0 stays 0.
    x8 = jnp.round(x_sign).astype(jnp.int8)
    out = jax.lax.conv_general_dilated(
        x8, k8, window_strides=tuple(strides), padding=padding,
        dimension_numbers=conv_dim_numbers(k_sign.ndim - 2),
        feature_group_count=groups,
        preferred_element_type=jnp.int32,
    )
    out = out.astype(jnp.float32)
    return out * safe.astype(jnp.float32) if scaled else out


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def int8_conv(x_sign: Array, k_sign: Array, strides: Tuple[int, ...],
              padding: str, groups: int = 1, scaled: bool = True,
              pack_residuals: bool = False,
              pallas_interpret: bool = None) -> Array:
    """Channels-last conv of quantized operands on the int8 MXU path —
    any spatial rank (1-D [N,W,C], 2-D NHWC, 3-D NDHWC; rank inferred
    from the kernel).

    Inputs must be exact small integers ({-1, 0, +1}); the kernel must be
    sign x per-output-channel scale. Exact vs the float conv on that
    domain (integer accumulation, one scale multiply), with the float
    conv's gradients (the op *is* that function there). ``groups``
    supports depthwise/grouped convs (QuantDepthwiseConv); pass
    ``scaled=False`` when the kernel is statically known to be pure
    {-1, 0, +1} (skips the scale extraction).

    ``pack_residuals=True`` stores the activation residual BIT-PACKED
    between forward and backward (1 bit/value instead of 16/32): the
    wgrad reconstructs ``x_sign`` from the packed words, bit-exactly,
    because the values are +-1 by contract. Requires strictly +-1 inputs
    (a 0 would unpack as +1 and corrupt the weight gradient — the layer
    gates this on the +-1 input quantizers). This is the activation-
    residency lever against the bandwidth-bound backward (the residual
    write+read traffic drops 32x; VERDICT r3 next #1).
    ``pallas_interpret`` applies to the residual pack/unpack kernels
    only (None = auto: interpret off-TPU)."""
    return _int8_conv_forward(x_sign, k_sign, strides, padding, groups, scaled)


def _int8_conv_fwd(x_sign, k_sign, strides, padding, groups, scaled,
                   pack_residuals, pallas_interpret):
    y = _int8_conv_forward(x_sign, k_sign, strides, padding, groups, scaled)
    if pack_residuals:
        # Size-0 token x[:0] (shape (0, *spatial, C)): bwd must rebuild
        # x at its original shape/dtype, and neither is recoverable from
        # the flat packed words alone (batch comes from the cotangent).
        res = (
            pack_resid(x_sign, interpret=pallas_interpret),
            x_sign[:0],
            k_sign,
        )
    else:
        res = (x_sign, k_sign)
    return y, res


def _int8_conv_bwd(strides, padding, groups, scaled, pack_residuals,
                   pallas_interpret, res, g):
    if pack_residuals:
        words, tok, k_sign = res
        shape = (g.shape[0], *tok.shape[1:])
        x_sign = unpack_resid_pm1(
            words, shape, tok.dtype, interpret=pallas_interpret
        )
    else:
        x_sign, k_sign = res
    _, vjp = jax.vjp(
        lambda x, k: _float_conv(x, k, strides, padding, groups),
        x_sign, k_sign,
    )
    dx, dk = vjp(g.astype(x_sign.dtype))
    return dx.astype(x_sign.dtype), dk.astype(k_sign.dtype)


int8_conv.defvjp(_int8_conv_fwd, _int8_conv_bwd)


def _float_conv_transpose(x, k, strides, padding):
    dtype = x.dtype
    return jax.lax.conv_transpose(
        x, k.astype(dtype), strides=tuple(strides), padding=padding,
        dimension_numbers=conv_dim_numbers(k.ndim - 2),
    )


def _int8_conv_transpose_forward(x_sign, k_sign, strides, padding, scaled):
    if scaled:
        kscale = jnp.max(jnp.abs(k_sign), axis=tuple(range(k_sign.ndim - 1)))
        safe = jnp.where(kscale > 0, kscale, jnp.ones_like(kscale))
        k8 = jnp.round(k_sign / safe).astype(jnp.int8)
    else:
        k8 = jnp.round(k_sign).astype(jnp.int8)
    x8 = jnp.round(x_sign).astype(jnp.int8)
    out = jax.lax.conv_transpose(
        x8, k8, strides=tuple(strides), padding=padding,
        dimension_numbers=conv_dim_numbers(k_sign.ndim - 2),
        preferred_element_type=jnp.int32,
    )
    out = out.astype(jnp.float32)
    return out * safe.astype(jnp.float32) if scaled else out


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def int8_conv_transpose(x_sign: Array, k_sign: Array,
                        strides: Tuple[int, ...], padding: str,
                        scaled: bool = True) -> Array:
    """Channels-last TRANSPOSED conv of quantized operands on the int8
    MXU path (any spatial rank; the fractionally-strided conv is still a
    conv, so the same exactness argument as :func:`int8_conv` applies —
    integer accumulation over {-1, 0, +1} values, one per-channel scale
    multiply; inserted stride zeros are exact in int8)."""
    return _int8_conv_transpose_forward(x_sign, k_sign, strides, padding,
                                        scaled)


def _int8_convt_fwd(x_sign, k_sign, strides, padding, scaled):
    return (
        _int8_conv_transpose_forward(x_sign, k_sign, strides, padding, scaled),
        (x_sign, k_sign),
    )


def _int8_convt_bwd(strides, padding, scaled, res, g):
    x_sign, k_sign = res
    _, vjp = jax.vjp(
        lambda x, k: _float_conv_transpose(x, k, strides, padding),
        x_sign, k_sign,
    )
    dx, dk = vjp(g.astype(x_sign.dtype))
    return dx.astype(x_sign.dtype), dk.astype(k_sign.dtype)


int8_conv_transpose.defvjp(_int8_convt_fwd, _int8_convt_bwd)
