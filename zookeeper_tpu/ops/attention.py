"""Ring attention: sequence-parallel exact attention over a mesh axis.

Beyond the reference's contract (SURVEY.md §2.5 scopes SP/long-context
out — the reference's CNN workloads have no attention anywhere), but the
mesh/sharding API here was "kept general so SP could be added without
redesign"; this module is that claim as working code, and the idiomatic
TPU design the task brief names (ring attention over ICI instead of
gathering the full sequence).

Design (Liu et al. 2023, "Ring Attention with Blockwise Transformers",
public technique): Q/K/V are sharded along the SEQUENCE dimension over a
mesh axis. Each device keeps its Q shard resident and processes one K/V
block at a time with a numerically-stable ONLINE softmax (running max /
running sum / weighted accumulator — the flash-attention recurrence),
rotating the K/V shards one hop around the ring with
``lax.ppermute`` per step. After ``axis_size`` steps every Q block has
attended to every K/V block without any device ever holding more than
``1/axis_size`` of the sequence — memory per device stays O(S/n), the
rotation rides the ICI ring, and XLA overlaps the permute with the
block's compute. Results are EXACT full attention (same reassociation
class as flash attention), not an approximation.

The op is written shard_map-first: :func:`ring_attention_local` is the
per-device program (composes with any outer pjit/shard_map program, and
reverse-differentiates — the ring is a ``lax.scan``, and the backward of
``ppermute`` is the inverse rotation, so gradients ride the same ring);
:func:`ring_attention` is the one-call wrapper that builds the
shard_map. On a 1-device axis both reduce to plain attention.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from zookeeper_tpu.ops.blocks import (  # noqa: F401  (re-exports)
    _FLASH_VMEM_BUDGET,
    _pool_decode_block_pages,
    _pool_decode_vmem_estimate,
    _round_up,
    _default_flash_blocks,
    _flash_bwd_vmem_estimate,
    vmem_limit_bytes,
)

# Large-negative mask value: finite (so a fully-masked row's exp()
# underflows to 0 instead of producing -inf - -inf = nan in the online
# rescale), far below any real fp32 score.
_MASK_VALUE = -0.5 * float(jnp.finfo(jnp.float32).max)

#: Lanes of one TPU vector register: the quantum a folded KV row pads to.
_KV_LANES = 128


def _mosaic_params(estimate: int):
    """The one ``compiler_params`` every pallas_call in the package
    passes: the scoped-VMEM limit ``ops.blocks.vmem_limit_bytes``
    derives from the call's own per-grid-step estimate."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes(estimate))


def _repeat_kv_heads(q, k, v):
    """Grouped heads in the oracle paths: key/value head ``j`` serves
    query heads ``j * g .. j * g + g - 1``, so each is repeated ``g``
    times (the kernels index instead and repeat nothing in memory).
    Equal head counts pass through untouched."""
    h, hkv = q.shape[2], k.shape[2]
    if h == hkv:
        return k, v
    if h % hkv:
        raise ValueError(
            f"{h} query heads are not a multiple of {hkv} key/value heads."
        )
    return jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain full softmax attention — the single-device path and the
    oracle the ring implementation is tested against.

    Shapes: ``q/k/v [batch, seq, heads, head_dim]`` -> same for the
    output (``k``/``v`` may hold fewer, grouped heads). Scores
    accumulate in fp32 regardless of input dtype (the TPU-standard
    mixed-precision contract); output casts back. ``window`` (with
    ``causal``) keeps a query at ``i`` to the keys ``i - window < p <=
    i``: the band a sliding-window layer attends.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("window needs causal=True.")
    k, v = _repeat_kv_heads(q, k, v)
    # HIGHEST precision: on TPU, f32 einsum at DEFAULT multiplies in
    # bf16; the ring and dense paths reassociate differently, so both
    # pin full-precision multiplies to stay comparable at tight
    # tolerances on any backend.
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q,
        k,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    ) * jnp.float32(scale)
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        ki = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        keep = ki <= qi
        if window is not None:
            keep = keep & (qi - ki < window)
        s = jnp.where(keep, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd",
        p,
        v.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(q.dtype)


def cached_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-position attention over a per-sequence KV cache — the
    incremental-decode counterpart of :func:`attention_reference`.

    Shapes: ``q [batch, 1, heads, head_dim]`` (the ONE new token per
    sequence), ``k_cache/v_cache [batch, capacity, heads, head_dim]``
    (the ring/paged KV buffers, already containing the new token's K/V
    at index ``lengths``), ``lengths [batch] int32`` — the number of
    PREVIOUSLY cached tokens per sequence, so cache rows ``0..lengths``
    inclusive are attended and everything past them (stale K/V from a
    refilled slot's previous occupant, not-yet-overwritten prefill
    padding) is masked out. Output ``[batch, 1, heads, head_dim]``.

    Numerics deliberately mirror :func:`attention_reference` op for op
    (fp32 HIGHEST-precision einsums, the same finite ``_MASK_VALUE``,
    ``jax.nn.softmax``): masked scores underflow to exactly 0.0 after
    the softmax shift, so the only divergence from the full-context
    oracle's row at the same position is dot-reduction reassociation
    over the (capacity vs sequence) axis — ULP-level, and pinned
    token-exact by the decode parity certification (docs/DESIGN.md
    §15). ``k_cache``/``v_cache`` may hold fewer, grouped heads;
    ``window`` keeps the new token (at ``lengths``) to the rows
    ``lengths - window < j <= lengths``.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    k_cache, v_cache = _repeat_kv_heads(q, k_cache, v_cache)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q,
        k_cache,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    ) * jnp.float32(scale)
    ki = lax.broadcasted_iota(jnp.int32, (k_cache.shape[1],), 0)
    mask = ki[None, None, None, :] <= lengths[:, None, None, None]
    if window is not None:
        mask = mask & (
            ki[None, None, None, :] > lengths[:, None, None, None] - window
        )
    s = jnp.where(mask, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd",
        p,
        v_cache.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(q.dtype)


def verify_cached_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-position attention over a per-sequence KV cache — the
    speculative-decode verify counterpart of :func:`cached_attention`
    (docs/DESIGN.md §18).

    Shapes: ``q [batch, w, heads, head_dim]`` (``w`` draft positions per
    sequence: position ``j`` is the token at sequence index
    ``lengths + j``), ``k_cache/v_cache [batch, capacity, heads,
    head_dim]`` (already containing all ``w`` new K/V rows at indices
    ``lengths..lengths+w-1``), ``lengths [batch] int32`` — the number of
    PREVIOUSLY cached tokens per sequence. Draft position ``j`` attends
    cache rows ``0..lengths+j`` inclusive (causal within the window,
    full prefix before it); everything past is masked. Output
    ``[batch, w, heads, head_dim]``. At ``w == 1`` this is exactly
    :func:`cached_attention` (same mask, same ops).

    Numerics mirror :func:`cached_attention` op for op — fp32
    HIGHEST-precision einsums, the same finite ``_MASK_VALUE``,
    ``jax.nn.softmax`` — so each verify position's output differs from
    the single-position decode step's at the same (sequence, position)
    only by dot-reduction reassociation over the batched-q einsum:
    ULP-level, and pinned TOKEN-exact (speculative greedy == plain
    greedy) by the speculative-decode certification. Grouped heads and
    ``window`` as in :func:`cached_attention`, the band taken at each
    draft position.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    k_cache, v_cache = _repeat_kv_heads(q, k_cache, v_cache)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q,
        k_cache,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    ) * jnp.float32(scale)
    w = q.shape[1]
    ki = lax.broadcasted_iota(jnp.int32, (k_cache.shape[1],), 0)
    qi = lax.broadcasted_iota(jnp.int32, (w,), 0)
    pos = lengths[:, None, None, None] + qi[None, None, :, None]
    mask = ki[None, None, None, :] <= pos
    if window is not None:
        mask = mask & (ki[None, None, None, :] > pos - window)
    s = jnp.where(mask, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd",
        p,
        v_cache.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(q.dtype)


def decode_attention_supported(num_heads: int, head_dim: int) -> bool:
    """Whether :func:`pool_paged_decode_attention` serves this geometry.

    The kernel reads folded rows (heads end to end on the lanes) and
    sums a head's lanes inside one 128-lane register, so ``head_dim``
    has to divide 128; a head_dim off the fp32 sublane quantum (8) is
    untested territory on real silicon, so such geometries take the
    reference einsum instead of risking a Mosaic lowering failure on
    the serving hot path. Interpret mode has no such constraint, but the
    predicate is deliberately backend-independent: a config must resolve
    to the same flavor on the CPU tier-1 runner as on the TPU it deploys
    to.
    """
    if num_heads < 1 or head_dim < 8 or head_dim % 8:
        return False
    return _KV_LANES % head_dim == 0


def kv_row_width(num_heads: int, head_dim: int, head_shards: int = 1) -> int:
    """Lanes one head shard's slice of a folded KV row takes: its
    ``heads * head_dim`` values rounded up to whole 128-lane vector
    registers (GPT-2 XL's 25 x 64 = 1600 -> 1664)."""
    if head_shards < 1 or num_heads % head_shards:
        raise ValueError(
            f"head_shards={head_shards} does not divide "
            f"num_heads={num_heads}."
        )
    return _round_up((num_heads // head_shards) * head_dim, _KV_LANES)


def fold_kv_rows(rows: jax.Array, head_shards: int, row_width: int):
    """``[..., heads, head_dim]`` K/V rows -> the page pool's storage
    form ``[..., head_shards, row_width]``: each shard's heads laid end
    to end on the lane dimension, zero-padded to ``row_width``
    (:func:`kv_row_width`). The pool keeps its rows this way because of
    what the TPU does to any other shape: a row-major ``[..., 25, 64]``
    tile wastes half of every vector register, so XLA gives such an
    array a transposed device layout and every scatter, gather and
    Pallas call (which all want row-major) re-lays-out the whole pool
    (docs/DESIGN.md §20)."""
    *lead, h, d = rows.shape
    rows = rows.reshape(*lead, head_shards, (h // head_shards) * d)
    pad = row_width - rows.shape[-1]
    if pad < 0:
        raise ValueError(
            f"row_width={row_width} cannot hold {rows.shape[-1]} values."
        )
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
    return rows


def unfold_kv_rows(rows: jax.Array, num_heads: int, head_dim: int):
    """Inverse of :func:`fold_kv_rows`: ``[..., head_shards,
    row_width]`` -> ``[..., heads, head_dim]`` (the padding dropped)."""
    *lead, s, _ = rows.shape
    rows = rows[..., : (num_heads // s) * head_dim]
    return rows.reshape(*lead, num_heads, head_dim)


def fold_kv_pool(pool: jax.Array, head_shards: int = 1) -> jax.Array:
    """A page-shaped ``[num_pages, page_size, heads, head_dim]`` array
    -> the pool's storage form ``[num_pages, head_shards, page_size,
    row_width]`` (for callers that build a pool by hand: tests, the
    on-chip kernel checks)."""
    _, _, h, d = pool.shape
    width = kv_row_width(h, d, head_shards)
    return jnp.swapaxes(fold_kv_rows(pool, head_shards, width), 1, 2)


def fold_kv_scales(scale: jax.Array, head_shards: int = 1) -> jax.Array:
    """Page-shaped int8 scales ``[num_pages, page_size, heads]`` -> the
    stored ``[num_pages, head_shards, page_size, heads_per_shard]``."""
    n, ps, h = scale.shape
    return jnp.swapaxes(
        scale.reshape(n, ps, head_shards, h // head_shards), 1, 2
    )


def _gathered_pool_view(pool, page_table, num_heads, head_dim, scale=None):
    """A slot-contiguous view of a shared page pool: gather each slot's
    pages by ``page_table``, unfold the stored rows
    (:func:`unfold_kv_rows`) and flatten the (pages, page_size) axes
    back into the familiar ``[slots, capacity_view, heads, head_dim]``
    cache layout, dequantizing int8 pools inline (``scale [num_pages,
    head_shards, page_size, heads_per_shard]`` — see
    ``ops.quantizers.quantize_kv_rows``). Rows in unallocated table
    entries (clipped to page 0) and garbage rows beyond a slot's length
    are harmless by the validity invariant: every pool-attention
    consumer masks ``j > lengths`` to the finite ``_MASK_VALUE``, whose
    softmax weight underflows to exactly 0.0 — which is also why a
    refilled slot may find its previous occupant's rows in a page."""
    idx = jnp.clip(page_table, 0, pool.shape[0] - 1)
    # [slots, max_pages, head_shards, page_size, row_width]
    g = unfold_kv_rows(jnp.swapaxes(pool[idx], 2, 3), num_heads, head_dim)
    b, m, ps, h, d = g.shape
    if scale is not None:
        sc = jnp.swapaxes(scale[idx], 2, 3).reshape(b, m, ps, h)
        g = g.astype(jnp.float32) * sc[..., None]
    return g.reshape(b, m * ps, h, d)


def pool_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    kv_heads: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-position decode attention over a SHARED page pool — the
    page-indirected counterpart of :func:`cached_attention`
    (docs/DESIGN.md §20).

    Shapes: ``q [slots, 1, heads, head_dim]``, ``k_pool/v_pool
    [num_pages, head_shards, page_size, row_width]`` (the
    device-resident pools every slot's pages live in, rows folded —
    :func:`fold_kv_rows`), ``page_table [slots, max_pages] int32``
    (each slot's logical page ``p`` lives at pool index
    ``page_table[slot, p]``; unallocated entries may be negative —
    they are clipped for the gather and masked by ``lengths``),
    ``lengths [slots]`` as in :func:`cached_attention`. Optional
    ``k_scale/v_scale [num_pages, head_shards, page_size,
    heads_per_shard]`` dequantize int8 pools inline.

    Numerics: the gathered view holds, at every live index, the row
    that was written once, and the math below IS
    :func:`cached_attention` op for op, so the token-parity
    certification composes through the full-context oracle
    (docs/DESIGN.md §15). int8 pools add one exactly-representable
    ``int8 × fp32 scale`` multiply before the same einsums
    (documented-ULP, argmax-pinned by the §20 sweep).

    ``kv_heads`` (default: ``q``'s heads) is how many heads a pool row
    holds when they are grouped; ``window`` as in
    :func:`cached_attention`. A window layer's table may have released
    the pages behind the window (``-1`` entries): the gather reads some
    other page there and the band masks it.
    """
    h, d = kv_heads or q.shape[2], q.shape[3]
    kc = _gathered_pool_view(k_pool, page_table, h, d, k_scale)
    vc = _gathered_pool_view(v_pool, page_table, h, d, v_scale)
    return cached_attention(q, kc, vc, lengths, scale=scale, window=window)


def pool_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    kv_heads: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-position (speculative verify / warm-prefix extend)
    attention over a shared page pool — the page-indirected counterpart
    of :func:`verify_cached_attention`: window position ``j`` attends
    pool rows ``0..lengths+j`` through the slot's page table (``q [slots,
    w, heads, head_dim]``), with the pool operands of
    :func:`pool_decode_attention`; at ``w == 1`` it computes exactly
    what :func:`pool_decode_attention` computes (``kv_heads`` and
    ``window`` as there)."""
    h, d = kv_heads or q.shape[2], q.shape[3]
    kc = _gathered_pool_view(k_pool, page_table, h, d, k_scale)
    vc = _gathered_pool_view(v_pool, page_table, h, d, v_scale)
    return verify_cached_attention(
        q, kc, vc, lengths, scale=scale, window=window
    )


def pool_paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    kv_heads: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Pallas TPU decode attention reading a SHARED page pool through
    per-slot page tables (docs/DESIGN.md §20, §26).

    Same contract as :func:`pool_decode_attention`; different cost
    model. The pools stay whole in HBM and the kernel fetches its own
    pages. A work item is (slot, block of ``N`` consecutive logical
    pages of that slot's band); the grid is (head-shard, LIVE work
    item), its second size a runtime value, with ``lengths``,
    ``page_table`` and the items as scalar-prefetch operands, so a block
    past a slot's length or behind its band is never visited, and the
    only pipelined operands of a grid step are the query block and the
    output block, whatever a block holds. Inside an item the kernel
    starts one async copy a LIVE page and pool, from
    ``pool[table[slot, page], shard]`` (wherever the allocator put the
    page) to the page's rows of a ``[2, N * page_size, row_width]``
    VMEM block a pool: the block is assembled in VMEM, so it need not
    be contiguous in the pool. A page past the length or behind the
    band starts no copy (the length-bounded read of §17, composed with
    indirection); its rows are masked. The block is double-buffered
    ACROSS work items: the copies of the next item (of this slot, or the
    first block of the next one) fly while this one is computed on.
    ``N`` is derived, not set (:func:`pool_decode_block_pages`): from a
    page's bytes, the pages a band can span and the scoped-VMEM budget.

    The rows are folded (heads end to end on the lanes), so a score is
    a sum over one head's ``head_dim`` lanes of a register: a masked
    lane reduction a head inside each 128-lane column; softmax state is
    kept per lane (every lane of a head carries that head's max and
    sum). That needs ``head_dim`` to divide 128
    (:func:`decode_attention_supported`). The
    fetched block is walked by loops whose trip counts come from the
    live length: 128 keys at a time over the whole 128-key pieces of
    the block's live pages, then a page at a time over the pages left
    over (an almost empty slot pays for one page), the row's columns by
    a loop inside. The traced kernel therefore holds one copy of a
    column a row height, whatever ``N`` and however wide the row: what
    a process pays to trace and lower the kernel follows the equations
    it holds, and four copies of a 13-column body cost every serving
    process 4 s of start-up (PERF.md, PR 27-28). int8 pools ride the
    same fetch, dequantized in VMEM; their ``[page_size, heads]``
    float32 scale pages stay pipelined operands, one a page of the
    block (Mosaic refuses to slice an HBM operand whose lane dimension
    pads to 128).

    Grouped heads (``kv_heads`` < ``q``'s heads): the pool's rows hold
    the key/value heads only and nothing is repeated in memory; the
    ``g`` query heads of a group ride the sublanes of the query block.
    Where a head fills a whole 128-lane column (``head_dim`` 128) the
    group's scores are one matmul a column, ``[g, 128] x [128, keys]``,
    over the whole fetched block where every page of it is live and
    over 128 keys at a time in a slot's last block; elsewhere the lane
    reduction runs once a member.

    ``window``: the new token (at ``lengths``) attends rows ``lengths -
    window < j <= lengths`` only, and the grid covers just the pages
    that band can touch, from a first page that is not page 0:
    ``max(lengths - window + 1, 0) // page_size``. Pages behind it are
    never fetched, so their table entries may be released (``-1``).

    Numerics: fp32 online-softmax accumulation with the reference's
    finite mask value: documented-ULP against the pool reference,
    argmax token-exact (docs/DESIGN.md §17); the result
    depends on ``N`` only through the order of float32 sums.
    """
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(
            f"pool_paged_decode_attention expects q [slots, 1, heads, "
            f"head_dim], got {q.shape}."
        )
    if k_pool.shape != v_pool.shape or k_pool.ndim != 4:
        raise ValueError(
            f"k_pool/v_pool must be identical [num_pages, head_shards, "
            f"page_size, row_width], got {k_pool.shape} / {v_pool.shape}."
        )
    b, _, h, d = q.shape
    hkv = int(kv_heads or h)
    num_pages, shards, ps, width = k_pool.shape
    if h % hkv or hkv % shards or width != kv_row_width(hkv, d, shards):
        raise ValueError(
            f"pool {k_pool.shape} does not match q {q.shape} with "
            f"{hkv} key/value heads."
        )
    if not decode_attention_supported(hkv, d):
        raise ValueError(
            f"head_dim={d} is off the pool kernel's geometry (a divisor "
            "of 128)."
        )
    if page_table.ndim != 2 or page_table.shape[0] != b:
        raise ValueError(
            f"page_table must be [slots={b}, max_pages], got "
            f"{page_table.shape}."
        )
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together.")
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1.")
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pool_paged_decode_call(
        q, k_pool, v_pool, page_table, lengths, k_scale, v_scale,
        scale=float(scale), interpret=bool(interpret), kv_heads=hkv,
        window=None if window is None else int(window),
    )


def pool_decode_block_pages(
    page_size: int, row_width: int, itemsize: int, max_pages: int,
    window: Optional[int] = None,
) -> int:
    """``N``: the consecutive logical pages one work item of the pool
    decode kernel fetches, derived from what the call can see — a
    page's bytes, the pages the band can span (the whole table, or a
    window's ``window / page_size + 1``) and the scoped-VMEM budget
    (``ops.blocks._pool_decode_block_pages``). Also the engine's: the
    ``decode_kv_blocks`` counter is reckoned with the kernel's ``N``."""
    return _pool_decode_block_pages(
        page_size, row_width, itemsize,
        _pool_band_span(max_pages, page_size, window),
    )


def _pool_band_span(max_pages, page_size, window):
    """Logical pages the rows a token attends can touch."""
    if window is None:
        return max_pages
    return min(max_pages, (window + page_size - 2) // page_size + 1)


def _pool_first_page(lengths, page_size, window):
    """The first logical page the band of the token at ``lengths`` can
    touch (numpy or jax, array or scalar)."""
    if window is None:
        return lengths * 0
    return (lengths - window + 1).clip(0) // page_size


def _pool_live_items(lengths, page_size, window, block_pages):
    """Work items a slot at ``lengths`` takes: blocks of ``block_pages``
    pages from its band's first page through the new token's (numpy or
    jax, array or scalar). The kernel's grid is their sum."""
    first = _pool_first_page(lengths, page_size, window)
    return (lengths // page_size - first) // block_pages + 1


def _pool_work_items(lens, page_size, window, block_pages, steps):
    """The pool kernel's grid, on the device: the LIVE (slot, block of
    ``block_pages`` pages) work items flattened in slot order, each slot
    from its band's first page to its last live one. ``(item_slot,
    item_step, total)``: which item a grid index is (``steps``: the most
    a slot can have, which sizes the arrays) and how many there are."""
    counts = _pool_live_items(lens, page_size, window, block_pages)
    ends = jnp.cumsum(counts)
    item = jnp.arange(lens.shape[0] * steps, dtype=jnp.int32)
    item_slot = jnp.minimum(
        jnp.searchsorted(ends, item, side="right"), lens.shape[0] - 1
    ).astype(jnp.int32)
    return item_slot, item - (ends - counts)[item_slot], ends[-1:]


def pool_decode_work(
    lengths, *, page_size: int, max_pages: int, block_pages: int,
    window: Optional[int] = None,
):
    """What one call of the pool decode kernel does at ``lengths``
    (numpy): ``(work_items, pages_live, pages_block_capacity)`` — the
    size of the kernel's grid, the pages it fetches, and the pages its
    fetched blocks have room for (``work_items x block_pages``). The
    same arithmetic sizes the grid on the device."""
    import numpy as np

    lens = np.clip(
        np.asarray(lengths, np.int64), 0, max_pages * page_size - 1
    )
    first = _pool_first_page(lens, page_size, window)
    items = int(_pool_live_items(lens, page_size, window, block_pages).sum())
    live = int((lens // page_size - first + 1).sum())
    return items, live, items * block_pages


@partial(
    jax.jit,
    static_argnames=("scale", "interpret", "kv_heads", "window", "halves"),
)
def _pool_paged_decode_call(
    q, k_pool, v_pool, page_table, lengths, k_scale, v_scale, *,
    scale, interpret, kv_heads, window, halves=("copies", "arithmetic"),
):
    """The kernel behind :func:`pool_paged_decode_attention` (operands
    checked there). Jitted so that a program which attends once a layer
    traces and lowers the kernel once, and not once a layer: 24
    lowerings of it were 13 s of an engine's warm-up (PERF.md, PR 25).

    ``halves``: what of a work item the kernel does. Only
    ``tools/probe_pool_decode.py`` passes less than both, to time the
    copies or the arithmetic alone (the result is then meaningless)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, d = q.shape
    num_pages, shards, ps, width = k_pool.shape
    group = h // kv_heads
    hs = kv_heads // shards
    nm = page_table.shape[1]
    # Query rows by group member: row g of a shard holds, at key/value
    # head j's lanes, query head j * group + g. One member: q folded.
    qs = q[:, 0].reshape(b, kv_heads, group, d).swapaxes(1, 2)
    qs = jnp.swapaxes(fold_kv_rows(qs, shards, width), 1, 2)
    cap_view = nm * ps
    lens = jnp.clip(lengths.astype(jnp.int32), 0, cap_view - 1)
    table = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    columns = width // _KV_LANES
    heads_per_column = _KV_LANES // d
    quantized = k_scale is not None
    # One matmul a column where a head is a column and has a group to
    # fill the MXU's rows; the lane reduction otherwise.
    matmul = d == _KV_LANES and group > 1 and not quantized
    per_item = pool_decode_block_pages(
        ps, width, k_pool.dtype.itemsize, nm, window
    )
    keys = per_item * ps
    # The 128 keys of one MXU pass (16 registers of a column): the rows
    # both paths take of a block at a time. The buffers' rows round up
    # to whole pieces, so that the last piece of a band that is no
    # multiple of it reads zeros (masked) and not the other buffer.
    piece = min(ps * max(1, _KV_LANES // ps), keys)
    buffer_rows = -(-keys // piece) * piece
    steps = -(-_pool_band_span(nm, ps, window) // per_item)
    dot_precision = _flash_precision(q.dtype)

    # The grid: the (slot, block of ``per_item`` pages) work items that
    # are LIVE, flattened into one dimension whose size is a runtime
    # value (each slot from its band's first page to its last live one;
    # ``item_slot`` / ``item_step`` say which item a grid index is), so
    # a block the band or the length rules out is never visited.
    item_slot, item_step, total = _pool_work_items(
        lens, ps, window, per_item, steps
    )

    def q_index_map(sh, it, lens_ref, table_ref, slot_ref, step_ref, n_ref):
        return (slot_ref[it], sh, 0, 0)

    def first_of(i, lens_ref, slot_ref, step_ref):
        # item i's slot, and the first logical page of its block
        s = slot_ref[i]
        first = _pool_first_page(lens_ref[s], ps, window)
        return s, first + step_ref[i] * per_item

    def scale_index_map(j):
        # An int8 pool's scale pages stay pipelined operands, one a
        # page of the block: Mosaic refuses to slice an HBM operand
        # whose lane dimension (the shard's heads) pads to 128. A page
        # past the length re-selects the last live one (a repeated
        # index is no DMA).
        def index_map(sh, it, lens_ref, table_ref, slot_ref, step_ref, n_ref):
            s, first = first_of(it, lens_ref, slot_ref, step_ref)
            live = jnp.minimum(first + j, lens_ref[s] // ps)
            return (table_ref[s, live], sh, 0, 0)

        return index_map

    scale_pages = per_item if quantized else 0

    def head_sums(x, heads):
        # Every lane ends up holding the sum over its own head's
        # head_dim lanes: one masked lane reduction a head of the
        # column (``heads``: each head's lanes). (A butterfly of lane
        # rotations, ``lane ^ stride``, gives the same sums 2.3-3.6
        # times slower on the v5e: PERF.md, PR 25.)
        out = jnp.zeros_like(x)
        for mine in heads:
            total = jnp.sum(jnp.where(mine, x, 0.0), axis=1, keepdims=True)
            out = jnp.where(mine, total, out)
        return out

    def kernel(lens_ref, table_ref, slot_ref, step_ref, n_ref, q_ref, *refs):
        hbm, refs = refs[:2], refs[2:]
        ks_refs = refs[:scale_pages]
        vs_refs = refs[scale_pages:2 * scale_pages]
        o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem, *scale_bufs = refs[
            2 * scale_pages:
        ]
        ks_buf, vs_buf = scale_bufs or (None, None)
        bufs = (k_buf, v_buf)
        sh, it = pl.program_id(0), pl.program_id(1)
        buf = lax.rem(it, 2)

        def item(i):
            # item i's slot, the first logical page of its block and how
            # many of the block's pages are live (through the length's)
            s, first = first_of(i, lens_ref, slot_ref, step_ref)
            return s, first, jnp.minimum(
                lens_ref[s] // ps - first + 1, per_item
            )

        def page_copies(s, first, j, into):
            # page j of a block: from wherever the slot's table says it
            # lies to its rows of buffer ``into``, K and V
            if "copies" not in halves:
                return []
            index = table_ref[s, first + j]
            rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
            return [
                pltpu.make_async_copy(
                    src.at[index, sh], dst.at[into, rows], sem.at[into, p]
                )
                for p, (src, dst) in enumerate(zip(hbm, bufs))
            ]

        def start(block, into):
            # One async copy a LIVE page and pool of an item's block
            # (:func:`item`); a page past the length starts none.
            s, first, live = block

            def page(j, carry):
                for copy in page_copies(s, first, j, into):
                    copy.start()
                return carry

            lax.fori_loop(0, live, page, 0)

        def wait(block, into):
            s, first, live = block

            @pl.when(live == per_item)
            def _whole():
                # the semaphore counts bytes: one wait the size of the
                # block is the wait for every page of a whole block
                for p, dst in enumerate(bufs if "copies" in halves else ()):
                    block = dst.at[into, pl.ds(0, keys)]
                    pltpu.make_async_copy(block, block, sem.at[into, p]).wait()

            @pl.when(live < per_item)
            def _pages():
                def page(j, carry):
                    for copy in page_copies(s, first, j, into):
                        copy.wait()
                    return carry

                lax.fori_loop(0, live, page, 0)

        mine = s, first, live = item(it)
        length = lens_ref[s]
        kb = step_ref[it]

        @pl.when(it == 0)
        def _prime():
            # Rows no copy ever lands on are masked, and 0 x garbage
            # must still be 0: the buffers start as zeros.
            for dst in bufs:
                dst[...] = jnp.zeros_like(dst)
            start(mine, buf)

        # Double-buffered across work items: the next item's pages (of
        # this slot, or the next one's first block) fly while this one's
        # are computed on.
        @pl.when(it + 1 < n_ref[0])
        def _prefetch():
            start(item(it + 1), 1 - buf)

        wait(mine, buf)

        @pl.when(kb == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def in_band(ki):
            live = ki <= length
            if window is not None:
                live = live & (ki > length - window)
            return live

        def aligned(start, size, to):
            # rows or lanes from a start that may be a runtime value
            if not isinstance(start, int):
                start = pl.multiple_of(start, to)
            return pl.ds(start, size)

        def lane_column(c, rows, band, heads):
            # one 128-lane column of the block's ``rows``: the parent's
            # masked lane reductions and per-lane softmax state
            col = aligned(c * _KV_LANES, _KV_LANES, _KV_LANES)
            kv = k_buf[buf, rows, col].astype(jnp.float32)  # [size, 128]
            vv = v_buf[buf, rows, col].astype(jnp.float32)
            if quantized:  # ``c`` is a Python int here
                ke = jnp.ones_like(kv)
                ve = jnp.ones_like(vv)
                first_head = c * heads_per_column
                for j, mine in enumerate(heads[: hs - first_head]):
                    one = pl.ds(first_head + j, 1)
                    ke = jnp.where(mine, ks_buf[rows, one], ke)
                    ve = jnp.where(mine, vs_buf[rows, one], ve)
                kv = kv * ke
                vv = vv * ve
            for g in range(group):
                row = pl.ds(g, 1)
                qv = q_ref[0, 0, row, col].astype(jnp.float32)  # [1, 128]
                sc = head_sums(qv * kv, heads) * scale
                sc = jnp.where(band, sc, _MASK_VALUE)
                m = m_ref[row, col]  # [1, 128]
                m_new = jnp.maximum(m, sc.max(axis=0, keepdims=True))
                p = jnp.exp(sc - m_new)
                corr = jnp.exp(m - m_new)
                m_ref[row, col] = m_new
                l_ref[row, col] = l_ref[row, col] * corr + p.sum(
                    axis=0, keepdims=True
                )
                acc_ref[row, col] = acc_ref[row, col] * corr + (
                    p * vv
                ).sum(axis=0, keepdims=True)

        def matmul_column(c, rows, band):
            col = aligned(c * _KV_LANES, _KV_LANES, _KV_LANES)
            qv = q_ref[0, 0, :, col]  # [group, 128]
            kv = k_buf[buf, rows, col]  # [size, 128]
            vv = v_buf[buf, rows, col]
            sc = lax.dot_general(
                qv, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            ) * scale
            sc = jnp.where(band, sc, _MASK_VALUE)
            m = m_ref[:, col][:, :1]  # [group, 1]
            m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            wide = (group, _KV_LANES)
            m_ref[:, col] = jnp.broadcast_to(m_new, wide)
            l_ref[:, col] = l_ref[:, col] * corr + jnp.broadcast_to(
                p.sum(axis=1, keepdims=True), wide
            )
            acc_ref[:, col] = acc_ref[:, col] * corr + lax.dot_general(
                p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )

        def attend(at, size):
            # ``size`` rows of the block from row ``at`` (a multiple of
            # the page size, static or not), every column of them; rows
            # past the length or behind the band are masked, whatever
            # lies in the buffer there
            if "arithmetic" not in halves:
                return
            rows = aligned(at, size, ps)
            shape = (group, size) if matmul else (size, _KV_LANES)
            ki = first * ps + at + lax.broadcasted_iota(
                jnp.int32, shape, 1 if matmul else 0
            )
            band = in_band(ki)
            if matmul:
                column = partial(matmul_column, rows=rows, band=band)
            else:
                lane = lax.broadcasted_iota(jnp.int32, shape, 1)
                heads = [(lane // d) == j for j in range(heads_per_column)]
                column = partial(
                    lane_column, rows=rows, band=band, heads=heads
                )
            if quantized:  # a column's heads and their scales: static
                for c in range(columns):
                    column(c)
                return

            def one(c, carry):
                column(c)
                return carry

            # ONE traced copy of a column, unrolled when it is lowered:
            # what a process pays to trace the kernel follows the
            # equations it holds (PERF.md, PR 28).
            lax.fori_loop(0, columns, one, 0, unroll=True)

        def walk(height, at, trips):
            # ``trips`` times ``height`` rows from row ``at``: one traced
            # copy of :func:`attend` whatever the trip count
            def trip(t, carry):
                attend(at + t * height, height)
                return carry

            lax.fori_loop(0, trips, trip, 0)

        if quantized:
            # the scale pages, operand by operand, into the block's rows
            for j in range(per_item):
                rows = pl.ds(j * ps, ps)
                ks_buf[rows, :] = ks_refs[j][0, 0]
                vs_buf[rows, :] = vs_refs[j][0, 0]

        if matmul:
            # A block whose every page is live is one matmul a column:
            # the MXU's weight loads and the softmax state once a
            # column, not once every 128 keys (0.53 -> 0.32 ms of
            # arithmetic a full layer of ``mellum2_8l``: PERF.md,
            # PR 27). A slot's last block goes a piece at a time.
            @pl.when(live == per_item)
            def _whole():
                attend(0, keys)

            @pl.when(live < per_item)
            def _partial():
                walk(piece, 0, -(-(live * ps) // piece))
        else:
            # The lane reductions cost what the rows they run over
            # cost: the whole pieces of the live pages, then the pages
            # left over one at a time, so an almost empty slot pays for
            # a page and not for 128 keys (the chat cell's calls are a
            # few short slots beside 45 empty ones).
            whole = live * ps // piece
            walk(piece, 0, whole)
            if piece > ps:
                walk(ps, whole * piece, live - whole * (piece // ps))

        @pl.when(kb == _pool_live_items(length, ps, window, per_item) - 1)
        def _finalize():
            o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    q_spec = pl.BlockSpec((1, 1, group, width), q_index_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(shards, total[0]),
        # The pools stay whole in HBM: the kernel fetches its own pages,
        # so a grid step's pipelined operands are q and the output
        # whatever a block holds.
        in_specs=[q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2 + [
            pl.BlockSpec((1, 1, ps, hs), scale_index_map(j))
            for j in range(scale_pages)
        ] * 2,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((group, width), jnp.float32)] * 3
        + [pltpu.VMEM((2, buffer_rows, width), k_pool.dtype)] * 2
        + [pltpu.SemaphoreType.DMA((2, 2))]
        + [pltpu.VMEM((keys, hs), jnp.float32)] * (2 if quantized else 0),
    )
    operands = [qs, k_pool, v_pool]
    if quantized:
        operands += [k_scale.astype(jnp.float32)] * scale_pages
        operands += [v_scale.astype(jnp.float32)] * scale_pages
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, shards, group, width), q.dtype),
        compiler_params=_mosaic_params(
            _pool_decode_vmem_estimate(
                buffer_rows, width, k_pool.dtype.itemsize
            )
        ),
        interpret=interpret,
    )(lens, table, item_slot, item_step, total, *operands)
    out = unfold_kv_rows(jnp.swapaxes(out, 1, 2), kv_heads, d)
    return out.swapaxes(1, 2).reshape(b, 1, h, d)


def sharded_pool_paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    mesh,
    data_axes=("data",),
    model_axis: Optional[str] = None,
    replicated: bool = False,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    **kernel_kwargs,
) -> jax.Array:
    """:func:`pool_paged_decode_attention` wrapped for the sharded
    decode path. Any slot may reference any page, so pages CANNOT
    shard over the data axes: the pools (and their scale arrays) shard
    over ``model_axis`` on the head-shard dimension only, while
    q/lengths/page_table shard over ``data_axes`` like batch rows
    (``parallel.rules.page_pool_rules``). Each device then runs the
    kernel over its slot shard against its head shard of every page,
    with ZERO collectives: decode attention is elementwise over both
    sharded dimensions. ``replicated=True`` is the engine's
    indivisible-geometry posture (the pool fell back to a replicated
    placement): every device runs the whole kernel on replicated
    operands, correct and redundant. An explicit shard_map because
    GSPMD cannot partition an opaque pallas custom call: it would
    gather the whole pool around it, precisely the bytes this kernel
    exists not to read."""
    from jax.sharding import PartitionSpec as P

    if replicated:
        q_spec = pool_spec = t_spec = l_spec = P()
    else:
        q_spec = P(tuple(data_axes), None, model_axis, None)
        pool_spec = P(None, model_axis, None, None)
        t_spec = P(tuple(data_axes), None)
        l_spec = P(tuple(data_axes))
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together.")
    if k_scale is None:

        def local(q_, k_, v_, t_, l_):
            return pool_paged_decode_attention(
                q_, k_, v_, t_, l_, **kernel_kwargs
            )

        fn = _shard_map_no_vma_check(
            local,
            mesh=mesh,
            in_specs=(q_spec, pool_spec, pool_spec, t_spec, l_spec),
            out_specs=q_spec,
        )
        return fn(q, k_pool, v_pool, page_table, lengths)

    def local_q(q_, k_, v_, t_, l_, ks_, vs_):
        return pool_paged_decode_attention(
            q_, k_, v_, t_, l_, k_scale=ks_, v_scale=vs_, **kernel_kwargs
        )

    fn = _shard_map_no_vma_check(
        local_q,
        mesh=mesh,
        in_specs=(
            q_spec, pool_spec, pool_spec, t_spec, l_spec,
            pool_spec, pool_spec,
        ),
        out_specs=q_spec,
    )
    return fn(q, k_pool, v_pool, page_table, lengths, k_scale, v_scale)


def _shard_map_no_vma_check(local, *, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes checker disabled."""
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _check_self_attention_shapes(q, k, v):
    """Identical q/k/v shapes are the supported contract for the SP
    kernels. Checked INSIDE the local programs (not just the shard_map
    wrappers — the locals are public API for users' own shard_maps):
    with causal=True and per-shard sk > sq, a non-first ring block can
    be fully masked while the running max still sits at the mask value,
    making p = exp(0) = 1 for masked entries and silently corrupting
    the l/acc accumulators — wrong output, no error."""
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            "Sequence-parallel attention requires q, k, v of identical "
            f"shape (self-attention); got q={q.shape}, k={k.shape}, "
            f"v={v.shape}."
        )


def _ring_rotate(k_blk, v_blk, axis_name, n):
    """One ring hop: device i sends its K/V block to i-1, so after t
    hops device r holds the block that originated on (r + t) % n. The
    final hop of a full ring returns the blocks home (and keeps the
    scan body uniform)."""
    perm = [(i, (i - 1) % n) for i in range(n)]
    return (
        lax.ppermute(k_blk, axis_name, perm),
        lax.ppermute(v_blk, axis_name, perm),
    )


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    overlap: bool = True,
) -> jax.Array:
    """The per-device ring program (call INSIDE shard_map/pjit with
    ``q/k/v`` already sequence-sharded: ``[batch, seq/n, heads, hd]``
    local shards, mesh axis ``axis_name`` of size n).

    ``overlap`` selects the DOUBLE-BUFFERED schedule (default): each
    scan step issues the next shard's ``ppermute``s FIRST, then runs
    the current block's attention on the held buffers — the rotation's
    only dependency is the held K/V, so the ICI transfer proceeds
    concurrently with the block compute (XLA's async
    collective-permute-start/done pair brackets the whole block
    program) instead of starting after it. Two K/V buffers are live per
    step (the held pair and the in-flight pair) — the double-buffer
    cost, +O(S/n) HBM. ``overlap=False`` keeps the sequential order
    (permute issued after the compute, the pre-overlap schedule): the
    dataflow is IDENTICAL either way — same ops on the same operands,
    only issue order changes — so outputs are bit-identical; the knob
    exists for A/B timing and as the measured-regression escape hatch.
    """
    _check_self_attention_shapes(q, k, v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]

    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # [b,h,sq,d]
    scale = jnp.float32(scale)

    def step(carry, _):
        k_blk, v_blk, t, m, l, acc = carry
        if overlap:
            # Prefetch: the next shard's rotation is in flight while
            # this block computes (see docstring).
            k_nxt, v_nxt = _ring_rotate(k_blk, v_blk, axis_name, n)
        s = jnp.einsum(
            "bhqd,bkhd->bhqk",
            qf,
            k_blk.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ) * scale
        if causal:
            # Global positions: this device's queries start at my*sq;
            # the held K/V block originated on device (my + t) % n.
            src = (my + t) % n
            qi = my * sq + lax.broadcasted_iota(
                jnp.int32, (sq, sk), 0
            )
            ki = src * sk + lax.broadcasted_iota(
                jnp.int32, (sq, sk), 1
            )
            s = jnp.where(ki <= qi, s, _MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        m = m_new  # Carry the updated running max forward.
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd",
            p,
            v_blk.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        if not overlap:
            k_nxt, v_nxt = _ring_rotate(k_blk, v_blk, axis_name, n)
        return (k_nxt, v_nxt, t + 1, m, l, acc), None

    # Initial carries DERIVED from qf (zero-cost arithmetic): under
    # shard_map's varying-manual-axes tracking, a scan's carry must
    # enter with the same device-varyingness its outputs have. The
    # outputs inherit qf's (varying over the ring axis AND any batch
    # axis of a dp x sp mesh); deriving the zeros from qf gives the
    # init identical provenance on every mesh shape, with no
    # version-specific pcast/pvary API.
    zeros_like_q = qf * jnp.float32(0.0)  # [b,h,sq,d]
    m0 = zeros_like_q[..., 0] + jnp.float32(_MASK_VALUE)
    l0 = zeros_like_q[..., 0]
    acc0 = zeros_like_q
    (_, _, _, m, l, acc), _ = lax.scan(
        step, (k, v, jnp.int32(0), m0, l0, acc0), None, length=n
    )
    out = acc / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def all_to_all_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    local_attention: str = "dense",
) -> jax.Array:
    """Ulysses-style sequence parallelism (the brief's OTHER named SP
    flavor): instead of streaming K/V around a ring, one
    ``lax.all_to_all`` re-shards from sequence-sharded
    ``[b, s/n, h, d]`` to HEAD-sharded ``[b, s, h/n, d]``, runs the
    local attention (each device owns whole heads, so causal masking
    needs no global-position bookkeeping), and a second all_to_all
    re-shards back. Four all_to_all collectives per call (q, k, v in;
    out back) vs the ring's 2n ppermutes (K and V per step) — cheaper
    at moderate sequence lengths. Requires ``heads % axis_size == 0``.

    ``local_attention`` picks the per-device compute: ``"dense"``
    materializes the full ``[s, s]`` scores per held head (fine at
    moderate s, the exact-oracle default), ``"flash"`` runs the Pallas
    flash kernel instead — O(block) VMEM at any length, which is what
    makes the Ulysses flavor long-context-capable (at s=16k the dense
    local scores alone are 8 GB and OOM; flash trains that length —
    ``git show 34de816:sweep_r07/flash_bwd_timing.py``).
    """
    if local_attention not in ("dense", "flash"):
        raise ValueError(
            f"local_attention={local_attention!r}: expected 'dense' or "
            "'flash'."
        )
    _check_self_attention_shapes(q, k, v)
    n = lax.psum(1, axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(
            f"heads={q.shape[2]} is not divisible by the '{axis_name}' "
            f"axis size {n}, which all-to-all (Ulysses) attention needs "
            "to give every device whole heads."
        )
    a2a = partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    local_fn = (
        flash_attention if local_attention == "flash" else attention_reference
    )
    out = local_fn(
        a2a(q, split_axis=2, concat_axis=1),
        a2a(k, split_axis=2, concat_axis=1),
        a2a(v, split_axis=2, concat_axis=1),
        causal=causal,
        scale=scale,
    )
    return a2a(out, split_axis=1, concat_axis=2)


def all_to_all_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    seq_axis: str,
    batch_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    local_attention: str = "dense",
) -> jax.Array:
    """One-call Ulysses attention — same contract as
    :func:`ring_attention` (global arrays, sequence sharded over
    ``seq_axis``, optional ``batch_axis``), different comm pattern.
    ``local_attention="flash"`` swaps the per-device dense compute for
    the Pallas flash kernel (long-context Ulysses; see
    :func:`all_to_all_attention_local`)."""
    local = partial(
        all_to_all_attention_local, local_attention=local_attention
    )
    return _sharded_attention_call(
        local, q, k, v,
        mesh=mesh, seq_axis=seq_axis, batch_axis=batch_axis,
        causal=causal, scale=scale,
        # Pallas interpret-mode lowering is not vma-annotated (same
        # workaround as ring_flash).
        check_vma=local_attention != "flash",
    )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    seq_axis: str,
    batch_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    overlap: bool = True,
) -> jax.Array:
    """One-call sequence-parallel attention: shards ``q/k/v``'s
    sequence dim over ``mesh``'s ``seq_axis`` and runs the ring.

    ``q/k/v`` are GLOBAL ``[batch, seq, heads, head_dim]`` arrays (or
    already-sharded global views); seq must divide by the axis size.
    ``batch_axis`` additionally shards the batch dim (the realistic
    dp x sp pod layout — attention is batch-elementwise, so each
    data-shard runs its own independent ring over ``seq_axis``).
    ``overlap`` selects the double-buffered comm-overlapped ring
    schedule (default; bit-identical values — see
    :func:`ring_attention_local`).
    """
    local = partial(ring_attention_local, overlap=overlap)
    return _sharded_attention_call(
        local, q, k, v,
        mesh=mesh, seq_axis=seq_axis, batch_axis=batch_axis,
        causal=causal, scale=scale,
    )


def _sharded_attention_call(
    local_fn, q, k, v, *, mesh, seq_axis, batch_axis, causal, scale,
    check_vma=True,
):
    from jax.sharding import PartitionSpec as P

    # Checked on GLOBAL shapes too, so the error fires at the call
    # boundary rather than inside the shard_map trace (the local
    # kernels re-check their per-shard views for direct callers).
    _check_self_attention_shapes(q, k, v)
    if q.shape[1] % mesh.shape[seq_axis] != 0:
        raise ValueError(
            f"Sequence length {q.shape[1]} does not divide the "
            f"'{seq_axis}' axis size {mesh.shape[seq_axis]}."
        )
    if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis] != 0:
        raise ValueError(
            f"Batch {q.shape[0]} does not divide the "
            f"'{batch_axis}' axis size {mesh.shape[batch_axis]}."
        )
    spec = P(batch_axis, seq_axis, None, None)
    local = partial(
        local_fn,
        axis_name=seq_axis,
        causal=causal,
        scale=scale,
    )
    if check_vma:
        fn = jax.shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )
    else:
        fn = _shard_map_no_vma_check(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )
    return fn(q, k, v)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-device flash attention as a Pallas TPU kernel — forward
    AND backward: exact attention with O(block) VMEM residency — only
    one (block_q, d) query tile and one (block_k, d) key/value tile
    live on-chip per grid step, so sequence length is HBM-bound, not
    VMEM-bound, and the [s, s] score matrix never exists. Measured
    verdict (``git show 34de816:sweep_r07/flash_bwd_timing.py``, v5e,
    b1 h8 d64 bf16 causal, perturbed-chain marginals): with the
    auto-scaled block sizes the TRAINING step (fwd+bwd) runs **2.5-5x
    faster than
    XLA's fused dense path** (0.61 vs 1.54 ms at s=2048, 1.09 vs 5.40
    at s=4096, 5.26 vs 21.6 at s=8192) and trains s=16384 in 11.6
    ms/step where the dense path OOMs outright. The round-6
    "parity, residency-only" verdict was an artifact of the old fixed
    128 blocks — at long sequence the grid-iteration overhead of tiny
    blocks dominated (22.7 ms at s=8192/blk128 vs 5.26 at blk1024).
    Same online-softmax recurrence as the ring — blocked over K inside
    the kernel instead of over devices — so the tiers compose: flash
    within a chip, ring/Ulysses across chips, for training as well as
    inference.

    ``block_q``/``block_k`` default to the largest aligned candidate
    (up to 1024) whose padding waste stays small AND whose backward
    working set fits the VMEM budget at this ``head_dim`` — see
    ``_default_flash_blocks``; the auto policy therefore never selects
    a block size whose backward fails Mosaic compilation on large head
    dims. Pass explicit sizes to override (they bypass both filters).

    The backward is the standard recompute scheme (`custom_vjp`): the
    forward saves only O and the per-row log-sum-exp; two blocked
    kernels recompute P = exp(S - lse) tile-by-tile — one accumulates
    dQ over k blocks, the other dK/dV over q blocks — so the backward
    holds the same O(block) residency guarantee as the forward
    (see ``_flash_backward``).

    Shapes ``[batch, seq, heads, head_dim]``; seq is padded internally
    to a common multiple of both block sizes (padded KEYS are masked
    out, padded query rows are dropped), accumulation in fp32, output
    in the input dtype. ``interpret=None`` auto-selects interpret mode
    off-TPU (the repo's Pallas convention).

    Forward only, for serving: ``window`` (with ``causal``) is the band
    ``i - window < p <= i`` of a sliding-window layer, and key blocks
    wholly outside it do no compute; ``k``/``v`` with fewer, grouped
    heads are indexed by ``query head // group`` and never repeated in
    memory. Either one makes the result non-differentiable: the
    backward raises (ROADMAP.md, Reach: the flash backward with a band).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _default_flash_blocks(
        q.shape[1], block_q, block_k,
        head_dim=q.shape[-1], itemsize=q.dtype.itemsize,
    )
    if window is not None or k.shape[2] != q.shape[2]:
        if window is not None and not causal:
            raise ValueError("window needs causal=True.")
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"{q.shape[2]} query heads are not a multiple of "
                f"{k.shape[2]} key/value heads."
            )
        return _flash_attention_forward_only(
            q, k, v, bool(causal), float(scale), int(block_q), int(block_k),
            bool(interpret), None if window is None else int(window),
        )
    return _flash_attention(
        q, k, v, bool(causal), float(scale), int(block_q), int(block_k),
        bool(interpret),
    )


# _FLASH_VMEM_BUDGET / _flash_bwd_vmem_estimate / _default_flash_blocks
# moved to ops/blocks.py (shared with the decode, residual, and §21 binary
# policies); imported at the top of this module so historical import
# sites (bench.py, the block-policy unit tests) keep working.


def _flash_dims(s, block_q, block_k):
    """Shared padding arithmetic for the forward and backward kernels:
    clamped block sizes and the padded length (a COMMON multiple of
    both block sizes — with unequal clamped blocks, rounding to
    max(bq, bk) alone leaves nq/nk floor-division dropping real
    rows/keys)."""
    import math

    # Clamp blocks for short sequences to the smallest 16-ALIGNED
    # length >= s (16 covers the bf16 sublane tile): clamping to raw s
    # would hand Mosaic a tile-unaligned block for awkward lengths
    # (e.g. s=999 -> block 999).
    cap = -(-max(8, s) // 16) * 16
    block_q = min(block_q, cap)
    block_k = min(block_k, cap)
    common = math.lcm(block_q, block_k)
    s_pad = -(-s // common) * common
    return block_q, block_k, s_pad


def _flash_precision(dtype):
    """f32 operands need HIGHEST for exact multiplies (default is bf16
    passes on the MXU); bf16 operands are exact at DEFAULT already —
    and Mosaic rejects an fp32 contract precision on bf16 vectors."""
    return (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )


def _to_bh(x, s_pad):
    """[b, s, h, d] -> [b*h, s_pad, d] (zero-padded sequence)."""
    b, s, h, d = x.shape
    x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)


def _from_bh(x, b, s, h, d):
    """Inverse of ``_to_bh`` (drops the padded rows)."""
    s_pad = x.shape[1]
    return x.reshape(b, h, s_pad, d).transpose(0, 2, 1, 3)[:, :s]


def _flash_forward(
    q, k, v, causal, scale, block_q, block_k, interpret, want_lse=False,
    window=None,
):
    """The forward kernel; returns ``out [b,s,h,d]``, or
    ``(out, lse [bh,s_pad,1])`` when ``want_lse`` — lse (the per-row
    log-sum-exp, m + log l) is the one residual the recompute backward
    needs beyond the primals, and pure-inference calls skip its HBM
    stream entirely (the flag is trace-time static).

    Layout: grid (batch*heads, q blocks, k blocks), the k dimension
    innermost (TPU grids iterate sequentially); the online-softmax
    carries (running max / sum / accumulator) live in VMEM scratch
    that persists across the k steps of one q block, initialized at
    k==0 and flushed to the output tile at the last k step. Causal
    skipping is a ``pl.when`` predicate (fully-masked k blocks do no
    compute, though their DMA still streams — see the index_map note).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    block_q, block_k, s_pad = _flash_dims(s, block_q, block_k)
    dot_precision = _flash_precision(q.dtype)
    qb, kb, vb = (_to_bh(x, s_pad) for x in (q, k, v))
    nq, nk = s_pad // block_q, s_pad // block_k

    def kv_index_map(i, j, kk):
        # grouped heads: query head i % h of batch i // h reads
        # key/value head (i % h) // group; nothing is repeated
        return ((i // h) * hkv + (i % h) // group, kk, 0)

    def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
        if want_lse:
            lse_ref, m_ref, l_ref, acc_ref = rest
        else:
            m_ref, l_ref, acc_ref = rest
        iq = pl.program_id(1)
        kb_idx = pl.program_id(2)

        @pl.when(kb_idx == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # Causal skip: a k block strictly above this q block's last row
        # is fully masked — no compute (the measured causal win).
        live = (
            kb_idx * block_k <= iq * block_q + block_q - 1
            if causal
            else True
        )
        if window is not None:
            # ...and one wholly behind the band of its first row.
            live = live & (
                kb_idx * block_k + block_k - 1 > iq * block_q - window
            )

        @pl.when(live)
        def _block():
            sc = jax.lax.dot_general(
                q_ref[0],
                k_ref[0],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            ) * jnp.float32(scale)
            ki = kb_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            valid = ki < s  # Padded keys never contribute.
            if causal:
                qi = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                valid = valid & (ki <= qi)
                if window is not None:
                    valid = valid & (qi - ki < window)
            sc = jnp.where(valid, sc, _MASK_VALUE)
            m = m_ref[...]
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            if window is not None:
                # a row whose band starts past this block has met no key
                # yet: its running max is still the mask value and
                # exp(0) would count the masked keys
                p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
            # p at v's dtype: f32 inputs stay exact; bf16 inputs round
            # p to bf16 (the standard flash trade, inside the bf16
            # tolerance class) and keep the native MXU path.
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype),
                v_ref[0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )

        @pl.when(kb_idx == nk - 1)
        def _finalize():
            # Padded query rows attended block 0's valid keys, so l > 0
            # everywhere (rows are sliced off by the wrapper anyway).
            # Under a band a padded row can lie past every key's window.
            l = l_ref[...]
            if window is not None:
                l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
            if want_lse:
                lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])

    # NOTE: causal fully-masked k blocks still stream from HBM (the
    # pl.when skips only their compute). A clamped kv index_map that
    # re-fetches the last live block (no-op DMA) was tried and measured
    # no better at s=4096 and only ~12% at s=16k (the dynamic index
    # costs Mosaic pipelining about what the skipped DMAs save); the
    # simple map stays.
    # Inside a shard_map trace (the ring_flash composition) the output
    # avals must declare how they vary over the manual mesh axes;
    # outside one the set is empty.
    aval_kw = {"vma": jax.typeof(qb).vma}
    out_shape = [
        jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype, **aval_kw)
    ]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0))]
    if want_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_pad, 1), jnp.float32, **aval_kw)
        )
        out_specs.append(
            pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0))
        )
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index_map),
            pl.BlockSpec((1, block_k, d), kv_index_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_mosaic_params(
            _flash_bwd_vmem_estimate(block_q, block_k, d, q.dtype.itemsize)
        ),
        interpret=interpret,
    )(qb, kb, vb)
    if want_lse:
        out, lse = res
        return _from_bh(out, b, s, h, d), lse
    return _from_bh(res[0], b, s, h, d)


def _flash_backward(
    q, k, v, out, lse, do, causal, scale, block_q, block_k, interpret,
    dlse=None,
):
    """Recompute-based flash backward: with S = scale*QK^T (masked),
    P = exp(S - lse), D_i = sum_d(dO ∘ O)_i, the gradients are

        dV = P^T dO
        dS = P ∘ (dO V^T - D)
        dQ = scale * dS K        dK = scale * dS^T Q

    When the caller also consumes the lse output (the ring_flash merge
    does), its cotangent folds in analytically: d lse_i/d S_ij = P_ij
    (the normalized row), so dS = P ∘ (dO V^T - (D - dlse)) — i.e. the
    same kernels run with D' = D - dlse, zero kernel changes.

    Two kernels share the recompute recurrence so each keeps the
    forward's O(block) VMEM residency: the dQ kernel walks k blocks
    innermost accumulating one (block_q, d) dQ tile in scratch; the
    dK/dV kernel walks q blocks innermost accumulating one (block_k, d)
    tile of each. D is precomputed outside (one fused elementwise
    reduce over d — XLA work, no kernel needed). Padded q rows carry
    dO = 0 so they contribute nothing; padded keys are masked to P = 0
    and their dK/dV rows are sliced off by the wrapper.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    block_q, block_k, s_pad = _flash_dims(s, block_q, block_k)
    dot_precision = _flash_precision(q.dtype)
    qb, kb, vb = (_to_bh(x, s_pad) for x in (q, k, v))
    dob = _to_bh(do.astype(q.dtype), s_pad)
    # D = rowsum(dO ∘ O): fp32, [bh, s_pad, 1]. Reduce over d FIRST in
    # the original layout (one fused elementwise+reduce), then pad/
    # transpose only the d=1 result — not two full [bh, s_pad, d] fp32
    # intermediates. Padded rows are 0.
    Db = _to_bh(
        jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32),
            axis=-1,
            keepdims=True,
        ),
        s_pad,
    )
    if dlse is not None:
        Db = Db - dlse.astype(jnp.float32)
    nq, nk = s_pad // block_q, s_pad // block_k

    def recompute_p(q_blk, k_blk, lse_blk, iq, ikb):
        """The shared tile recompute: P = exp(S - lse), masked."""
        sc = jax.lax.dot_general(
            q_blk,
            k_blk,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=dot_precision,
        ) * jnp.float32(scale)
        ki = ikb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = ki < s
        if causal:
            qi = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            valid = valid & (ki <= qi)
        p = jnp.exp(jnp.where(valid, sc, _MASK_VALUE) - lse_blk)
        # exp(_MASK - lse) underflows to 0 for any realistic lse, but a
        # hard zero is exact for the padded/causal-masked entries.
        return jnp.where(valid, p, 0.0)

    def dq_kernel(
        q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, dq_acc
    ):
        iq = pl.program_id(1)
        ikb = pl.program_id(2)

        @pl.when(ikb == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        live = (
            ikb * block_k <= iq * block_q + block_q - 1 if causal else True
        )

        @pl.when(live)
        def _block():
            p = recompute_p(q_ref[0], k_ref[0], lse_ref[0], iq, ikb)
            dp = jax.lax.dot_general(
                do_ref[0],
                v_ref[0],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )
            ds = p * (dp - d_ref[0])
            dq_acc[...] += jax.lax.dot_general(
                ds.astype(k_ref.dtype),
                k_ref[0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )

        @pl.when(ikb == nk - 1)
        def _finalize():
            dq_ref[0] = (dq_acc[...] * jnp.float32(scale)).astype(
                dq_ref.dtype
            )

    def dkv_kernel(
        k_ref, v_ref, q_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
        dk_acc, dv_acc,
    ):
        ikb = pl.program_id(1)
        iq = pl.program_id(2)

        @pl.when(iq == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        live = (
            ikb * block_k <= iq * block_q + block_q - 1 if causal else True
        )

        @pl.when(live)
        def _block():
            p = recompute_p(q_ref[0], k_ref[0], lse_ref[0], iq, ikb)
            # P^T dO and dS^T Q as contracting-dim-0 dots (no explicit
            # transpose — Mosaic keeps both operands in natural layout).
            dv_acc[...] += jax.lax.dot_general(
                p.astype(do_ref.dtype),
                do_ref[0],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )
            dp = jax.lax.dot_general(
                do_ref[0],
                v_ref[0],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )
            ds = p * (dp - d_ref[0])
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(q_ref.dtype),
                q_ref[0],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=dot_precision,
            )

        @pl.when(iq == nq - 1)
        def _finalize():
            dk_ref[0] = (dk_acc[...] * jnp.float32(scale)).astype(
                dk_ref.dtype
            )
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    q_spec = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0))
    k_spec_inner = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0))
    lse_spec = pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0))
    bwd_params = _mosaic_params(
        _flash_bwd_vmem_estimate(block_q, block_k, d, q.dtype.itemsize)
    )
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        grid=(b * h, nq, nk),
        in_specs=[q_spec, k_spec_inner, k_spec_inner, q_spec, lse_spec,
                  lse_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=bwd_params,
        interpret=interpret,
    )(qb, kb, vb, dob, lse, Db)

    # dK/dV: k blocks outer, q blocks inner (the accumulator must
    # persist across the innermost dimension).
    k_spec_outer = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0))
    q_spec_inner = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, kk, 0))
    lse_spec_inner = pl.BlockSpec(
        (1, block_q, 1), lambda i, j, kk: (i, kk, 0)
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_pad, d), v.dtype),
        ],
        grid=(b * h, nk, nq),
        in_specs=[k_spec_outer, k_spec_outer, q_spec_inner, q_spec_inner,
                  lse_spec_inner, lse_spec_inner],
        out_specs=[k_spec_outer, k_spec_outer],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=bwd_params,
        interpret=interpret,
    )(kb, vb, qb, dob, lse, Db)

    return (
        _from_bh(dq, b, s, h, d),
        _from_bh(dk, b, s, h, d),
        _from_bh(dv, b, s, h, d),
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret):
    # Primal (pure-inference) path: no lse output at all, and the
    # kernel under its own jit, so a forward of many layers traces and
    # lowers it once (24 lowerings a prefill program were 2 s of a
    # serving cell's set-up on the chip's host, PERF.md PR 26).
    return _flash_forward_call(
        q, k, v, causal, scale, block_q, block_k, interpret
    )


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret, want_lse=True
    )
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(
    causal, scale, block_q, block_k, interpret, residuals, do
):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, do, causal, scale, block_q, block_k, interpret
    )


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


#: The forward kernel under a jit of its own, for the serving forward: a
#: program that attends once a layer traces and lowers the kernel once
#: (as ``_pool_paged_decode_call`` does), and the device trace names the
#: kernel's op ``_flash_forward`` and not after the layer that called it.
_flash_forward_call = jax.jit(
    _flash_forward,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret", "want_lse",
        "window",
    ),
)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_forward_only(
    q, k, v, causal, scale, block_q, block_k, interpret, window
):
    """The serving forward with a band and/or grouped heads."""
    return _flash_forward_call(
        q, k, v, causal, scale, block_q, block_k, interpret, window=window
    )


def _flash_forward_only_fwd(
    q, k, v, causal, scale, block_q, block_k, interpret, window
):
    out = _flash_attention_forward_only(
        q, k, v, causal, scale, block_q, block_k, interpret, window
    )
    return out, None


def _flash_forward_only_bwd(*_):
    raise NotImplementedError(
        "flash_attention has no backward with a window or with grouped "
        "key/value heads yet: train such a layer with attention='dense' "
        "(ROADMAP.md, Reach)."
    )


_flash_attention_forward_only.defvjp(
    _flash_forward_only_fwd, _flash_forward_only_bwd
)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    """Flash forward returning ``(out, lse)`` with a VJP that accepts
    BOTH cotangents — the entry point for callers that consume lse (the
    ring_flash block merge)."""
    return _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret, want_lse=True
    )


def _flash_attention_lse_fwd(
    q, k, v, causal, scale, block_q, block_k, interpret
):
    out, lse = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret, want_lse=True
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_attention_lse_bwd(
    causal, scale, block_q, block_k, interpret, residuals, cts
):
    do, dlse = cts
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, do, causal, scale, block_q, block_k, interpret,
        dlse=dlse,
    )


_flash_attention_lse.defvjp(_flash_attention_lse_fwd, _flash_attention_lse_bwd)


def ring_flash_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    overlap: bool = True,
) -> jax.Array:
    """The composed tier — flash WITHIN the chip, ring ACROSS chips:
    the per-device ring program whose block compute is the Pallas flash
    kernel instead of a dense einsum, so per-device VMEM residency is
    O(block) in BOTH the local and the streamed dimension while the
    sequence is sharded over ``axis_name``. Exact full attention; fully
    differentiable (the flash kernels carry their ``custom_vjp``, the
    merge is plain jnp, and ``ppermute``'s backward is the inverse
    rotation). ``overlap`` selects the double-buffered schedule — the
    next shard's rotation is issued BEFORE the flash block compute so
    the ICI hop hides under the kernel (bit-identical values; see
    :func:`ring_attention_local` for the schedule contract).

    Each ring step computes ``(o_t, lse_t)`` for the held K/V block via
    the flash forward (which emits the per-row log-sum-exp) and folds it
    into the running output with the standard two-block softmax merge::

        lse' = logaddexp(lse, lse_t)
        o'   = o * exp(lse - lse') + o_t * exp(lse_t - lse')

    With equal shards the causal structure is block-triangular per ring
    step: the t=0 block is the diagonal (causal flash on local
    indices), a source shard strictly before this device's is fully
    live (non-causal flash), and one strictly after is fully masked
    (skipped — contributes ``lse_t = -inf``). ``lax.switch`` selects
    among the three statically-shaped branches at run time.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_self_attention_shapes(q, k, v)
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = float(scale)
    # Auto blocks scale with the PER-SHARD length (each flash call sees
    # one K/V shard).
    block_q, block_k = _default_flash_blocks(
        sq, block_q, block_k, head_dim=d, itemsize=q.dtype.itemsize,
    )

    def flash_block(k_blk, v_blk, blk_causal):
        o_t, lse_t = _flash_attention_lse(
            q, k_blk, v_blk, blk_causal, scale, block_q, block_k,
            interpret,
        )
        # lse [b*h, s_pad, 1] -> [b, sq, h, 1]: _from_bh with d=1.
        return o_t.astype(jnp.float32), _from_bh(lse_t, b, sq, h, 1)

    def merge(o, lse, o_t, lse_t):
        lse_new = jnp.logaddexp(lse, lse_t)
        return (
            o * jnp.exp(lse - lse_new) + o_t * jnp.exp(lse_t - lse_new),
            lse_new,
        )

    def step(carry, _):
        k_blk, v_blk, t, o, lse = carry
        if overlap:
            # Double-buffered schedule: the next shard is in flight on
            # the ICI ring while the flash kernel runs on the held one.
            k_nxt, v_nxt = _ring_rotate(k_blk, v_blk, axis_name, n)
        if causal:
            src = (my + t) % n

            def diag(_):
                return flash_block(k_blk, v_blk, True)

            def past(_):
                return flash_block(k_blk, v_blk, False)

            def future(_):
                return (
                    jnp.zeros((b, sq, h, d), jnp.float32),
                    jnp.full((b, sq, h, 1), _MASK_VALUE, jnp.float32),
                )

            idx = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
            o_t, lse_t = lax.switch(idx, [diag, past, future], None)
        else:
            o_t, lse_t = flash_block(k_blk, v_blk, False)
        o, lse = merge(o, lse, o_t, lse_t)
        if not overlap:
            k_nxt, v_nxt = _ring_rotate(k_blk, v_blk, axis_name, n)
        return (k_nxt, v_nxt, t + 1, o, lse), None

    # Carries derived from q for identical device-varying provenance on
    # every mesh shape (see ring_attention_local's init note).
    zeros = q.astype(jnp.float32) * jnp.float32(0.0)
    o0 = zeros
    lse0 = zeros[..., :1] + jnp.float32(_MASK_VALUE)
    (_, _, _, o, _), _ = lax.scan(
        step, (k, v, jnp.int32(0), o0, lse0), None, length=n
    )
    return o.astype(q.dtype)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    seq_axis: str,
    batch_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    overlap: bool = True,
) -> jax.Array:
    """One-call composed-tier attention — same contract as
    :func:`ring_attention` (global arrays, sequence sharded over
    ``seq_axis``, optional ``batch_axis``), with the Pallas flash
    kernel as each device's block compute: O(block) VMEM within the
    chip, O(S/n) HBM per chip across the ring. ``overlap`` selects the
    double-buffered comm-overlapped schedule (default; bit-identical
    values)."""
    local = partial(
        ring_flash_attention_local,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        overlap=overlap,
    )
    # check_vma off: Pallas' interpret-mode lowering builds internal
    # dynamic_slices whose index operands carry no varying-manual-axes
    # annotation, which the shard_map vma checker rejects (jax's own
    # error suggests exactly this workaround). Correctness is pinned
    # the stronger way — value/grad parity vs the dense oracle.
    return _sharded_attention_call(
        local, q, k, v,
        mesh=mesh, seq_axis=seq_axis, batch_axis=batch_axis,
        causal=causal, scale=scale, check_vma=False,
    )
