"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434): the
two attention kernels of a layer whose cache is ONE latent row a token.

A token's cached row is ``[c, k_pe]``: ``c`` the ``kv_rank``-wide latent
after its norm and ``k_pe`` the ``rope_dim``-wide rotary key that every
head shares, stored in a page pool leaf ``[num_pages, 1, page_size,
width]`` (``width`` the row rounded up to whole 128-lane registers, the
rule of every pool row: ``ops.kv_row_width(1, row)``). Head ``h``'s key is
``[c W_uk_h, k_pe]`` and its value ``c W_uv_h``.

- Decode, the ABSORBED form (:func:`mla_cascade_decode_attention`, a
  Pallas kernel, and :func:`mla_pool_decode_attention`, its ``jax.numpy``
  oracle): the query is absorbed into the latent, ``q_h = [q_nope_h
  W_uk_h^T, q_pe_h]`` (``row`` wide), so every head scores the latent rows
  themselves (``s = scale q_h . row``) and reads back their first
  ``kv_rank`` values (``o_lat_h = sum softmax(s) c``); the caller
  multiplies ``o_lat_h W_uv_h``. The kernel reads each page once for all
  heads: 16 query heads over one shared latent "head" are one matmul a
  block. And it reads a page that many slots hold at the same logical
  position (a shared prefix) once for all of them, a cascade
  (:func:`mla_shared_runs`; docs/DESIGN.md §30).
- Prefill and extend, the EXPANDED form (:func:`mla_flash_attention`, a
  Pallas flash forward, and :func:`mla_attention_reference`): keys of
  ``nope + rope`` and values of ``v_dim`` a head, expanded from the latent
  rows by the caller, causal from an offset a sequence (a cold prefill
  starts at 0; an extend's window at the cached prefix's length).
  Expanded, because a window of ``w`` queries against ``n`` cached rows
  costs ``n r (nope + v)`` to expand plus ``w n (nope + rope + v)`` a
  head, where the absorbed form costs ``w n (row + kv_rank)`` a head:
  at a 16,384-row prefix the two meet near ``w`` = 200 and the expanded
  form wins beyond (docs/DESIGN.md §29).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from zookeeper_tpu.observability.peaks import (
    TPU_DATASHEET_BF16_TFLOPS,
    TPU_DATASHEET_HBM_GBPS,
)
from zookeeper_tpu.ops.attention import _MASK_VALUE, _flash_precision
from zookeeper_tpu.ops.blocks import _round_up, vmem_limit_bytes


def mla_gathered_rows(pool, page_table, row: int):
    """A slot-contiguous view ``[slots, max_pages * page_size, row]`` of a
    latent pool through the page table (unallocated entries clipped to a
    real page; every reader masks by the length)."""
    idx = jnp.clip(page_table, 0, pool.shape[0] - 1)
    g = pool[idx][:, :, 0, :, :row]  # [slots, max_pages, page_size, row]
    b, m, ps, _ = g.shape
    return g.reshape(b, m * ps, row)


def mla_pool_decode_attention(
    q, pool, page_table, lengths, *, scale: float, value_dim: int
):
    """The absorbed decode step over a latent page pool, in ``jax.numpy``
    (the oracle of :func:`mla_cascade_decode_attention`, same contract):
    ``q [slots, heads, row]`` the absorbed queries, ``pool [num_pages, 1,
    page_size, width]``, ``page_table [slots, max_pages]``, ``lengths
    [slots]`` the rows already cached (rows ``0..lengths`` are attended:
    the new token's row is written first). Returns ``o_lat [slots, heads,
    value_dim]`` float32: the softmax-weighted sum of the rows' first
    ``value_dim`` values."""
    rows = mla_gathered_rows(pool, page_table, q.shape[-1])
    s = jnp.einsum(
        "bhr,bkr->bhk", q, rows, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    ) * jnp.float32(scale)
    ki = lax.broadcasted_iota(jnp.int32, (rows.shape[1],), 0)
    s = jnp.where(ki[None, None, :] <= lengths[:, None, None], s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhk,bkc->bhc", p, rows[..., :value_dim].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )


#: Bytes of latent rows one work item of the decode kernel fetches into a
#: buffer: a work item costs a grid step and a wait whatever it holds, and
#: a slot holds 16,000 rows and more (1,600 rows of 640 lanes in two
#: buffers are 4 MB of VMEM).
_MLA_BLOCK_BYTES = 2 * 2**20

#: Query rows (slots x heads) a shared work item scores at a time, so that
#: its scores stay a few MB of VMEM (on a v5e at DeepSeek-V2-Lite's shapes,
#: 128, 256 and 512 rows took 0.833, 0.802 and 0.799 ms a layer).
_CASCADE_CHUNK_ROWS = 256

#: bf16 MXU operations a v5e does in the time its HBM moves one byte (197
#: TFLOP/s over 819 GB/s, the datasheet's): the exchange rate of the
#: cascade's break-even (:func:`mla_cascade_need`).
_OPS_PER_HBM_BYTE = (
    TPU_DATASHEET_BF16_TFLOPS["v5e"] * 1e12 / (TPU_DATASHEET_HBM_GBPS["v5e"] * 1e9)
)


def mla_head_rows(heads: int, itemsize: int) -> int:
    """Query rows a slot takes in the decode kernel: its heads rounded up
    to whole sublane tiles of the pool's dtype (8 rows of float32, 16 of
    bfloat16), so that a slot's rows start on a tile."""
    return _round_up(heads, 32 // itemsize)


def mla_cascade_need(
    slots: int, heads: int, width: int, value_dim: int, itemsize: int
) -> int:
    """Slots that must hold a page at the same logical position before the
    decode kernel reads it once for all of them: the cascade's break-even.

    A member that reads the page alone moves ``page_size x width x
    itemsize`` bytes at HBM's rate. A shared item moves them once and
    scores them against every slot's query rows, ``2 x slots x rows x
    page_size x (width + value_dim)`` operations at the MXU's (the most it
    costs: it skips a block of slots that holds no member). The shared
    read pays once ``need`` separate reads cost more: never fewer than 2;
    16 of 128 slots at DeepSeek-V2-Lite's widths (16 heads, rows of 640,
    values of 512, bfloat16)."""
    rows = slots * mla_head_rows(heads, itemsize)
    ratio = 2.0 * rows * (width + value_dim) / (width * itemsize * _OPS_PER_HBM_BYTE)
    return max(2, int(ratio) + 1)


def mla_shared_runs(page_table, lengths, *, page_size: int, need: int):
    """The cascade's grouping rule, in numpy or ``jax.numpy`` as its
    operands are (the decode kernel reads its pages by it, and the
    engine's ``decode_mla_rows`` counter counts by it). ``page_table
    [slots, max_pages]`` (-1: unallocated), ``lengths [slots]`` the rows
    already cached (a slot attends ``lengths + 1``).

    A slot HOLDS its logical page ``j`` when the page is allocated and
    whole: a partial last page is never shared (copy-on-write gives each
    writer its own). Slots that hold the same physical page as their
    logical page 0 form a group, led by the lowest of them; a member's
    MATCH is the run of leading pages it holds where its leader holds the
    same physical page. The group's RUN is the leading pages that at least
    ``need`` of its members match (:func:`mla_cascade_need`), and a member
    reads its first ``min(match, run)`` pages through the group's shared
    items, the rest alone.

    Returns ``(leader, shared, run)``, each ``[slots]`` int32: the slot
    whose table a slot's shared pages are read through, the leading pages
    it reads there (0: none, and ``leader`` then means nothing), and for a
    leader its group's run (0 for the others)."""
    xp = np if isinstance(page_table, np.ndarray) else jnp
    slots, max_pages = page_table.shape
    whole = (xp.clip(lengths, 0, max_pages * page_size - 1) + 1) // page_size
    j = xp.arange(max_pages)
    held = (page_table >= 0) & (j[None, :] < whole[:, None])
    first = xp.where(held[:, 0], page_table[:, 0], -1)
    same = (first[:, None] == first[None, :]) & (first >= 0)[:, None]
    leader = xp.argmax(same, axis=1)
    matched = held & held[leader] & (page_table == page_table[leader])
    match = xp.cumprod(matched.astype(xp.int32), axis=1).sum(axis=1)
    # members[g, j]: the slots of the group led by g that match page j
    groups = (leader[None, :] == xp.arange(slots)[:, None]).astype(xp.float32)
    members = groups @ (match[:, None] > j[None, :]).astype(xp.float32)
    run = (members >= need).sum(axis=1)
    shared = xp.minimum(match, run[leader])
    return leader.astype(xp.int32), shared.astype(xp.int32), run.astype(xp.int32)


def _cascade_work_items(lens, leader, shared, run, page_size, per_item, steps):
    """The cascade kernel's grid, on the device: first each group's SHARED
    items (its leader, a block of ``per_item`` pages of its run), then
    each slot's OWN items (a block of its pages from its first unshared
    one through the new token's). ``(item_slot, item_first, item_live,
    item_shared, total)``: whose table an item reads, its first logical
    page, the pages it fetches, its kind, and how many items there are
    (``steps``: the most blocks a slot's table holds, which sizes the
    arrays: at most half the slots lead a run)."""
    slots = lens.shape[0]
    last = lens // page_size
    counts = jnp.concatenate(
        [-(-run // per_item), (last - shared) // per_item + 1]
    )
    ends = jnp.cumsum(counts)
    item = jnp.arange((slots + slots // 2) * steps, dtype=jnp.int32)
    owner = jnp.minimum(jnp.searchsorted(ends, item, side="right"), 2 * slots - 1)
    is_shared = owner < slots
    slot = jnp.where(is_shared, owner, owner - slots).astype(jnp.int32)
    first = jnp.where(is_shared, 0, shared[slot]) + (
        item - (ends - counts)[owner]
    ) * per_item
    live = jnp.minimum(
        jnp.where(is_shared, run[slot], last[slot] + 1) - first, per_item
    )
    return (
        slot, first.astype(jnp.int32), live.astype(jnp.int32),
        is_shared.astype(jnp.int32), ends[-1:].astype(jnp.int32),
    )


def _cascade_chunk_slots(slots: int, head_rows: int) -> int:
    """Slots a shared item scores at a time: the most that divides
    ``slots`` and keeps their rows within ``_CASCADE_CHUNK_ROWS``."""
    most = max(1, _CASCADE_CHUNK_ROWS // head_rows)
    return max(d for d in range(1, min(slots, most) + 1) if slots % d == 0)


def mla_cascade_decode_attention(
    q, pool, page_table, lengths, *, scale: float, value_dim: int,
    interpret: Optional[bool] = None, block_pages: Optional[int] = None,
):
    """Pallas TPU decode attention in absorbed form over a latent page
    pool, the contract of :func:`mla_pool_decode_attention`, reading a
    shared prefix once a step for all the slots that hold it.

    The pool stays whole in HBM and the kernel fetches its own pages, one
    async copy a page into a ``[2, N * page_size, width]`` VMEM block,
    double-buffered across work items (``N`` pages: as many as
    ``_MLA_BLOCK_BYTES`` hold, or ``block_pages``). The grid is the step's
    work items (:func:`mla_shared_runs` groups the slots):

    - a SHARED item is a block of a group's run, fetched once and scored
      against the query rows of every slot, ``_CASCADE_CHUNK_ROWS`` at a
      time (a block of slots with no member is skipped), a row's keys
      masked to its slot's shared pages (none for a slot off the group);
    - an OWN item is a block of one slot's pages past its shared ones,
      scored against its heads' rows, masked to its length.

    Each is two matmuls, scores ``[rows, width] x [width, keys]`` over the
    whole row (the query's padding lanes are zeros) and ``[rows, keys] x
    [keys, value_dim]`` over the latent's values, with the online softmax
    a row (float32) into a running max, sum and latent average of every
    (slot, head) that stay in VMEM for the whole grid: the parts of a row
    merge exactly, whatever their order, and the average is divided by the
    sum once, after the last item. With no run a group shares past the
    break-even (:func:`mla_cascade_need`), the grid is the slots' own
    items alone: every slot reads every page of its own."""
    b, h, row = q.shape
    if pool.ndim != 4 or pool.shape[1] != 1 or pool.shape[3] < row:
        raise ValueError(
            f"latent pool {pool.shape} cannot hold rows of {row} values."
        )
    if page_table.shape[0] != b or value_dim > row:
        raise ValueError(
            f"page_table {page_table.shape} / value_dim {value_dim} do not "
            f"match q {q.shape}."
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, _, ps, width = pool.shape
    itemsize = pool.dtype.itemsize
    if block_pages is None:
        block_pages = max(
            1, min(page_table.shape[1], _MLA_BLOCK_BYTES // (ps * width * itemsize))
        )
    return _mla_cascade_decode_call(
        q, pool, page_table, lengths, scale=float(scale),
        value_dim=int(value_dim), interpret=bool(interpret),
        per_item=int(block_pages),
        need=mla_cascade_need(b, h, width, value_dim, itemsize),
    )


@partial(
    jax.jit,
    static_argnames=("scale", "value_dim", "interpret", "per_item", "need"),
)
def _mla_cascade_decode_call(
    q, pool, page_table, lengths, *, scale, value_dim, interpret, per_item,
    need,
):
    """The kernel behind :func:`mla_cascade_decode_attention`, jitted so a
    program traces and lowers it once, not once a layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, row = q.shape
    num_pages, _, ps, width = pool.shape
    nm = page_table.shape[1]
    hp = mla_head_rows(h, pool.dtype.itemsize)
    rows = b * hp
    qs = jnp.pad(q, ((0, 0), (0, hp - h), (0, width - row)))
    qs = qs.astype(pool.dtype).reshape(rows, width)
    lens = jnp.clip(lengths.astype(jnp.int32), 0, nm * ps - 1)
    leader, shared, run = mla_shared_runs(
        page_table.astype(jnp.int32), lens, page_size=ps, need=need
    )
    table = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    keys = per_item * ps
    steps = -(-nm // per_item)
    items = _cascade_work_items(lens, leader, shared, run, ps, per_item, steps)
    spc = _cascade_chunk_slots(b, hp)
    chunk, chunks = spc * hp, b // spc
    # a row's slot's group and shared rows; and, a leader by a block of
    # slots, whether the block holds a member of its group
    member = (leader[None, :] == jnp.arange(b)[:, None]) & (shared > 0)[None, :]
    busy = member.reshape(b, chunks, spc).any(axis=2).astype(jnp.int32).reshape(-1)
    row_leader = jnp.repeat(leader, hp)[:, None]
    row_limit = jnp.repeat(shared * ps, hp)[:, None]
    precision = _flash_precision(pool.dtype)
    lanes = 128

    def kernel(lens_ref, table_ref, busy_ref, slot_ref, first_ref, live_ref,
               kind_ref, n_ref, q_ref, leader_ref, limit_ref, pool_hbm,
               o_ref, m_ref, l_ref, buf, sem):
        it = pl.program_id(0)
        into = lax.rem(it, 2)
        s, first = slot_ref[it], first_ref[it]

        def each_page(i, slot, act):
            # item i's live pages, from wherever its table put them
            def page(j, carry):
                act(pltpu.make_async_copy(
                    pool_hbm.at[table_ref[slot_ref[i], first_ref[i] + j], 0],
                    buf.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)],
                    sem.at[slot],
                ))
                return carry

            lax.fori_loop(0, live_ref[i], page, 0)

        @pl.when(it == 0)
        def _prime():
            # rows no copy lands on are masked, and 0 x garbage must stay 0
            buf[...] = jnp.zeros_like(buf)
            m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            o_ref[...] = jnp.zeros_like(o_ref)
            each_page(it, into, lambda c: c.start())

        @pl.when(it + 1 < n_ref[0])
        def _prefetch():
            each_page(it + 1, 1 - into, lambda c: c.start())

        each_page(it, into, lambda c: c.wait())

        def attend(r0, n, limit):
            # rows r0 .. r0 + n against the block's keys below ``limit``;
            # a row whose keys are all masked keeps its state, or (before
            # its first key) gathers a sum its first key's rescale zeroes
            kv = buf[into]  # [keys, width]
            sc = lax.dot_general(
                q_ref[pl.ds(r0, n), :], kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            ) * scale  # [n, keys]
            ki = first * ps + lax.broadcasted_iota(jnp.int32, (n, keys), 1)
            sc = jnp.where(ki < limit, sc, _MASK_VALUE)
            m = m_ref[pl.ds(r0, n), :1]
            m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            m_ref[pl.ds(r0, n), :] = jnp.broadcast_to(m_new, (n, lanes))
            l_ref[pl.ds(r0, n), :] = l_ref[pl.ds(r0, n), :] * corr + (
                jnp.broadcast_to(p.sum(axis=1, keepdims=True), (n, lanes))
            )
            o_ref[pl.ds(r0, n), :] = o_ref[pl.ds(r0, n), :] * corr + (
                lax.dot_general(
                    p.astype(kv.dtype), kv[:, :value_dim],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision,
                )
            )

        @pl.when(kind_ref[it] == 0)
        def _own():
            attend(pl.multiple_of(s * hp, hp), hp, lens_ref[s] + 1)

        @pl.when(kind_ref[it] == 1)
        def _shared():
            def block(c, carry):
                @pl.when(busy_ref[s * chunks + c] > 0)
                def _attend():
                    # a row's keys: its slot's shared rows if the item's
                    # group is its own, else none
                    r0 = pl.multiple_of(c * chunk, chunk)
                    limit = jnp.where(
                        leader_ref[pl.ds(r0, chunk), :] == s,
                        limit_ref[pl.ds(r0, chunk), :], 0,
                    )
                    attend(r0, chunk, limit)

                return carry

            lax.fori_loop(0, chunks, block, 0)

        @pl.when(it == n_ref[0] - 1)
        def _finalize():
            o_ref[...] = o_ref[...] / l_ref[:, :1]

    vmem = pltpu.MemorySpace.VMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(items[-1][0],),
        in_specs=[pl.BlockSpec(memory_space=vmem)] * 3
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=vmem),
        scratch_shapes=[
            pltpu.VMEM((rows, lanes), jnp.float32),
            pltpu.VMEM((rows, lanes), jnp.float32),
            pltpu.VMEM((2, keys, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    estimate = (
        rows * width * pool.dtype.itemsize  # the queries
        + rows * value_dim * 4 + 4 * rows * lanes * 4  # average, max, sum,
        # and a row's group and limit
        + 2 * keys * width * pool.dtype.itemsize  # the two blocks
        + 4 * chunk * keys * 4  # a chunk's scores, p and their mask
        + 2 * chunk * value_dim * 4  # its product and rescaled average
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(estimate)
        ),
        interpret=interpret,
    )(lens, table, busy, *items, qs, row_leader, row_limit, pool)
    return out.reshape(b, hp, value_dim)[:, :h]


def mla_attention_reference(q, k, v, offsets, *, scale: float):
    """Causal attention from an offset, plain ``jax.numpy`` (the oracle of
    :func:`mla_flash_attention`, same contract)."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    ) * jnp.float32(scale)
    qi = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    ki = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    keep = ki[None, None] <= qi[None, None] + offsets[:, None, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, _MASK_VALUE), axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(q.dtype)


def mla_flash_attention(
    q, k, v, offsets, *, scale: float, block_q: int = 256,
    block_k: int = 512, interpret: Optional[bool] = None,
):
    """Causal attention from an offset as a Pallas flash forward (the
    expanded form of a latent layer's prefill and extend): ``q [b, w,
    heads, dqk]``, ``k [b, n, heads, dqk]``, ``v [b, n, heads, dv]``
    (``dv`` may differ from ``dqk``: 192 against 128 in DeepSeek-V2),
    ``offsets [b]``: query ``j`` of sequence ``i`` sits at position
    ``offsets[i] + j`` and attends keys ``0 .. offsets[i] + j`` (none at
    or past ``n``). Returns ``[b, w, heads, dv]`` in ``q``'s dtype.

    Grid (sequence x head, query block, key block), the key blocks
    innermost; a key block wholly past a query block's last position
    does no arithmetic (its copy still streams, as in
    ``ops.flash_attention``). float32 online softmax; the probabilities
    are cast to ``v``'s dtype for the second product. Forward only."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, w, h, dqk = q.shape
    n, dv = k.shape[1], v.shape[-1]
    if k.shape != (b, n, h, dqk) or v.shape[:3] != (b, n, h):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape} do not match.")
    block_q = min(block_q, _round_up(w, 16))
    block_k = min(block_k, _round_up(n, 16))
    return _mla_extend_call(
        q, k, v, offsets.astype(jnp.int32), scale=float(scale),
        block_q=int(block_q), block_k=int(block_k), interpret=bool(interpret),
    )


@partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "interpret"),
)
def _mla_extend_call(q, k, v, offsets, *, scale, block_q, block_k, interpret):
    """The kernel behind :func:`mla_flash_attention`, jitted so a program
    traces and lowers it once, not once a layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, w, h, dqk = q.shape
    n, dv = k.shape[1], v.shape[-1]
    w_pad, n_pad = _round_up(w, block_q), _round_up(n, block_k)
    nq, nk = w_pad // block_q, n_pad // block_k
    precision = _flash_precision(q.dtype)

    def heads_first(x, length):
        x = jnp.pad(x, ((0, 0), (0, length - x.shape[1]), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(b * h, length, x.shape[-1])

    qb, kb, vb = heads_first(q, w_pad), heads_first(k, n_pad), heads_first(v, n_pad)

    def kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        i, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        off = off_ref[i // h]

        @pl.when(ik == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(ik * block_k <= off + iq * block_q + block_q - 1)
        def _block():
            sc = lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            ) * jnp.float32(scale)
            qi = off + iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            ki = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            sc = jnp.where((ki <= qi) & (ki < n), sc, _MASK_VALUE)
            m = m_ref[...]
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )

        @pl.when(ik == nk - 1)
        def _finalize():
            o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, w_pad, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, dqk), lambda i, j, kk, o: (i, j, 0)),
                pl.BlockSpec((1, block_k, dqk), lambda i, j, kk, o: (i, kk, 0)),
                pl.BlockSpec((1, block_k, dv), lambda i, j, kk, o: (i, kk, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, dv), lambda i, j, kk, o: (i, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(
                4 * block_q * block_k * 4
                + 2 * (block_q + 2 * block_k) * max(dqk, dv) * q.dtype.itemsize
                + 2 * block_q * dv * 4
            )
        ),
        interpret=interpret,
    )(offsets, qb, kb, vb)
    return out.reshape(b, h, w_pad, dv).transpose(0, 2, 1, 3)[:, :w]
