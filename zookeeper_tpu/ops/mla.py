"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434): the
two attention kernels of a layer whose cache is ONE latent row a token.

A token's cached row is ``[c, k_pe]``: ``c`` the ``kv_rank``-wide latent
after its norm and ``k_pe`` the ``rope_dim``-wide rotary key that every
head shares, stored in a page pool leaf ``[num_pages, 1, page_size,
width]`` (``width`` the row rounded up to whole 128-lane registers, the
rule of every pool row: ``ops.kv_row_width(1, row)``). Head ``h``'s key is
``[c W_uk_h, k_pe]`` and its value ``c W_uv_h``.

- Decode, the ABSORBED form (:func:`mla_paged_decode_attention`, a Pallas
  kernel, and :func:`mla_pool_decode_attention`, its ``jax.numpy``
  oracle): the query is absorbed into the latent, ``q_h = [q_nope_h
  W_uk_h^T, q_pe_h]`` (``row`` wide), so every head scores the latent rows
  themselves (``s = scale q_h . row``) and reads back their first
  ``kv_rank`` values (``o_lat_h = sum softmax(s) c``); the caller
  multiplies ``o_lat_h W_uv_h``. The kernel reads each page once for all
  heads: 16 query heads over one shared latent "head" are one matmul a
  block.
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
from jax import lax

from zookeeper_tpu.ops.attention import (
    _MASK_VALUE,
    _flash_precision,
    _pool_work_items,
)
from zookeeper_tpu.ops.blocks import _round_up, vmem_limit_bytes

#: Bytes of latent rows one work item of the decode kernel fetches into a
#: buffer: a work item costs a grid step and a wait whatever it holds, and
#: a slot holds 16,000 rows and more (1,600 rows of 640 lanes in two
#: buffers are 4 MB of VMEM).
_MLA_BLOCK_BYTES = 2 * 2**20


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
    (the oracle of :func:`mla_paged_decode_attention`, same contract):
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


def mla_decode_block_pages(page_size: int, width: int, itemsize: int, max_pages: int) -> int:
    """Pages one work item of :func:`mla_paged_decode_attention` fetches:
    as many as ``_MLA_BLOCK_BYTES`` hold, at least one, at most a slot's
    table."""
    return max(1, min(max_pages, _MLA_BLOCK_BYTES // (page_size * width * itemsize)))


def mla_paged_decode_attention(
    q, pool, page_table, lengths, *, scale: float, value_dim: int,
    interpret: Optional[bool] = None, block_pages: Optional[int] = None,
):
    """Pallas TPU decode attention in absorbed form over a latent page
    pool, the contract of :func:`mla_pool_decode_attention`.

    The pool stays whole in HBM and the kernel fetches its own pages, as
    ``ops.pool_paged_decode_attention`` does: the grid is the LIVE (slot,
    block of ``N`` consecutive logical pages) work items of the step
    (``N`` by :func:`mla_decode_block_pages`, or ``block_pages``), one async copy a live page
    from wherever the slot's table put it into a ``[2, N * page_size,
    width]`` VMEM block, double-buffered across work items. A block is
    then TWO matmuls for all heads: scores ``[heads, width] x [width,
    keys]`` over the whole row (the query's padding lanes are zeros) and
    ``[heads, keys] x [keys, value_dim]`` over the latent's values, with
    the online softmax a row of heads (float32). Rows past a slot's length
    are masked, whatever the block holds there."""
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
    if block_pages is None:
        block_pages = mla_decode_block_pages(
            pool.shape[2], pool.shape[3], pool.dtype.itemsize,
            page_table.shape[1],
        )
    return _mla_paged_decode_call(
        q, pool, page_table, lengths, scale=float(scale),
        value_dim=int(value_dim), interpret=bool(interpret),
        per_item=int(block_pages),
    )


@partial(
    jax.jit, static_argnames=("scale", "value_dim", "interpret", "per_item")
)
def _mla_paged_decode_call(
    q, pool, page_table, lengths, *, scale, value_dim, interpret, per_item
):
    """The kernel behind :func:`mla_paged_decode_attention`, jitted so a
    program traces and lowers it once, not once a layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, row = q.shape
    num_pages, _, ps, width = pool.shape
    nm = page_table.shape[1]
    qs = jnp.pad(q, ((0, 0), (0, 0), (0, width - row))).astype(pool.dtype)
    lens = jnp.clip(lengths.astype(jnp.int32), 0, nm * ps - 1)
    table = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    keys = per_item * ps
    steps = -(-nm // per_item)
    item_slot, item_step, total = _pool_work_items(
        lens, ps, None, per_item, steps
    )
    precision = _flash_precision(pool.dtype)
    lanes = 128

    def kernel(lens_ref, table_ref, slot_ref, step_ref, n_ref, q_ref,
               pool_hbm, o_ref, m_ref, l_ref, acc_ref, buf, sem):
        it = pl.program_id(0)
        into = lax.rem(it, 2)

        def item(i):
            # item i's slot, its block's first logical page, live pages
            s = slot_ref[i]
            first = step_ref[i] * per_item
            return s, first, jnp.minimum(lens_ref[s] // ps - first + 1, per_item)

        def copy(s, first, j, slot):
            return pltpu.make_async_copy(
                pool_hbm.at[table_ref[s, first + j], 0],
                buf.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)],
                sem.at[slot],
            )

        def each_page(block, slot, act):
            s, first, live = block

            def page(j, carry):
                act(copy(s, first, j, slot))
                return carry

            lax.fori_loop(0, live, page, 0)

        mine = s, first, live = item(it)

        @pl.when(it == 0)
        def _prime():
            # rows no copy lands on are masked, and 0 x garbage must stay 0
            buf[...] = jnp.zeros_like(buf)
            each_page(mine, into, lambda c: c.start())

        @pl.when(it + 1 < n_ref[0])
        def _prefetch():
            each_page(item(it + 1), 1 - into, lambda c: c.start())

        each_page(mine, into, lambda c: c.wait())

        @pl.when(step_ref[it] == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        kv = buf[into]  # [keys, width]
        sc = lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale  # [heads, keys]
        ki = first * ps + lax.broadcasted_iota(jnp.int32, (h, keys), 1)
        sc = jnp.where(ki <= lens_ref[s], sc, _MASK_VALUE)
        m = m_ref[:, :1]
        m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, (h, lanes))
        l_ref[...] = l_ref[...] * corr + jnp.broadcast_to(
            p.sum(axis=1, keepdims=True), (h, lanes)
        )
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p.astype(kv.dtype), kv[:, :value_dim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )

        @pl.when(step_ref[it] == lens_ref[s] // ps // per_item)
        def _finalize():
            o_ref[0] = acc_ref[...] / l_ref[:, :1]

    def by_slot(it, lens_ref, table_ref, slot_ref, step_ref, n_ref):
        return (slot_ref[it], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(total[0],),
        in_specs=[
            pl.BlockSpec((1, h, width), by_slot),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, value_dim), by_slot),
        scratch_shapes=[
            pltpu.VMEM((h, lanes), jnp.float32),
            pltpu.VMEM((h, lanes), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
            pltpu.VMEM((2, keys, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    estimate = (
        2 * keys * width * pool.dtype.itemsize  # the two blocks
        + 3 * h * keys * 4  # scores, p and their mask
        + 2 * h * width * 4 + h * value_dim * 4  # q, out, accumulator
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(estimate)
        ),
        interpret=interpret,
    )(lens, table, item_slot, item_step, total, qs, pool)


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
