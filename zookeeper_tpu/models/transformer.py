"""Causal transformer language model — the long-context model family.

Beyond the reference's CNN contract (SURVEY.md §2.3 scopes the zoo to
image classifiers), but the brief makes long-context first-class and the
attention tiers (``ops.flash_attention`` / ``ring_attention`` /
``ring_flash_attention``) need a MODEL surface, not just bare ops: this
is the family that exercises them through the same ``Model`` /
``configure`` / ``TrainingExperiment`` machinery as the CNN zoo.

Design (TPU-first, standard pre-norm decoder):

- pre-RMSNorm blocks, GELU MLP, learned positional embedding, weight-
  tied LM head (embed.T) — the shapes XLA tiles well on the MXU
  (d_model/heads chosen so head_dim lands on 64/128 lanes);
- attention runs the Pallas flash kernel by default (``attention=
  "flash"``): O(block) VMEM at any sequence length, measured 2.5-5x
  faster fwd+bwd than the dense path and trains s=16k where dense OOMs
  (BASELINE.md round-7); ``"dense"`` keeps the reference oracle path;
- the module is pure (no mesh assumptions): data parallelism comes from
  the Partitioner sharding the batch; SEQUENCE parallelism composes at
  the ops layer (``ring_flash_attention`` inside a shard_map over a
  mesh with the sequence axis — see ``ops/attention.py``);
- the existing jittable train step works unchanged: ``softmax_cross_
  entropy`` and ``accuracy`` broadcast over the position dimension
  (logits ``[b, s, vocab]``, targets ``[b, s]``), so an LM batch is
  ``{"input": tokens, "target": next_tokens}`` and ``make_train_step``
  / ``TrainingExperiment`` need no LM-specific fork.
"""

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from zookeeper_tpu.core import Field, component
from zookeeper_tpu.models.base import Model
from zookeeper_tpu.ops import (
    attention_reference,
    cached_attention,
    flash_attention,
    paged_decode_attention,
)
from zookeeper_tpu.parallel.sharding import constrain_batch_sharded


def _resolve_attention(attention):
    """``"flash"`` / ``"dense"`` / any ``callable(q, k, v, *, causal)``
    — the callable form is how sequence parallelism plugs in (e.g.
    ``partial(ring_flash_attention, mesh=mesh, seq_axis="sp",
    batch_axis="data")`` shards the attention over a mesh while the
    rest of the model runs an ordinary pjit program). Checked at the
    MODULE level too (it is public API): a typo'd tier must not
    silently fall back to dense — at s=16k that materializes the
    [s, s] scores and OOMs."""
    if callable(attention):
        return attention
    if attention == "flash":
        return flash_attention
    if attention == "dense":
        return attention_reference
    raise ValueError(
        f"attention={attention!r}: expected 'flash', 'dense', or an "
        "attention callable."
    )


def _resolve_paged_attention(paged_attention):
    """``"reference"`` / ``"pallas"`` / any ``callable(q, k_pool,
    v_pool, page_table, lengths, *, k_scale=None, v_scale=None)`` — the
    page-pool analogue of :func:`_resolve_decode_attention`
    (docs/DESIGN.md §20). ``"reference"`` is the
    :func:`~zookeeper_tpu.ops.pool_decode_attention` gather+einsum
    oracle; ``"pallas"`` the page-table scalar-prefetch kernel; the
    callable form is how the decode engine injects the mesh-composed
    sharded wrapper."""
    from zookeeper_tpu.ops import (
        pool_decode_attention,
        pool_paged_decode_attention,
    )

    if callable(paged_attention):
        return paged_attention
    if paged_attention == "reference":
        return pool_decode_attention
    if paged_attention == "pallas":
        return pool_paged_decode_attention
    raise ValueError(
        f"paged attention={paged_attention!r}: expected 'reference', "
        "'pallas', or a callable(q, k_pool, v_pool, page_table, "
        "lengths)."
    )


def _pool_write_rows(layer, rows, pages, offsets):
    """Write ``rows [b(, w), heads, head_dim]`` into a page-pool layer
    dict at ``(pages, offsets)`` (same leading shape; entries with
    ``page == num_pages`` drop — the OOB sentinel covering inactive
    slots, unallocated table entries, and padding rows). The rows are
    folded to the pool's stored form first (``ops.fold_kv_rows``: heads
    end to end, padded to whole 128-lane registers), so the scatter's
    window is the stored row and the donated pool is updated in place
    in the row-major layout it is held, gathered and read by the decode
    kernel in. (A pool of ``[..., heads, head_dim]`` rows is not: the
    TPU holds it transposed, and this scatter, like every other reader,
    then re-lays-out the whole pool — docs/DESIGN.md §20.) Quantizes
    inline when the layer carries scale arrays (int8 pools — see
    ``ops.quantizers.quantize_kv_rows``); the scales go through the
    same write. Returns the updated layer dict."""
    from zookeeper_tpu.ops import fold_kv_rows, quantize_kv_rows

    def write(buf, vals):
        return buf.at[pages, :, offsets].set(
            vals.astype(buf.dtype), mode="drop"
        )

    out = dict(layer)
    for name, scale_name in (("k", "k_scale"), ("v", "v_scale")):
        buf = layer[name]
        shards, width = buf.shape[1], buf.shape[3]
        vals = rows[name]
        if scale_name in layer:
            vals, s = quantize_kv_rows(vals)
            out[scale_name] = write(
                layer[scale_name], s.reshape(*s.shape[:-1], shards, -1)
            )
        out[name] = write(buf, fold_kv_rows(vals, shards, width))
    return out


def _pool_scales(layer):
    return layer.get("k_scale"), layer.get("v_scale")


def _resolve_decode_attention(decode_attention):
    """``"reference"`` / ``"pallas"`` / any ``callable(q, k_cache,
    v_cache, lengths)`` — the decode-path analogue of
    :func:`_resolve_attention`. ``"reference"`` is the
    :func:`cached_attention` oracle einsum; ``"pallas"`` the
    length-aware paged decode kernel (auto interpret off-TPU); the
    callable form is how the decode engine injects the mesh-composed
    ``sharded_paged_decode_attention`` (or any future flavor) without
    rebuilding the module — see ``DecodeEngine.decode_attention``."""
    if callable(decode_attention):
        return decode_attention
    if decode_attention == "reference":
        return cached_attention
    if decode_attention == "pallas":
        return paged_decode_attention
    raise ValueError(
        f"decode_attention={decode_attention!r}: expected 'reference', "
        "'pallas', or a callable(q, k_cache, v_cache, lengths)."
    )


class RMSNorm(nn.Module):
    """Root-mean-square layernorm (no mean subtraction, no bias): the
    cheaper norm that long-context transformer stacks standardized on;
    fp32 statistics regardless of compute dtype."""

    dtype: Any = jnp.float32
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (y * scale).astype(self.dtype)


class _Block(nn.Module):
    """One pre-norm decoder block.

    ``setup()``-structured (not ``nn.compact``) so the SAME weights
    serve two traced programs: the full-context ``__call__`` (training
    / prefill) and the single-position :meth:`decode` (cached
    attention over a KV buffer). Submodule names are pinned to the
    names the original compact implementation auto-assigned
    (``RMSNorm_0``/``RMSNorm_1``/``qkv``/``proj``/``up``/``down``) so
    every existing checkpoint and partition rule keeps matching.
    """

    d_model: int
    num_heads: int
    mlp_ratio: int
    attention: Any
    dtype: Any
    pin_activations: bool = True
    #: Decode-path attention flavor: "reference" (the cached_attention
    #: oracle), "pallas" (the paged decode kernel), or a callable. A
    #: per-call ``attention_override`` (the engine seam) wins.
    decode_attention: Any = "reference"

    def setup(self):
        d = self.d_model
        self.ln1 = RMSNorm(dtype=self.dtype, name="RMSNorm_0")
        self.wqkv = nn.Dense(
            3 * d, use_bias=False, dtype=self.dtype, name="qkv"
        )
        self.wproj = nn.Dense(d, use_bias=False, dtype=self.dtype, name="proj")
        self.ln2 = RMSNorm(dtype=self.dtype, name="RMSNorm_1")
        self.wup = nn.Dense(
            self.mlp_ratio * d, use_bias=False, dtype=self.dtype, name="up"
        )
        self.wdown = nn.Dense(d, use_bias=False, dtype=self.dtype, name="down")

    def _mlp(self, x):
        h = self.ln2(x)
        h = self.wup(h)
        h = nn.gelu(h)
        h = self.wdown(h)
        # Pin the residual stream to the canonical layout (batch on the
        # data axes) at every block boundary: without the pin, GSPMD
        # was observed picking an FSDP-axis-spread layout for the
        # attention intermediates it then could not reshard — the same
        # involuntary-full-remat pathology the CNN Quant layers pin
        # against (parallel/sharding.py). No-op outside a mesh scope;
        # see ``_auto_pin_activations`` for when the pin is skipped.
        out = x + h
        if self.pin_activations:
            out = constrain_batch_sharded(out)
        return out

    def __call__(self, x, training: bool, return_kv: bool = False):
        b, s, d = x.shape
        head_dim = d // self.num_heads

        h = self.ln1(x)
        qkv = self.wqkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(b, s, self.num_heads, head_dim)
        kh, vh = to_heads(k), to_heads(v)
        attn = _resolve_attention(self.attention)
        o = attn(to_heads(q), kh, vh, causal=True)
        x = x + self.wproj(o.reshape(b, s, d))
        out = self._mlp(x)
        if return_kv:
            return out, (kh, vh)
        return out

    def decode(self, x, k_cache, v_cache, lengths, attention_override=None):
        """One cached-attention step: ``x [b, 1, d]`` is the new token's
        residual stream, ``k_cache/v_cache [b, capacity, heads,
        head_dim]`` the slot KV buffers, ``lengths [b]`` the tokens
        already cached. Writes the new position's K/V at index
        ``lengths`` (clamped to the last row — the scheduler never
        decodes past capacity; the clamp only keeps an inactive slot's
        idle write in bounds), attends rows ``0..lengths``, and returns
        ``(x_out, k_cache, v_cache)``. Same projections/norms as
        ``__call__`` — the weights are literally the same submodules.
        The attention over the cache runs ``attention_override`` when
        given (the decode engine's flavor seam), else the block's
        ``decode_attention`` setting."""
        b = x.shape[0]
        head_dim = self.d_model // self.num_heads

        h = self.ln1(x)
        qkv = self.wqkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(b, 1, self.num_heads, head_dim)
        q, k, v = to_heads(q), to_heads(k), to_heads(v)
        write = jnp.clip(lengths, 0, k_cache.shape[1] - 1)
        rows = jnp.arange(b)
        k_cache = k_cache.at[rows, write].set(k[:, 0], mode="drop")
        v_cache = v_cache.at[rows, write].set(v[:, 0], mode="drop")
        attn = (
            attention_override
            if attention_override is not None
            else _resolve_decode_attention(self.decode_attention)
        )
        o = attn(q, k_cache, v_cache, lengths)
        x = x + self.wproj(o.reshape(b, 1, self.d_model))
        return self._mlp(x), k_cache, v_cache

    def decode_verify(self, x, k_cache, v_cache, lengths):
        """The multi-token (speculative verify) step: ``x [b, w, d]`` is
        the residual stream of ``w`` draft positions (position ``j`` is
        the token at sequence index ``lengths + j``), appended to the
        cache in ONE dispatch — all ``w`` new K/V rows land via a
        per-slot dynamic-update-slice at ``lengths``
        (``cache.append_kv_rows``) and every position attends
        cache+window causally (``ops.verify_cached_attention``: row
        ``j`` sees cache rows ``0..lengths+j``). Same submodules as
        ``__call__``/``decode`` — one weight set, three traced programs.
        Rollback-by-length: the caller commits only the accepted prefix
        by advancing ``lengths`` that far; rejected rows stay masked
        garbage (docs/DESIGN.md §18)."""
        from zookeeper_tpu.ops import verify_cached_attention
        from zookeeper_tpu.serving.decode.cache import append_kv_rows

        b, w, _ = x.shape
        head_dim = self.d_model // self.num_heads

        h = self.ln1(x)
        qkv = self.wqkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(b, w, self.num_heads, head_dim)
        q, k, v = to_heads(q), to_heads(k), to_heads(v)
        k_cache = append_kv_rows(k_cache, k, lengths)
        v_cache = append_kv_rows(v_cache, v, lengths)
        o = verify_cached_attention(q, k_cache, v_cache, lengths)
        x = x + self.wproj(o.reshape(b, w, self.d_model))
        return self._mlp(x), k_cache, v_cache

    def decode_paged(
        self, x, layer, page_table, lengths, attention_override=None
    ):
        """The page-pool twin of :meth:`decode` (docs/DESIGN.md §20):
        ``layer`` is a pool dict (``k``/``v`` ``[num_pages,
        head_shards, page_size, row_width]``, plus scale arrays for
        int8 pools) shared by EVERY slot; the new position's K/V row
        lands at ``(page_table[slot, lengths // page_size], lengths %
        page_size)`` — the indirected write, ``_pool_write_rows`` — and
        the attention reads through the table
        (``ops.pool_decode_attention`` or the injected kernel). A slot
        whose write target is unallocated (``-1`` table entry, or an
        inactive slot past its pages) drops the write via the OOB page
        sentinel — the paged analogue of the §15 clamp, and like it
        only ever taken by slots whose output is discarded."""
        b = x.shape[0]
        head_dim = self.d_model // self.num_heads
        num_pages, ps = layer["k"].shape[0], layer["k"].shape[2]

        h = self.ln1(x)
        qkv = self.wqkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(b, 1, self.num_heads, head_dim)
        q, k, v = to_heads(q), to_heads(k), to_heads(v)
        row = jnp.clip(lengths // ps, 0, page_table.shape[1] - 1)
        page = jnp.take_along_axis(page_table, row[:, None], axis=1)[:, 0]
        page = jnp.where(
            (page < 0) | (lengths >= page_table.shape[1] * ps),
            num_pages,
            page,
        )
        off = lengths % ps
        layer = _pool_write_rows(
            layer, {"k": k[:, 0], "v": v[:, 0]}, page, off
        )
        attn = (
            attention_override
            if attention_override is not None
            else _resolve_paged_attention(self.decode_attention)
        )
        k_scale, v_scale = _pool_scales(layer)
        o = attn(
            q, layer["k"], layer["v"], page_table, lengths,
            k_scale=k_scale, v_scale=v_scale,
        )
        x = x + self.wproj(o.reshape(b, 1, self.d_model))
        return self._mlp(x), layer

    def decode_verify_paged(
        self, x, layer, page_table, lengths, valid=None,
        attention_override=None,
    ):
        """The page-pool twin of :meth:`decode_verify`: all ``w``
        window rows scatter through the page table in one dispatch
        (position ``lengths + j`` → its table-resolved page/offset, so
        a window crossing a page boundary just lands in two pages), and
        every position attends cache+window through
        ``ops.pool_verify_attention``. ``valid [b]`` bounds how many
        window rows are REAL per slot (the warm-prefix extend program's
        padding rows write nowhere — OOB sentinel); None = all ``w``
        (the speculative verify, whose eligibility check already
        guarantees the pages exist). Rollback stays by-length."""
        b, w, _ = x.shape
        head_dim = self.d_model // self.num_heads
        num_pages, ps = layer["k"].shape[0], layer["k"].shape[2]

        h = self.ln1(x)
        qkv = self.wqkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(b, w, self.num_heads, head_dim)
        q, k, v = to_heads(q), to_heads(k), to_heads(v)
        pos = lengths[:, None] + jnp.arange(w)[None, :]
        row = jnp.clip(pos // ps, 0, page_table.shape[1] - 1)
        page = jnp.take_along_axis(page_table, row, axis=1)
        dead = (page < 0) | (pos >= page_table.shape[1] * ps)
        if valid is not None:
            dead = dead | (jnp.arange(w)[None, :] >= valid[:, None])
        page = jnp.where(dead, num_pages, page)
        off = pos % ps
        layer = _pool_write_rows(layer, {"k": k, "v": v}, page, off)
        k_scale, v_scale = _pool_scales(layer)
        from zookeeper_tpu.ops import pool_verify_attention

        attn = (
            attention_override
            if attention_override is not None
            else pool_verify_attention
        )
        o = attn(
            q, layer["k"], layer["v"], page_table, lengths,
            k_scale=k_scale, v_scale=v_scale,
        )
        x = x + self.wproj(o.reshape(b, w, self.d_model))
        return self._mlp(x), layer


def _auto_pin_activations(attention, pin_activations):
    """Whether the residual-stream pins apply. ``None`` (the default)
    auto-selects: pinned for the within-chip tiers (incl. the bare
    ``flash_attention``/``attention_reference`` callables — they are
    functionally identical to their string forms and need the same
    FSDP protection), skipped for any OTHER callable, which is assumed
    mesh-composed sequence parallelism: the SP op owns the
    sequence-sharded layout, and the ambient scope's canonical spec
    (which reads every non-data axis as a CHANNEL axis) would pin
    d_model over the sequence axis and fight it. Pass an explicit bool
    to override either way (e.g. ``True`` for a custom within-chip
    kernel under FSDP)."""
    if pin_activations is not None:
        return pin_activations
    return (
        not callable(attention)
        or attention in (flash_attention, attention_reference)
    )


class TransformerLMModule(nn.Module):
    """The causal LM module. ``setup()``-structured so three methods
    share one weight set and one param tree (names unchanged from the
    original compact layout):

    - ``__call__`` — the full-context forward (training, eval, the
      full-recompute ``greedy_decode`` oracle).
    - ``prefill`` — full-context forward that ALSO returns every
      layer's K/V heads (to seed a decode engine's KV cache) and the
      next-token logits at each sequence's true last position.
    - ``decode_step`` — one token per sequence through the cached-
      attention path (``ops.cached_attention``) over caller-owned KV
      buffers.

    Prefill/decode share weights AND numerics with ``__call__`` by
    construction — same submodules, same einsum/precision discipline —
    which is what the decode-parity certification pins
    (docs/DESIGN.md §15).
    """

    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    mlp_ratio: int
    attention: Any  # "flash" | "dense" | callable(q, k, v, *, causal)
    max_seq_len: int
    dtype: Any
    #: None = auto (see ``_auto_pin_activations``); bool overrides.
    pin_activations: Any = None
    #: Decode-path attention flavor ("reference" | "pallas" |
    #: callable); a ``decode_step`` per-call override wins — see
    #: ``_resolve_decode_attention``.
    decode_attention: Any = "reference"

    def setup(self):
        self.embed = self.param(
            "embed",
            nn.initializers.normal(0.02),
            (self.vocab_size, self.d_model),
        )
        self.pos = self.param(
            "pos",
            nn.initializers.normal(0.02),
            (self.max_seq_len, self.d_model),
        )
        pin = _auto_pin_activations(self.attention, self.pin_activations)
        self.blocks = [
            _Block(
                d_model=self.d_model,
                num_heads=self.num_heads,
                mlp_ratio=self.mlp_ratio,
                attention=self.attention,
                dtype=self.dtype,
                pin_activations=pin,
                decode_attention=self.decode_attention,
                name=f"block{i}",
            )
            for i in range(self.num_layers)
        ]
        self.final_norm = RMSNorm(dtype=self.dtype, name="RMSNorm_0")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    def _pin(self) -> bool:
        return _auto_pin_activations(self.attention, self.pin_activations)

    def _logits(self, x):
        x = self.final_norm(x)
        # Weight-tied LM head: logits in fp32 (the loss reduction dtype).
        return jnp.einsum(
            "bsd,vd->bsv",
            x.astype(jnp.float32),
            self.embed.astype(jnp.float32),
        )

    def _backbone(self, tokens, training: bool, collect_kv: bool):
        if tokens.ndim != 2:
            raise ValueError(
                f"TransformerLM expects [batch, seq] int tokens, got "
                f"shape {tokens.shape}."
            )
        s = tokens.shape[1]
        if s > self.max_seq_len:
            raise ValueError(
                f"Sequence length {s} exceeds max_seq_len "
                f"{self.max_seq_len} (the positional table size)."
            )
        x = (self.embed[tokens] + self.pos[None, :s]).astype(self.dtype)
        if self._pin():
            x = constrain_batch_sharded(x)
        kv = []
        for block in self.blocks:
            if collect_kv:
                x, layer_kv = block(x, training, return_kv=True)
                kv.append(layer_kv)
            else:
                x = block(x, training)
        return x, kv

    def __call__(self, tokens, training: bool = False):
        x, _ = self._backbone(tokens, training, collect_kv=False)
        return self._logits(x)

    def prefill(self, tokens, lengths):
        """Write-path of the decode engine's two-program split: run the
        ordinary full-context forward over a right-padded prompt batch
        ``tokens [b, s]`` (``lengths [b]`` true prompt lengths), and
        return ``(last_logits [b, vocab], kv)`` where ``last_logits``
        is each sequence's next-token distribution at its TRUE last
        position (right padding cannot influence it — causal) and
        ``kv`` is a per-layer tuple of ``(k, v) [b, s, heads,
        head_dim]`` head tensors for the caller to scatter into its KV
        cache. Numerically the same program as ``__call__`` — the
        first emitted token is the full-context oracle's."""
        x, kv = self._backbone(tokens, False, collect_kv=True)
        logits = self._logits(x)
        idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return last, tuple(kv)

    def decode_step(self, tokens, lengths, cache, attention_override=None):
        """One incremental token per sequence. ``tokens [b] int`` are
        the CURRENT input tokens (each sits at position ``lengths``),
        ``cache`` is a per-layer tuple of ``{"k", "v"}`` buffers
        ``[b, capacity, heads, head_dim]``. Returns ``(logits [b,
        vocab], new_cache)`` — the caller owns length bookkeeping and
        feeds ``argmax(logits)`` back as the next step's ``tokens``.
        ``attention_override`` (a ``callable(q, k_cache, v_cache,
        lengths)``) selects the cache-attention flavor for THIS trace,
        overriding the module's ``decode_attention`` — the seam the
        decode engine threads its config-selected kernel (or the
        mesh-composed sharded wrapper) through without rebuilding the
        module."""
        if len(cache) != self.num_layers:
            raise ValueError(
                f"cache has {len(cache)} layers, model has "
                f"{self.num_layers}."
            )
        pos_idx = jnp.clip(lengths, 0, self.max_seq_len - 1)
        x = (self.embed[tokens] + self.pos[pos_idx]).astype(self.dtype)
        x = x[:, None, :]
        if self._pin():
            x = constrain_batch_sharded(x)
        new_cache = []
        for block, layer in zip(self.blocks, cache):
            x, kc, vc = block.decode(
                x, layer["k"], layer["v"], lengths,
                attention_override=attention_override,
            )
            new_cache.append({"k": kc, "v": vc})
        return self._logits(x)[:, 0], tuple(new_cache)

    def decode_verify(self, tokens, lengths, cache):
        """``w`` tokens per sequence through the cached-attention path
        in ONE dispatch — the speculative-decode verify/append program
        (docs/DESIGN.md §18). ``tokens [b, w] int`` are the window's
        input tokens (token ``j`` sits at position ``lengths + j``),
        ``cache`` the per-layer ``{"k", "v"}`` buffers. Returns
        ``(logits [b, w, vocab], new_cache)`` with all ``w`` K/V rows
        appended per layer (``cache.append_kv_rows``); ``logits[:, j]``
        is the next-token distribution AFTER consuming token ``j`` —
        the verify scores for greedy acceptance. The caller owns length
        bookkeeping: advancing ``lengths`` by only the accepted prefix
        is the whole rollback contract (rejected rows stay at
        ``j >= length`` where every attention path masks them).
        Positions past the table clamp like ``decode_step``'s — the
        scheduler never COMMITS past ``token_limit``, so a clamped row
        is never attended. At ``w == 1`` this computes exactly what
        ``decode_step`` computes (same ops, ``verify_cached_attention``
        reduces to ``cached_attention``)."""
        if len(cache) != self.num_layers:
            raise ValueError(
                f"cache has {len(cache)} layers, model has "
                f"{self.num_layers}."
            )
        if tokens.ndim != 2:
            raise ValueError(
                f"decode_verify expects [batch, w] int tokens, got "
                f"shape {tokens.shape}."
            )
        w = tokens.shape[1]
        pos_idx = jnp.clip(
            lengths[:, None] + jnp.arange(w)[None, :],
            0,
            self.max_seq_len - 1,
        )
        x = (self.embed[tokens] + self.pos[pos_idx]).astype(self.dtype)
        if self._pin():
            x = constrain_batch_sharded(x)
        new_cache = []
        for block, layer in zip(self.blocks, cache):
            x, kc, vc = block.decode_verify(
                x, layer["k"], layer["v"], lengths
            )
            new_cache.append({"k": kc, "v": vc})
        return self._logits(x), tuple(new_cache)

    def decode_step_paged(
        self, tokens, lengths, cache, page_table, attention_override=None
    ):
        """:meth:`decode_step` over a SHARED page pool (docs/DESIGN.md
        §20): ``cache`` is a per-layer tuple of pool dicts (``k``/``v``
        ``[num_pages, head_shards, page_size, row_width]``, plus
        ``k_scale``/``v_scale`` for int8 pools), ``page_table [b,
        max_pages] int32`` resolves each sequence's logical pages.
        Same contract otherwise — the caller owns lengths, the new K/V
        row is written (through the table) before attending, and
        ``attention_override`` is the engine's paged-flavor seam
        (``callable(q, k_pool, v_pool, page_table, lengths, *,
        k_scale=None, v_scale=None)``)."""
        if len(cache) != self.num_layers:
            raise ValueError(
                f"cache has {len(cache)} layers, model has "
                f"{self.num_layers}."
            )
        pos_idx = jnp.clip(lengths, 0, self.max_seq_len - 1)
        x = (self.embed[tokens] + self.pos[pos_idx]).astype(self.dtype)
        x = x[:, None, :]
        if self._pin():
            x = constrain_batch_sharded(x)
        new_cache = []
        for block, layer in zip(self.blocks, cache):
            x, new_layer = block.decode_paged(
                x, layer, page_table, lengths,
                attention_override=attention_override,
            )
            new_cache.append(new_layer)
        return self._logits(x)[:, 0], tuple(new_cache)

    def decode_verify_paged(
        self, tokens, lengths, cache, page_table, valid=None,
        attention_override=None,
    ):
        """:meth:`decode_verify` over a shared page pool: ``w`` window
        tokens per sequence scatter through the page table in one
        dispatch (windows cross page boundaries freely) and every
        position's logits come back for acceptance scoring — ALSO the
        warm-prefix extend program (docs/DESIGN.md §20): a prompt whose
        prefix is cache-resident enters here with the SUFFIX as the
        window (``valid [b]`` = true suffix lengths; padding rows write
        nowhere), each suffix position attending the shared prefix
        pages it never recomputed — which is the entire TTFT win."""
        if len(cache) != self.num_layers:
            raise ValueError(
                f"cache has {len(cache)} layers, model has "
                f"{self.num_layers}."
            )
        if tokens.ndim != 2:
            raise ValueError(
                f"decode_verify_paged expects [batch, w] int tokens, "
                f"got shape {tokens.shape}."
            )
        w = tokens.shape[1]
        pos_idx = jnp.clip(
            lengths[:, None] + jnp.arange(w)[None, :],
            0,
            self.max_seq_len - 1,
        )
        x = (self.embed[tokens] + self.pos[pos_idx]).astype(self.dtype)
        if self._pin():
            x = constrain_batch_sharded(x)
        new_cache = []
        for block, layer in zip(self.blocks, cache):
            x, new_layer = block.decode_verify_paged(
                x, layer, page_table, lengths, valid=valid,
                attention_override=attention_override,
            )
            new_cache.append(new_layer)
        return self._logits(x), tuple(new_cache)


def greedy_decode(
    module: nn.Module, variables: Any, prompt: Any, steps: int
) -> jax.Array:
    """Greedy argmax continuation: ``[batch, t0]`` int tokens ->
    ``[batch, t0 + steps]``. Each step recomputes the FULL context
    (one jitted forward per emitted token, no KV cache) — a smoke/debug
    utility for eyeballing what a trained LM memorized and the seed of
    a future incremental-decode serving path, not a serving path
    itself. Deterministic by construction (argmax, no sampling).

    The module's positional table bounds the total length: building
    with ``max_seq_len`` headroom (an explicit capacity larger than
    the training ``seq_len``) is what makes room to decode past the
    training window.
    """
    if steps < 0:
        raise ValueError(f"steps={steps} must be >= 0.")
    tokens = jnp.asarray(prompt)
    if tokens.ndim != 2:
        raise ValueError(
            f"prompt must be [batch, t0] int tokens, got {tokens.shape}."
        )
    cap = getattr(module, "max_seq_len", None)
    if cap is not None and tokens.shape[1] + steps > cap:
        raise ValueError(
            f"prompt length {tokens.shape[1]} + steps {steps} exceeds "
            f"the positional table capacity {cap}; build the model with "
            "a larger max_seq_len to decode further."
        )
    # One executable per total length (steps distinct compiles): fine
    # for a smoke utility; an incremental decoder would bucket lengths.
    forward = jax.jit(
        lambda v, t: module.apply(v, t, training=False)
    )
    for _ in range(int(steps)):
        logits = forward(variables, tokens)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(tokens.dtype)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return tokens


@component
class TransformerLM(Model):
    """Causal LM model component (see module docstring).

    ``build(input_shape=(seq_len,), num_classes=vocab_size)`` follows
    the Model contract — the "classes" of a language model are its
    vocabulary, scored at every position.
    """

    num_layers: int = Field(4)
    d_model: int = Field(256)
    num_heads: int = Field(4)
    mlp_ratio: int = Field(4)
    #: "flash" (Pallas kernels, long-context default) or "dense" (the
    #: oracle path).
    attention: str = Field("flash")
    #: Decode-path (KV-cache) attention flavor: "reference" (the
    #: ``cached_attention`` oracle einsum — reads the full capacity
    #: axis every step) or "pallas" (the length-aware paged decode
    #: kernel). The DEFAULT stays the reference so direct module users
    #: keep oracle numerics; the serving engine's own
    #: ``decode_attention="auto"`` Field selects the kernel on TPU —
    #: see ``DecodeEngine``.
    decode_attention: str = Field("reference")
    #: Positional-table capacity. -1 (the default) sizes it to the
    #: sequence length ``build()`` receives — the common case, and it
    #: keeps one ``seq_len`` knob sufficient in CLI tasks. Set
    #: explicitly to train short now and run longer contexts later
    #: without a table reshape; build() raises if the configured
    #: sequence exceeds an explicit capacity.
    max_seq_len: int = Field(-1)

    def set_attention_override(self, fn) -> None:
        """The partitioner injection seam (``Partitioner.prepare_model``):
        a mesh-owning partitioner (``SequenceParallelPartitioner``)
        installs its attention callable here BEFORE ``build()``, which
        then takes precedence over the string ``attention`` Field — so
        sequence-parallel recipes drive from the CLI without hand-wiring
        callables into model configs. ``None`` clears the override."""
        if fn is not None and not callable(fn):
            raise ValueError(
                f"attention override must be callable(q, k, v, *, "
                f"causal) or None, got {fn!r}."
            )
        object.__setattr__(self, "_attention_override", fn)

    def set_decode_attention_override(self, fn) -> None:
        """The decode-path twin of :meth:`set_attention_override`: a
        mesh-owning caller installs a ``callable(q, k_cache, v_cache,
        lengths)`` here before ``build()`` and it takes precedence over
        the string ``decode_attention`` Field. ``None`` clears."""
        if fn is not None and not callable(fn):
            raise ValueError(
                f"decode attention override must be callable(q, k_cache, "
                f"v_cache, lengths) or None, got {fn!r}."
            )
        object.__setattr__(self, "_decode_attention_override", fn)

    def build(self, input_shape: Sequence[int], num_classes: int) -> nn.Module:
        if len(input_shape) != 1:
            raise ValueError(
                f"TransformerLM input_shape must be (seq_len,), got "
                f"{tuple(input_shape)}."
            )
        # One source of truth for valid tiers (the Field is a string;
        # callables plug in at the MODULE level — see
        # ``_resolve_attention``). An injected override (the
        # partitioner seam above) wins over the Field.
        attention = getattr(self, "_attention_override", None)
        if attention is None:
            _resolve_attention(self.attention)
            attention = self.attention
        decode_attention = getattr(self, "_decode_attention_override", None)
        if decode_attention is None:
            _resolve_decode_attention(self.decode_attention)
            decode_attention = self.decode_attention
        if self.d_model % self.num_heads != 0:
            raise ValueError(
                f"d_model={self.d_model} not divisible by "
                f"num_heads={self.num_heads}."
            )
        (seq_len,) = input_shape
        if self.max_seq_len == -1:
            max_seq_len = seq_len
        elif self.max_seq_len > 0:
            max_seq_len = self.max_seq_len
        else:
            # 0 or other negatives are config typos, not the sentinel —
            # silently auto-sizing them would hide the mistake.
            raise ValueError(
                f"max_seq_len={self.max_seq_len}: expected a positive "
                "capacity or -1 (size to the built sequence)."
            )
        if seq_len > max_seq_len:
            raise ValueError(
                f"seq_len {seq_len} exceeds max_seq_len {max_seq_len}."
            )
        return TransformerLMModule(
            vocab_size=num_classes,
            num_layers=self.num_layers,
            d_model=self.d_model,
            num_heads=self.num_heads,
            mlp_ratio=self.mlp_ratio,
            attention=attention,
            max_seq_len=max_seq_len,
            dtype=self.dtype(),
            decode_attention=decode_attention,
        )

    def initialize(
        self,
        module: nn.Module,
        input_shape: Sequence[int],
        seed: int = 0,
    ) -> Tuple[Any, Any]:
        """Token models init with an INT dummy (the base class's float
        zeros would be an invalid embedding index dtype)."""
        rng = jax.random.PRNGKey(seed)
        dummy = jnp.zeros((1, *input_shape), jnp.int32)
        variables = module.init(rng, dummy, training=False)
        params = variables.pop("params")
        return params, variables
