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

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.errors import ScopeParamShapeError
from flax.linen.dtypes import promote_dtype

from zookeeper_tpu.core import Field, component
from zookeeper_tpu.models.base import Model
from zookeeper_tpu.ops import (
    attention_reference,
    flash_attention,
    kv_row_width,
    pool_decode_attention,
    pool_verify_attention,
)
from zookeeper_tpu.ops.kda import kda_chunk_scan, kda_decode_update
from zookeeper_tpu.ops.mla import (
    mla_attention_reference,
    mla_flash_attention,
    mla_gathered_rows,
    mla_pool_decode_attention,
)
from zookeeper_tpu.ops.moe import sparse_moe
from zookeeper_tpu.ops.ssm import (
    causal_conv,
    ssm_chunk_scan,
    ssm_decode_update,
)
from zookeeper_tpu.parallel.sharding import constrain_batch_sharded


#: Taps of a state-space mixer's causal convolution: 4 in every Mamba-2
#: configuration the repo runs (a field once one needs another).
SSM_CONV_TAPS = 4


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """A Mamba-2 state-space mixer's sizes and its own multipliers, as
    :class:`TransformerLM` hands them to every block: ``heads`` heads of
    ``head_dim`` with a state of ``state`` a channel, ``B`` and ``C``
    shared by the heads of each of ``groups`` groups, prefilled in
    chunks of ``chunk`` tokens; ``in_multiplier`` on the mixer's input,
    ``out_multiplier`` on its output, ``multipliers`` on the five
    segments of its projection (gate, x, B, C, dt; empty: all 1)."""

    heads: int
    head_dim: int
    state: int
    groups: int = 1
    chunk: int = 128
    in_multiplier: float = 1.0
    out_multiplier: float = 1.0
    multipliers: Tuple[float, ...] = ()

    @property
    def inner(self) -> int:
        """The mixer's inner width: ``heads x head_dim``."""
        return self.heads * self.head_dim

    @property
    def bc(self) -> int:
        """The width of ``B`` (or ``C``): ``groups x state``."""
        return self.groups * self.state

    @property
    def segments(self) -> Tuple[int, ...]:
        """Widths of the projection's segments: gate, x, B, C, dt."""
        return (self.inner, self.inner, self.bc, self.bc, self.heads)

    def slot_state(self, dtype) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What the mixer keeps a sequence between tokens, ``{name:
        (shape, dtype)}`` in the order :meth:`_Block._ssm` returns it:
        the recurrence's float32 state and the convolution's last input
        rows in the compute ``dtype``."""
        return {
            "ssm": ((self.heads, self.head_dim, self.state), jnp.float32),
            "conv": ((SSM_CONV_TAPS - 1, self.inner + 2 * self.bc), dtype),
        }


@dataclasses.dataclass(frozen=True)
class KDASpec:
    """A gated delta-rule linear-attention mixer's sizes (Kimi Delta
    Attention; ``ops/kda.py``), as :class:`TransformerLM` hands them to
    the blocks of its ``"kda"`` layers: ``heads`` heads whose keys and
    values are both ``head_dim`` wide, a causal convolution of
    ``conv_taps`` taps on q, k and v, the decay's and the output gate's
    low-rank projections of ``gate_rank``, prefilled in chunks of
    ``chunk`` tokens; ``neg_eigval``: ``beta = 2 sigmoid(.)`` (the
    transition may have negative eigenvalues), else ``sigmoid(.)``."""

    heads: int
    head_dim: int
    conv_taps: int = 4
    gate_rank: int = 128
    chunk: int = 64
    neg_eigval: bool = True

    @property
    def inner(self) -> int:
        """The mixer's inner width: ``heads x head_dim``."""
        return self.heads * self.head_dim

    def slot_state(self, dtype) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What the mixer keeps a sequence between tokens, ``{name:
        (shape, dtype)}`` in the order :meth:`_Block._kda` returns it:
        the rule's float32 state a head and the convolution's last input
        rows (q, k and v side by side) in the compute ``dtype``."""
        return {
            "kda": ((self.heads, self.head_dim, self.head_dim), jnp.float32),
            "kda_conv": ((self.conv_taps - 1, 3 * self.inner), dtype),
        }


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """Multi-head latent attention's sizes (DeepSeek-V2, LongCat-Flash;
    ``ops/mla.py``), as :class:`TransformerLM` hands them to every block:
    a token's keys and values come from one ``kv_rank``-wide latent
    (normed, then times ``kv_scale``) through ``mla_kv_b``, each head's
    key is ``nope_dim`` of it beside a ``rope_dim``-wide rotary key that
    the heads share, its value ``v_dim`` wide; ``scale`` is the
    softmax's, YaRN's factor included (:func:`yarn_score_factor`). The
    query is one projection (``q_rank`` 0, DeepSeek-V2-Lite) or a
    low-rank pair with a norm between (``mla_q_a``, ``mla_q_norm``,
    ``mla_q_b``), the whole query then times ``q_scale``. 1.0 traces no
    multiply. The cache keeps ``[c, k_pe]``, :attr:`row` values a token a
    layer, and no K/V a head."""

    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    scale: float
    q_rank: int = 0
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def qk_dim(self) -> int:
        """A head's query/key width: ``nope_dim + rope_dim``."""
        return self.nope_dim + self.rope_dim

    @property
    def row(self) -> int:
        """A token's cached latent row: ``kv_rank + rope_dim``."""
        return self.kv_rank + self.rope_dim


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The scalar multipliers of a maximal-update parametrisation outside
    the state-space mixer: on the embedding, the logits, attention's
    output, the keys (before the rotation) and the gated MLP (its gate,
    its output; empty: 1). 1.0 traces no multiply."""

    embedding: float = 1.0
    lm_head: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    mlp: Tuple[float, ...] = ()


def _scaled(x, multiplier: float):
    return x if multiplier == 1.0 else x * multiplier


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
    same write. Returns the updated layer dict.

    One scatter index a ROW: the write of the programs whose window
    starts at ``lengths``, anywhere in a page (``decode_paged``: one
    row a slot; ``decode_verify_paged``: the speculative verify, the
    warm-prefix extend, a prefill chunk). A cold prefill starts at
    position 0 and writes by page: :func:`_pool_write_pages`."""

    def write(buf, vals):
        return buf.at[pages, :, offsets].set(
            vals.astype(buf.dtype), mode="drop"
        )

    return _pool_write(layer, rows, write)


def _pool_write_pages(layer, rows, pages):
    """Write ``rows [pb, sb, heads, head_dim]``, the K/V of positions
    ``0 .. sb - 1`` of ``pb`` sequences, into a page-pool layer dict a
    PAGE at a time: positions ``p * page_size .. (p + 1) * page_size -
    1`` of sequence ``i`` land as page ``pages[i, p]`` whole (``pages
    [pb, ceil(sb / page_size)]``; an entry ``== num_pages``, the OOB
    sentinel, writes nowhere; ``sb`` is padded to whole pages here).
    Folded and quantized as :func:`_pool_write_rows` does, the scales
    through the same write, the donated pool updated in place; what
    differs is the scatter's window, a page's ``[head_shards,
    page_size, row_width]`` (whole tiles, contiguous) where a row's is
    a strided sixteenth of each of them, and one index a page where
    there was one a row (on the v5e a row at a time cost a third of a
    1,024-token prefill's device time: docs/DESIGN.md §20).

    The cold prefill's write (``DecodeEngine``'s ``prefill_fn``): only
    a window that starts at position 0 holds whole pages. It leaves one
    thing in the pool that the row write does not: the rows from a
    sequence's length to the end of its last page hold the padding
    positions' K/V (finite) and not the page's last tenant's. Every
    reader masks by the sequence's length, and a decode step writes row
    ``length`` before it reads it."""
    first = next(iter(rows))
    ps = layer[first].shape[2]
    pb, sb = rows[first].shape[:2]
    pad = pages.shape[1] * ps - sb

    def write(buf, vals):
        # [pb, sb, head_shards, x] -> a page's block [head_shards, ps, x]
        if pad:
            vals = jnp.pad(vals, [(0, 0), (0, pad)] + [(0, 0)] * 2)
        vals = vals.reshape(pb, -1, ps, *vals.shape[2:]).swapaxes(2, 3)
        return buf.at[pages].set(vals.astype(buf.dtype), mode="drop")

    return _pool_write(layer, rows, write)


def _pool_write(layer, rows, write):
    """``write(buf, vals)`` for each leaf of ``rows`` (``k`` and ``v``, or
    a latent layer's ``c``) in the pool's stored form
    (``ops.fold_kv_rows``; int8 with the scales when the layer carries
    them: ``ops.quantizers.quantize_kv_rows``)."""
    from zookeeper_tpu.ops import fold_kv_rows, quantize_kv_rows

    out = dict(layer)
    for name, vals in rows.items():
        scale_name = f"{name}_scale"
        buf = layer[name]
        shards, width = buf.shape[1], buf.shape[3]
        if scale_name in layer:
            vals, s = quantize_kv_rows(vals)
            out[scale_name] = write(
                layer[scale_name], s.reshape(*s.shape[:-1], shards, -1)
            )
        out[name] = write(buf, fold_kv_rows(vals, shards, width))
    return out


def layer_page_table(page_table, windowed: bool):
    """A layer's page table out of a dispatch's operand: the table
    itself for a model of one layer group, else the group's of the two
    stacked ``[full, window]`` (``PagePool.operand``)."""
    if page_table.ndim == 2:
        return page_table
    return page_table[1 if windowed else 0]


def _pool_scales(layer):
    return layer.get("k_scale"), layer.get("v_scale")


def _param(module, name, init_fn, shape, dtype, held_shape=None):
    """``module.param(name, init_fn, shape, dtype)``, cheap where the
    parameter is already there. On every apply flax evaluates
    ``init_fn`` abstractly only to compare the shape it gives with the
    shape held (``Scope.param``: 1-3 ms a parameter a trace, which over
    the 147 parameters and eight programs of a 24-layer server is a
    quarter of its lowering time). The shape is an argument here, so the
    same check is made on it as it stands. ``held_shape`` is the one
    other shape a serving tree may hold the parameter in
    (:meth:`TransformerLMModule.serving_leaf`'s tables); any third is
    refused like any parameter's."""
    if not module.has_variable("params", name):
        return module.param(name, init_fn, shape, dtype)
    value = module.get_variable("params", name)
    if jnp.shape(value) not in (tuple(shape), held_shape):
        raise ScopeParamShapeError(
            name, module.scope.path_text, jnp.shape(value), tuple(shape)
        )
    return value


class _Dense(nn.Module):
    """``nn.Dense`` without a bias, op for op (its ``kernel`` under the
    same name, cast to ``dtype`` on use, the same ``dot_general``), its
    parameter read through :func:`_param`."""

    features: int
    dtype: Any
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = _param(
            self, "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), self.param_dtype,
        )
        x, kernel = promote_dtype(x, kernel, dtype=self.dtype)
        return jax.lax.dot_general(
            x, kernel, (((x.ndim - 1,), (0,)), ((), ()))
        )


class RMSNorm(nn.Module):
    """Root-mean-square layernorm (no mean subtraction, no bias): the
    cheaper norm that long-context transformer stacks standardized on;
    fp32 statistics regardless of compute dtype."""

    dtype: Any = jnp.float32
    eps: float = 1e-6
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        scale = _param(
            self, "scale", nn.initializers.ones, (x.shape[-1],),
            self.param_dtype,
        )
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (y * scale).astype(self.dtype)


class _GatedGroupNorm(nn.Module):
    """The state-space mixer's output norm: ``RMSNorm(y * silu(z))`` with
    the statistics taken over each of ``groups`` equal runs of channels
    (float32), one gain a channel."""

    groups: int
    dtype: Any
    eps: float = 1e-6
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y, z):
        y = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        scale = _param(
            self, "scale", nn.initializers.ones, (y.shape[-1],),
            self.param_dtype,
        )
        grouped = y.reshape(*y.shape[:-1], self.groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + self.eps
        )
        return (grouped.reshape(y.shape) * scale).astype(self.dtype)


def _yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1``."""
    import math

    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(head_dim: int, theta: float, yarn: Tuple = ()):
    """``(inv_freq [head_dim // 2] float32, attention_factor)`` of rotary
    positions over the whole head: ``inv_i = theta ** (-2 i /
    head_dim)``. ``yarn = (factor, original_len, beta_fast, beta_slow)``
    stretches the slow dimensions for contexts past ``original_len``
    (YaRN): with ``d(b) = head_dim ln(original_len / (2 pi b)) / (2 ln
    theta)`` the dimension that turns ``b`` times over the original
    length, ``low = floor(d(beta_fast))`` and ``high = ceil(d(beta_slow))``
    (clipped to the head), dimensions below ``low`` keep ``inv_i``,
    those above ``high`` take ``inv_i / factor``, and a linear ramp
    blends between. The table is static: it does not depend on the
    sequence's length.

    ``attention_factor`` is what cos and sin are scaled by, and which of
    YaRN's two conventions applies is said by the tuple, never assumed:
    four numbers (no ``mscale`` in the config, mellum2's) give
    ``0.1 ln(factor) + 1``; six, ``yarn + (mscale, mscale_all_dim)``
    (DeepSeek's), give ``m(mscale) / m(mscale_all_dim)`` with ``m(s) =
    0.1 s ln(factor) + 1`` (1.0 where the two are equal), and the score
    then takes ``m(mscale_all_dim) ** 2`` instead (:func:`yarn_score_factor`)."""
    import math

    import numpy as np

    half = head_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    if not yarn:
        return jnp.asarray(inv, jnp.float32), 1.0
    factor, original_len, beta_fast, beta_slow = yarn[:4]

    def turns_dim(turns):
        return (
            head_dim
            * math.log(original_len / (turns * 2 * math.pi))
            / (2 * math.log(theta))
        )

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), head_dim - 1)
    ramp = np.clip(
        (np.arange(half, dtype=np.float64) - low) / max(high - low, 1e-3),
        0.0,
        1.0,
    )
    inv = inv * (1.0 - ramp) + (inv / factor) * ramp
    if len(yarn) == 6:
        on_rotation = _yarn_mscale(factor, yarn[4]) / _yarn_mscale(
            factor, yarn[5]
        )
    else:
        on_rotation = _yarn_mscale(factor)
    return jnp.asarray(inv, jnp.float32), on_rotation


def yarn_score_factor(yarn: Tuple = ()) -> float:
    """What DeepSeek's YaRN puts on the softmax scale: ``m(mscale_all_dim)
    ** 2`` for a six-number ``yarn`` (:func:`rope_inv_freq`), else 1.0
    (the other convention scales cos and sin instead)."""
    if len(yarn) != 6:
        return 1.0
    return _yarn_mscale(yarn[0], yarn[5]) ** 2


def apply_rope(xs, positions, inv_freq, attention_factor: float = 1.0):
    """Rotate each ``x [b, s, heads, head_dim]`` of ``xs`` by its
    ``positions [b, s]`` (rotate-half convention: dimension ``i`` pairs
    with ``i + head_dim / 2``). Angles, cos and sin in float32, once
    for all of ``xs``; each result in its ``x``'s dtype."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(angles) * attention_factor)[:, :, None, :]
    sin = (jnp.sin(angles) * attention_factor)[:, :, None, :]

    def rotate(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )
        return out.astype(x.dtype)

    return tuple(rotate(x) for x in xs)


class _Block(nn.Module):
    """One pre-norm decoder block.

    ``setup()``-structured (not ``nn.compact``) so the SAME weights
    serve three traced programs: the full-context ``__call__`` (training
    / prefill), the single-position :meth:`decode_paged` and the
    multi-position :meth:`decode_verify_paged` (attention over the
    page pool). Submodule names are pinned to the
    names the original compact implementation auto-assigned
    (``RMSNorm_0``/``RMSNorm_1``/``qkv``/``proj``/``up``/``down``) so
    every existing checkpoint and partition rule keeps matching.

    What differs by model is a field, and every default is the GPT-2
    shape: ``num_kv_heads``/``head_dim`` (grouped heads, a head size
    that is not ``d_model / num_heads``), ``rope_theta`` (rotary
    positions on q and k, YaRN-scaled where ``rope_yarn`` is given),
    ``window`` (a sliding-window layer: causal and at most ``window``
    keys back), ``mlp="moe"`` (sparse SwiGLU experts, ``ops/moe.py``) or
    ``"swiglu"`` (one dense gated MLP of ``mlp_dim``), ``ssm`` (a
    Mamba-2 state-space mixer beside attention, on the same normed
    input, :meth:`_ssm`; ``ops/ssm.py``), ``kda`` (the block's ONLY
    mixer is gated delta-rule linear attention, :meth:`_kda`;
    ``ops/kda.py``: no q/k/v/proj are built and the layer keeps no K/V
    rows), ``attention_gate`` (a sigmoid gate a channel on attention's
    heads before the output projection), ``held_experts`` (the chip's
    share of the routed experts, ``ops/moe.py``) and
    ``shared_expert_dim`` (a SwiGLU expert every token takes beside the
    routed ones), ``mla`` (latent attention in place of q/k/v heads, one
    latent row a token: :meth:`_mla_expanded` for prefill and extend,
    :meth:`_mla_absorbed` for decode; ``ops/mla.py``, docs/DESIGN.md
    §29), ``renormalize_experts``, the router's ``router_bias`` (a
    correction bias that picks and does not weigh), ``routed_scale`` and
    ``zero_experts`` (identity experts past the real ones), ``moe_branch``
    and ``moe_join`` (LongCat-Flash's shortcut MoE: the block that
    branches computes the experts on its MLP's normed input beside its
    dense MLP and hands their sum on beside the stream, the block that
    joins adds it at its end; docs/DESIGN.md §31), ``multipliers`` (1.0:
    no multiply is traced) and ``param_dtype``. The three traced methods
    share ONE projection-and-positions helper (:meth:`_qkv`) and one
    attention keyword set (:meth:`_attention_kwargs`).

    A block with the state-space mixer carries a second kind of state
    beside its K/V rows (:meth:`SSMSpec.slot_state`): the recurrence's
    ``ssm [b, heads, head_dim, state]`` float32 and the convolution's
    last input rows ``conv [b, SSM_CONV_TAPS - 1, channels]``.
    ``__call__`` returns them at each sequence's own length,
    ``decode_paged`` reads and writes them beside the pool's leaves (a
    fixed block a slot, not rows a token); ``decode_verify_paged`` is
    refused, since advancing ``lengths`` by fewer rows than were
    written rolls K/V back and cannot roll a recurrence back.
    """

    d_model: int
    num_heads: int
    mlp_ratio: int
    attention: Any
    dtype: Any
    pin_activations: bool = True
    num_kv_heads: int = 0  # 0: as many as num_heads
    head_dim: int = 0  # 0: d_model // num_heads
    rope_theta: float = 0.0  # 0: no rotary positions
    rope_yarn: Tuple = ()
    window: int = 0  # 0: full attention
    mlp: str = "gelu"
    num_experts: int = 0
    experts_per_token: int = 0
    expert_dim: int = 0
    param_dtype: Any = jnp.float32
    mlp_dim: int = 0  # mlp="swiglu": the gated MLP's width
    norm_eps: float = 1e-6
    ssm: Optional[SSMSpec] = None  # None: no state-space mixer
    multipliers: Multipliers = Multipliers()
    kda: Optional[KDASpec] = None  # a spec: the block's only mixer
    attention_gate: bool = False
    held_experts: Tuple[int, ...] = ()  # (first, count); empty: all
    shared_expert_dim: int = 0  # 0: no shared expert
    mla: Optional[MLASpec] = None  # a spec: latent attention, one row a token
    renormalize_experts: bool = True  # False: the top-k softmax weights as they are
    router_bias: bool = False  # a correction bias in the experts' choice
    routed_scale: float = 1.0  # on the routed weights
    zero_experts: int = 0  # identity experts past num_experts
    moe_branch: bool = False  # the experts beside the dense MLP, carried on
    moe_join: bool = False  # the carried experts' sum added at the end

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def setup(self):
        d = self.d_model
        dense = partial(
            _Dense, dtype=self.dtype, param_dtype=self.param_dtype
        )
        norm = partial(
            RMSNorm, dtype=self.dtype, param_dtype=self.param_dtype,
            eps=self.norm_eps,
        )
        self.ln1 = norm(name="RMSNorm_0")
        if self.mla is not None:
            mla = self.mla
            # The query's heads (nope then rope a head), the latent with
            # the shared rotary key, the latent's norm, and the latent's
            # expansion to each head's key (nope) then value: a raw
            # kernel, since decode multiplies it in absorbed form.
            if mla.q_rank:
                self.wmla_q_a = dense(mla.q_rank, name="mla_q_a")
                self.mla_q_norm = norm(name="mla_q_norm")
                self.wmla_q_b = dense(
                    self.num_heads * mla.qk_dim, name="mla_q_b"
                )
            else:
                self.wmla_q = dense(self.num_heads * mla.qk_dim, name="mla_q")
            self.wmla_kv_a = dense(mla.row, name="mla_kv_a")
            self.mla_kv_norm = norm(name="mla_kv_norm")
            self.mla_kv_b = _param(
                self, "mla_kv_b", nn.initializers.lecun_normal(),
                (mla.kv_rank, self.num_heads * (mla.nope_dim + mla.v_dim)),
                self.param_dtype,
            )
            self.wproj = dense(d, name="proj")
        elif self.kda is None:
            # One fused projection: query heads, then key heads, then
            # value heads (three equal thirds in the GPT-2 shape).
            self.wqkv = dense(
                (self.num_heads + 2 * self.kv_heads) * self.head_size,
                name="qkv",
            )
            self.wproj = dense(d, name="proj")
            if self.attention_gate:
                self.wattn_gate = dense(
                    self.num_heads * self.head_size, name="attn_gate"
                )
        else:
            kda = self.kda
            # One projection: q, k and v, each `inner` wide, in that order.
            self.wkda_qkv = dense(3 * kda.inner, name="kda_qkv")
            self.wkda_f1 = dense(kda.gate_rank, name="kda_f1")
            self.wkda_f2 = dense(kda.inner, name="kda_f2")
            self.wkda_g1 = dense(kda.gate_rank, name="kda_g1")
            self.wkda_g2 = dense(kda.inner, name="kda_g2")
            self.wkda_beta = dense(kda.heads, name="kda_beta")
            self.wkda_out = dense(d, name="kda_out")
            self.kda_norm = norm(name="kda_norm")
            for name, init, shape in (
                ("kda_conv_kernel", nn.initializers.lecun_normal(),
                 (kda.conv_taps, 3 * kda.inner)),
                ("kda_dt_bias", nn.initializers.zeros, (kda.inner,)),
                ("kda_A_log", nn.initializers.zeros, (kda.heads,)),
            ):
                setattr(
                    self, name,
                    _param(self, name, init, shape, self.param_dtype),
                )
        self.ln2 = norm(name="RMSNorm_1")
        if self.mlp == "moe" or self.moe_branch:
            f = self.expert_dim
            held = self.held_experts[1] if self.held_experts else self.num_experts
            # The experts' matrices lie side by side, expert e the column
            # block e (ops/moe.py): each leaf a plain [fan_in, out] kernel.
            # The router scores every expert, the identity ones last; the
            # three expert leaves hold the chip's share of the real ones.
            kernel = nn.initializers.lecun_normal()
            width = self.num_experts + self.zero_experts
            if self.router_bias:
                self.router_bias_ = _param(
                    self, "router_bias", nn.initializers.zeros, (width,),
                    self.param_dtype,
                )
            for name, shape in (
                ("router", (d, width)),
                ("experts_gate", (d, held * f)),
                ("experts_up", (d, held * f)),
                ("experts_down", (f, held * d)),
            ):
                setattr(
                    self, name,
                    _param(self, name, kernel, shape, self.param_dtype),
                )
            if self.shared_expert_dim:
                self.wshared_gate = dense(
                    self.shared_expert_dim, name="shared_gate"
                )
                self.wshared_up = dense(self.shared_expert_dim, name="shared_up")
                self.wshared_down = dense(d, name="shared_down")
        if self.mlp != "moe":
            width = self.mlp_ratio * d
            if self.mlp == "swiglu":
                width = self.mlp_dim
                self.wgate = dense(width, name="gate")
            self.wup = dense(width, name="up")
            self.wdown = dense(d, name="down")
        if self.ssm is not None:
            ssm = self.ssm
            channels = ssm.inner + 2 * ssm.bc
            # One projection: gate z, x, B, C, dt, in that order.
            self.wssm_in = dense(sum(ssm.segments), name="ssm_in")
            self.wssm_out = dense(d, name="ssm_out")
            self.ssm_norm = _GatedGroupNorm(
                groups=ssm.groups, dtype=self.dtype, eps=self.norm_eps,
                param_dtype=self.param_dtype, name="ssm_norm",
            )
            for name, init, shape in (
                ("ssm_conv_kernel", nn.initializers.lecun_normal(),
                 (SSM_CONV_TAPS, channels)),
                ("ssm_conv_bias", nn.initializers.zeros, (channels,)),
                ("dt_bias", nn.initializers.zeros, (ssm.heads,)),
                ("A_log", nn.initializers.zeros, (ssm.heads,)),
                ("D", nn.initializers.ones, (ssm.heads,)),
            ):
                setattr(
                    self, name,
                    _param(self, name, init, shape, self.param_dtype),
                )

    def _qkv(self, normed, positions):
        """The projection and the positions, once for every traced
        method: ``normed [b, s, d]`` (the block's input after its first
        norm) -> ``q [b, s, heads, head_dim]``, ``k`` and ``v [b, s,
        kv_heads, head_dim]``, q and k rotated by ``positions [b, s]``
        where the block has rotary positions."""
        b, s, _ = normed.shape
        h, hkv, hd = self.num_heads, self.kv_heads, self.head_size
        qkv = self.wqkv(normed)
        q, k, v = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, hkv, hd)
        v = v.reshape(b, s, hkv, hd)
        k = _scaled(k, self.multipliers.key)
        if self.rope_theta:
            inv_freq, factor = rope_inv_freq(
                hd, self.rope_theta, self.rope_yarn
            )
            q, k = apply_rope((q, k), positions, inv_freq, factor)
        return q, k, v

    def _attention_kwargs(self, pool: bool = False):
        """What this block's attention call adds to the plain one: the
        band of a window layer, and (pool paths) how many heads a pool
        row holds when they are grouped. Empty in the GPT-2 shape, so
        a plain ``callable(q, k, v, *, causal)`` still plugs in."""
        kwargs = {}
        if self.window:
            kwargs["window"] = self.window
        if pool and self.kv_heads != self.num_heads:
            kwargs["kv_heads"] = self.kv_heads
        return kwargs

    def _ssm(self, h, state=None, lengths=None):
        """The state-space mixer on the normed ``h [b, s, d]``
        (``ops/ssm.py`` holds the mathematics). ``state`` None: whole
        sequences from their start, by the chunked scan, rows at or past
        ``lengths [b]`` (None: none) being padding that neither the
        recurrence nor the convolution's carry sees. ``state = (ssm,
        conv)``: one token a sequence (``s == 1``) from that state.
        Returns ``(out [b, s, d], (ssm, conv))``, the state after each
        sequence's last real token."""
        b, s, _ = h.shape
        ssm = self.ssm
        inner, bc = ssm.inner, ssm.bc
        proj = self.wssm_in(_scaled(h, ssm.in_multiplier))
        if ssm.multipliers:
            proj = proj * jnp.asarray(
                np.repeat(
                    np.asarray(ssm.multipliers, np.float32), ssm.segments
                ),
                proj.dtype,
            )
        z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
        xbc, conv = causal_conv(
            xbc, self.ssm_conv_kernel, self.ssm_conv_bias,
            carry=None if state is None else state[1], lengths=lengths,
        )
        xbc = nn.silu(xbc)
        xs, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
        xs = xs.reshape(b, s, ssm.heads, ssm.head_dim)
        B = B.reshape(b, s, ssm.groups, ssm.state)
        C = C.reshape(b, s, ssm.groups, ssm.state)
        dt = nn.softplus(
            dt.astype(jnp.float32) + self.dt_bias.astype(jnp.float32)
        )
        A = -jnp.exp(self.A_log.astype(jnp.float32))
        if state is None:
            if lengths is not None:
                real = jnp.arange(s)[None, :] < lengths[:, None]
                dt = jnp.where(real[:, :, None], dt, 0.0)
            y, carried = ssm_chunk_scan(
                xs, dt, A, B, C, chunk=min(ssm.chunk, s)
            )
        else:
            y, carried = ssm_decode_update(
                state[0], xs[:, 0], dt[:, 0], A, B[:, 0], C[:, 0]
            )
            y = y[:, None]
        y = y + self.D.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
        out = self.wssm_out(self.ssm_norm(y.reshape(b, s, inner), z))
        return _scaled(out, ssm.out_multiplier), (carried, conv)

    def _kda(self, h, state=None, lengths=None):
        """The gated delta-rule mixer on the normed ``h [b, s, d]``
        (``ops/kda.py`` holds the mathematics), with :meth:`_ssm`'s
        contract: ``state`` None runs whole sequences from their start by
        the chunked form, rows at or past ``lengths [b]`` being padding
        that neither the rule nor the convolution's carry sees; ``state
        = (kda, kda_conv)`` runs one token a sequence from that state.
        Returns ``(out [b, s, d], (kda, kda_conv))``."""
        b, s, _ = h.shape
        kda = self.kda
        heads, hd = kda.heads, kda.head_dim
        f32 = jnp.float32
        qkv, conv = causal_conv(
            self.wkda_qkv(h), self.kda_conv_kernel, None,
            carry=None if state is None else state[1], lengths=lengths,
        )
        q, k, v = (
            x.reshape(b, s, heads, hd)
            for x in jnp.split(nn.silu(qkv), 3, axis=-1)
        )

        def unit(x):
            x = x.astype(f32)
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6
            )

        q = (unit(q) * hd ** -0.5).astype(self.dtype)
        k = unit(k).astype(self.dtype)
        # the log decay, a channel of the key: <= 0, float32
        g = -jnp.exp(self.kda_A_log.astype(f32))[:, None] * nn.softplus(
            self.wkda_f2(self.wkda_f1(h)).astype(f32)
            + self.kda_dt_bias.astype(f32)
        ).reshape(b, s, heads, hd)
        beta = nn.sigmoid(self.wkda_beta(h).astype(f32))
        if kda.neg_eigval:
            beta = 2.0 * beta
        if state is None:
            o, carried = kda_chunk_scan(
                q, k, v, g, beta, chunk=kda.chunk, lengths=lengths
            )
        else:
            o, carried = kda_decode_update(
                state[0], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
            )
            o = o[:, None]
        gate = nn.sigmoid(self.wkda_g2(self.wkda_g1(h)).astype(f32))
        o = self.kda_norm(o).astype(f32).reshape(b, s, -1) * gate
        return self.wkda_out(o.astype(self.dtype)), (carried, conv)

    def _mla_rotate(self, x, positions):
        """The rotary part of a latent layer's queries or keys, ``x [b, s,
        heads, rope_dim]``, rotated by ``positions [b, s]``."""
        inv_freq, factor = rope_inv_freq(
            self.mla.rope_dim, self.rope_theta, self.rope_yarn
        )
        return apply_rope((x,), positions, inv_freq, factor)[0]

    def _mla_latent(self, h, positions):
        """A latent layer's cached rows for the normed ``h [b, s, d]`` at
        ``positions [b, s]``: ``[kv_scale RMSNorm(c), RoPE(k_pe)]``,
        ``[b, s, row]`` in the compute dtype."""
        mla = self.mla
        c, k_pe = jnp.split(self.wmla_kv_a(h), [mla.kv_rank], axis=-1)
        k_pe = self._mla_rotate(k_pe[:, :, None, :], positions)[:, :, 0]
        c = _scaled(self.mla_kv_norm(c), mla.kv_scale)
        return jnp.concatenate([c, k_pe], axis=-1)

    def _mla_query(self, h, positions):
        """``(q_nope [b, s, heads, nope_dim], q_pe [b, s, heads,
        rope_dim])``, the rotary part rotated."""
        b, s, _ = h.shape
        mla = self.mla
        if mla.q_rank:
            q = self.wmla_q_b(self.mla_q_norm(self.wmla_q_a(h)))
        else:
            q = self.wmla_q(h)
        q = _scaled(q, mla.q_scale).reshape(b, s, self.num_heads, mla.qk_dim)
        q_nope, q_pe = jnp.split(q, [mla.nope_dim], axis=-1)
        return q_nope, self._mla_rotate(q_pe, positions)

    def _mla_kv_b(self):
        """The latent's expansion ``[kv_rank, heads, nope_dim + v_dim]``
        in the compute dtype: ``[..., :nope_dim]`` is ``W_uk``, the rest
        ``W_uv``."""
        mla = self.mla
        return self.mla_kv_b.astype(self.dtype).reshape(
            mla.kv_rank, self.num_heads, mla.nope_dim + mla.v_dim
        )

    def _mla_expanded(self, h, positions, rows, offsets):
        """Latent attention in the EXPANDED form (prefill, extend;
        ``ops/mla.py``): the queries of ``h [b, w, d]`` at ``positions``
        against every head's keys and values expanded from the cached
        ``rows [b, n, row]``, query ``j`` attending rows ``0 ..
        offsets + j``. Returns ``[b, w, heads, v_dim]``."""
        mla = self.mla
        q_nope, q_pe = self._mla_query(h, positions)
        c, k_pe = jnp.split(rows, [mla.kv_rank], axis=-1)
        kv = jnp.einsum("bnr,rhe->bnhe", c, self._mla_kv_b())
        k_nope, v = jnp.split(kv, [mla.nope_dim], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (
                *k_nope.shape[:3], mla.rope_dim
            ))], axis=-1,
        )
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        attend = (
            mla_attention_reference if self.attention == "dense"
            else mla_flash_attention
        )
        return attend(q, k, v, offsets, scale=mla.scale)

    def _mla_absorbed(self, h, positions, pool, page_table, lengths, attn):
        """One decode token a sequence in the ABSORBED form: the query
        multiplied into the latent (``q_nope W_uk^T``, beside ``q_pe``),
        ``attn`` over the pool's rows (``ops/mla.py``), and the latent
        average out through ``W_uv``. Returns ``[b, 1, heads, v_dim]``."""
        mla = self.mla
        q_nope, q_pe = self._mla_query(h, positions)
        w = self._mla_kv_b()
        q_lat = jnp.einsum(
            "bhn,rhn->bhr", q_nope[:, 0], w[..., : mla.nope_dim],
            preferred_element_type=jnp.float32,
        )
        q = jnp.concatenate([q_lat.astype(self.dtype), q_pe[:, 0]], axis=-1)
        o_lat = attn(
            q, pool, page_table, lengths, scale=mla.scale,
            value_dim=mla.kv_rank,
        )
        o = jnp.einsum(
            "bhr,rhv->bhv", o_lat.astype(self.dtype), w[..., mla.nope_dim :],
            preferred_element_type=jnp.float32,
        )
        return o.astype(self.dtype)[:, None]

    def _out(self, x, o, mixed=None, h=None):
        """The residual stream after the mixers: attention's heads ``o``
        (times a sigmoid gate a channel read from the normed ``h``, where
        the block has one) through the output projection, plus the
        state-space mixer's ``mixed`` where the block has one."""
        b, s = o.shape[:2]
        o = o.reshape(b, s, -1)
        if self.attention_gate:
            gate = nn.sigmoid(self.wattn_gate(h).astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(o.dtype)
        x = x + _scaled(self.wproj(o), self.multipliers.attention_out)
        return x if mixed is None else x + mixed

    def _experts(self, h):
        """The experts' sum for the normed ``h [b, s, d]``: the routed
        ones (the chip's share; the identity ones), and a shared one
        where the block has it."""
        b, s, d = h.shape
        routed, load, *zero = sparse_moe(
            h.reshape(b * s, d),
            self.router,
            self.experts_gate, self.experts_up, self.experts_down,
            k=self.experts_per_token,
            held=tuple(self.held_experts) or None,
            renormalize=self.renormalize_experts,
            bias=self.router_bias_ if self.router_bias else None,
            scale=self.routed_scale,
            zero_experts=self.zero_experts,
        )
        routed = routed.reshape(b, s, d)
        if self.shared_expert_dim:
            routed = routed + self.wshared_down(
                self.wshared_up(h) * nn.silu(self.wshared_gate(h))
            )
        # Rows each expert took, and the identity choices where the
        # router has them, for whoever asks (``mutable=["moe_load"]``:
        # the decode engine while tracing).
        for name, count in zip(("tokens_per_expert", "zero_choices"), [load] + zero):
            self.sow(
                "moe_load", name, count,
                init_fn=partial(jnp.zeros_like, count),
                reduce_fn=lambda a, b: a + b,
            )
        return routed

    def _split_carry(self, x):
        """``(stream, carried)`` of what the block is handed. A joining
        block (``moe_join``) is handed ``(stream, m)`` by the branching
        block before it (``moe_branch``), ``m`` the experts' sum it
        carries; every other block the stream alone. That pair is the
        only place the stream is not an array."""
        if self.moe_join:
            stream, carried = x
            return stream, carried
        return x, None

    def _mlp(self, x, carried=None):
        """The MLP half: ``x`` plus the MLP of ``RMSNorm_1(x)``. A
        branching block returns ``(out, m)``, ``m`` the experts' sum on
        the same normed input; a joining block adds the ``carried`` one
        (:meth:`_split_carry`)."""
        h = self.ln2(x)
        branch = self._experts(h) if self.moe_branch else None
        if self.mlp == "moe":
            h = self._experts(h)
        elif self.mlp == "swiglu":
            on_gate, on_out = self.multipliers.mlp or (1.0, 1.0)
            gate = _scaled(self.wgate(h), on_gate)
            h = _scaled(self.wdown(self.wup(h) * nn.silu(gate)), on_out)
        else:
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
        if self.moe_join:
            out = out + carried
        if self.pin_activations:
            out = constrain_batch_sharded(out)
        return out if branch is None else (out, branch)

    def __call__(
        self, x, training: bool, return_kv: bool = False, lengths=None
    ):
        """``return_kv``: also the layer's state to seed a decode from,
        ``(k, v)`` head tensors, for a block with the state-space
        mixer ``(k, v, ssm, conv)`` and for a ``kda`` block ``(kda,
        kda_conv)`` alone, a mixer's state at each sequence's own length
        (``lengths [b]``; None: the whole ``s``)."""
        x, shortcut = self._split_carry(x)
        b, s, _ = x.shape
        h = self.ln1(x)
        if self.kda is not None:
            mixed, state = self._kda(h, lengths=lengths)
            out = self._mlp(x + mixed, shortcut)
            return (out, state) if return_kv else out
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if self.mla is not None:
            # The layer's state is its latent rows, one "head" a token.
            rows = self._mla_latent(h, positions)
            o = self._mla_expanded(
                h, positions, rows, jnp.zeros((b,), jnp.int32)
            )
            out = self._mlp(self._out(x, o, h=h), shortcut)
            return (out, (rows[:, :, None, :],)) if return_kv else out
        q, kh, vh = self._qkv(h, positions)
        attn = _resolve_attention(self.attention)
        o = attn(q, kh, vh, causal=True, **self._attention_kwargs())
        state = (kh, vh)
        mixed = None
        if self.ssm is not None:
            mixed, slot_state = self._ssm(h, lengths=lengths)
            state += slot_state
        out = self._mlp(self._out(x, o, mixed, h), shortcut)
        if return_kv:
            return out, state
        return out

    def decode_paged(
        self, x, layer, page_table, lengths, attention_override=None
    ):
        """One decode step over the page pool (docs/DESIGN.md §20):
        ``x [b, 1, d]`` is the new token's residual stream, ``lengths
        [b]`` the tokens already cached, ``layer`` a pool dict
        (``k``/``v`` ``[num_pages, head_shards, page_size,
        row_width]``, plus scale arrays for int8 pools) shared by EVERY
        slot. The new position's K/V row lands at
        ``(page_table[slot, lengths // page_size], lengths %
        page_size)`` — the indirected write, ``_pool_write_rows`` — and
        the attention reads rows ``0..lengths`` through the table:
        ``attention_override`` when given (the decode engine's kernel),
        else the reference ``ops.pool_decode_attention``. A slot whose
        write target is unallocated (``-1`` table entry, or an inactive
        slot past its pages) drops the write via the OOB page sentinel,
        only ever taken by slots whose output is discarded. Same
        projections and norms as ``__call__``: the weights are
        literally the same submodules."""
        x, shortcut = self._split_carry(x)
        h = self.ln1(x)
        if self.kda is not None:
            # No K/V rows: the layer's whole state is its block a slot,
            # advanced in place for every slot (a dead slot's too).
            names = tuple(self.kda.slot_state(self.dtype))
            mixed, carried = self._kda(
                h, state=tuple(layer[name] for name in names)
            )
            layer = {
                name: leaf.astype(layer[name].dtype)
                for name, leaf in zip(names, carried)
            }
            return self._mlp(x + mixed, shortcut), layer
        page_table = layer_page_table(page_table, bool(self.window))
        pool = layer["c" if self.mla is not None else "k"]
        num_pages, ps = pool.shape[0], pool.shape[2]
        row = jnp.clip(lengths // ps, 0, page_table.shape[1] - 1)
        page = jnp.take_along_axis(page_table, row[:, None], axis=1)[:, 0]
        page = jnp.where(
            (page < 0) | (lengths >= page_table.shape[1] * ps),
            num_pages,
            page,
        )
        off = lengths % ps
        if self.mla is not None:
            rows = self._mla_latent(h, lengths[:, None])
            layer = _pool_write_rows(layer, {"c": rows}, page, off)
            o = self._mla_absorbed(
                h, lengths[:, None], layer["c"], page_table, lengths,
                attention_override or mla_pool_decode_attention,
            )
            return self._mlp(self._out(x, o, h=h), shortcut), layer
        q, k, v = self._qkv(h, lengths[:, None])
        layer = _pool_write_rows(
            layer, {"k": k[:, 0], "v": v[:, 0]}, page, off
        )
        attn = attention_override or pool_decode_attention
        k_scale, v_scale = _pool_scales(layer)
        o = attn(
            q, layer["k"], layer["v"], page_table, lengths,
            k_scale=k_scale, v_scale=v_scale,
            **self._attention_kwargs(pool=True),
        )
        mixed = None
        if self.ssm is not None:
            # Every slot's block of state, advanced in place: a dead
            # slot's too, which the next admission overwrites.
            names = tuple(self.ssm.slot_state(self.dtype))
            mixed, carried = self._ssm(
                h, state=tuple(layer[name] for name in names)
            )
            layer = dict(layer)
            for name, leaf in zip(names, carried):
                layer[name] = leaf.astype(layer[name].dtype)
        return self._mlp(self._out(x, o, mixed, h), shortcut), layer

    def decode_verify_paged(
        self, x, layer, page_table, lengths, valid=None,
        attention_override=None,
    ):
        """The multi-token step (speculative verify, warm-prefix
        extend, prefill chunk; docs/DESIGN.md §18, §20): ``x [b, w,
        d]`` is the residual stream of ``w`` positions (position ``j``
        is the token at sequence index ``lengths + j``). All ``w`` rows
        scatter through the page table in one dispatch (position
        ``lengths + j`` → its table-resolved page/offset, so a window
        crossing a page boundary just lands in two pages), and every
        position attends cache+window causally through
        ``ops.pool_verify_attention`` (row ``j`` sees rows
        ``0..lengths+j``). ``valid [b]`` bounds how many window rows
        are REAL per slot (the warm-prefix extend program's padding
        rows write nowhere — OOB sentinel); None = all ``w`` (the
        speculative verify, whose eligibility check already guarantees
        the pages exist). Rollback-by-length: the caller commits only
        the accepted prefix by advancing ``lengths`` that far; rejected
        rows stay masked garbage."""
        if self.ssm is not None or self.kda is not None:
            raise NotImplementedError(
                "decode_verify_paged is not implemented for a block with "
                "a recurrent mixer: the caller commits a prefix of the "
                "window by advancing `lengths`, which rolls K/V rows back "
                "and cannot roll a recurrence's state back."
            )
        page_table = layer_page_table(page_table, bool(self.window))
        x, shortcut = self._split_carry(x)
        w = x.shape[1]
        pool = layer["c" if self.mla is not None else "k"]
        num_pages, ps = pool.shape[0], pool.shape[2]
        pos = lengths[:, None] + jnp.arange(w)[None, :]
        h = self.ln1(x)
        row = jnp.clip(pos // ps, 0, page_table.shape[1] - 1)
        page = jnp.take_along_axis(page_table, row, axis=1)
        dead = (page < 0) | (pos >= page_table.shape[1] * ps)
        if valid is not None:
            dead = dead | (jnp.arange(w)[None, :] >= valid[:, None])
        page = jnp.where(dead, num_pages, page)
        off = pos % ps
        if self.mla is not None:
            # The window's rows go in first; every query then attends the
            # slot's rows through the table, expanded (ops/mla.py).
            layer = _pool_write_rows(
                layer, {"c": self._mla_latent(h, pos)[:, :, None, :]},
                page, off,
            )
            rows = mla_gathered_rows(layer["c"], page_table, self.mla.row)
            o = self._mla_expanded(h, pos, rows, lengths)
            return self._mlp(self._out(x, o, h=h), shortcut), layer
        q, k, v = self._qkv(h, pos)
        layer = _pool_write_rows(layer, {"k": k, "v": v}, page, off)
        k_scale, v_scale = _pool_scales(layer)
        attn = attention_override or pool_verify_attention
        o = attn(
            q, layer["k"], layer["v"], page_table, lengths,
            k_scale=k_scale, v_scale=v_scale,
            **self._attention_kwargs(pool=True),
        )
        return self._mlp(self._out(x, o, h=h), shortcut), layer


#: What :meth:`TransformerLMModule.serving_leaf` names, below
#: ``params/block<i>``: the dense layers' kernels and the experts'
#: side-by-side ones.
_BLOCK_MATMUL_LEAVES = frozenset(
    [
        (dense, "kernel")
        for dense in (
            "qkv", "proj", "up", "down", "gate", "ssm_in", "ssm_out",
            "attn_gate", "shared_gate", "shared_up", "shared_down",
            "kda_qkv", "kda_f1", "kda_f2", "kda_g1", "kda_g2", "kda_beta",
            "kda_out", "mla_q", "mla_kv_a", "mla_q_a", "mla_q_b",
        )
    ]
    + [
        (name,)
        for name in ("experts_gate", "experts_up", "experts_down", "mla_kv_b")
    ]
)


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
    """The causal LM module. ``setup()``-structured so four methods
    share one weight set and one param tree (names unchanged from the
    original compact layout):

    - ``__call__`` — the full-context forward (training, eval, the
      full-recompute ``greedy_decode`` oracle).
    - ``prefill`` — full-context forward that ALSO returns every
      layer's K/V heads (to seed a decode engine's KV cache) and the
      next-token logits at each sequence's true last position.
    - ``decode_step_paged`` — one token per sequence, attending the
      caller-owned page pool through its page table
      (``ops.pool_decode_attention`` or the engine's kernel).
    - ``decode_verify_paged`` — ``w`` tokens per sequence over the same
      pool (speculative verify, warm-prefix extend, prefill chunks).

    Prefill/decode share weights AND numerics with ``__call__`` by
    construction — same submodules, same einsum/precision discipline —
    which is what the decode-parity certification pins
    (docs/DESIGN.md §15).

    A model with ``ssm`` (a state-space mixer beside attention in every
    block) threads a second kind of state through them
    (docs/DESIGN.md §27): ``prefill`` also returns each layer's
    recurrent state at each sequence's own length, ``decode_step_paged``
    reads and writes it as two more leaves of each cache layer (a fixed
    block a slot), and ``decode_verify_paged`` is refused. What a layer
    keeps follows its kind (docs/DESIGN.md §28): a ``"kda"`` layer of
    ``layer_types`` has linear attention as its only mixer, so its cache
    layer is the block a slot alone and it has no K/V rows
    (:attr:`attention_layers`, :meth:`slot_state_spec`).
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
    # What differs by model (``_Block``'s fields of the same names;
    # every default is the GPT-2 shape):
    num_kv_heads: int = 0
    head_size: int = 0  # 0: d_model // num_heads
    positions: str = "learned"  # "rope", "none": no position table
    rope_theta: float = 10000.0
    rope_yarn: Tuple = ()  # (factor, original_len, beta_fast, beta_slow)
    #: "full", "window" or "kda" a layer; empty: every layer full.
    #: Window layers attend ``window`` keys back with plain rotary
    #: positions, full layers everything with the YaRN-scaled table, a
    #: "kda" layer has linear attention (``kda``) in attention's place.
    layer_types: Tuple = ()
    window: int = 0
    mlp: str = "gelu"  # or "moe"
    num_experts: int = 0
    experts_per_token: int = 0
    expert_dim: int = 0
    tie_embeddings: bool = True
    param_dtype: Any = jnp.float32
    mlp_dim: int = 0
    norm_eps: float = 1e-6
    #: A Mamba-2 state-space mixer beside attention in every block
    #: (``_Block._ssm``); None: none.
    ssm: Optional[SSMSpec] = None
    #: Scalar multipliers; 1.0 (and empty tuples) trace no multiply.
    multipliers: Multipliers = Multipliers()
    #: The "kda" layers' mixer; the attention layers' output gate; the
    #: chip's share ``(first, count)`` of the routed experts; the width
    #: of a shared expert beside them (``_Block``'s fields).
    kda: Optional[KDASpec] = None
    attention_gate: bool = False
    held_experts: Tuple[int, ...] = ()
    shared_expert_dim: int = 0
    #: Latent attention in every layer in place of q/k/v heads (a spec);
    #: the leading layers whose MLP is the dense ``mlp_dim`` SwiGLU in a
    #: model of experts; whether the top-k router weights are
    #: renormalised.
    mla: Optional[MLASpec] = None
    dense_layers: int = 0
    moe_renormalize: bool = True
    #: The router's correction bias, its scale and its identity experts
    #: (``_Block``'s ``router_bias``, ``routed_scale``, ``zero_experts``);
    #: layers in pairs whose first branches the experts off its MLP's
    #: normed input and whose second joins them at its end (LongCat-Flash's
    #: shortcut MoE: every block's MLP is then the dense ``mlp_dim``
    #: SwiGLU).
    moe_router_bias: bool = False
    moe_scale: float = 1.0
    moe_zero_experts: int = 0
    moe_shortcut: bool = False

    def setup(self):
        # A serving tree may hold the tables :meth:`_embed` gathers from
        # with rows of whole lane tiles (:meth:`serving_leaf`): ``embed``
        # only where the head has a table of its own, which for a tied
        # head is the table as bound under ``tied_head`` (``init`` never
        # makes it).
        own_head = not self.tie_embeddings or self.has_variable(
            "params", "tied_head"
        )
        self.embed = _param(
            self,
            "embed",
            nn.initializers.normal(0.02),
            (self.vocab_size, self.d_model),
            self.param_dtype,
            held_shape=(self.vocab_size, self.table_row_width)
            if own_head
            else None,
        )
        if self.tie_embeddings:
            self.tied_head = (
                _param(
                    self, "tied_head", None,
                    (self.vocab_size, self.d_model), self.param_dtype,
                )
                if own_head
                else self.embed
            )
        rope = self.positions == "rope"
        if self.positions == "learned":
            self.pos = _param(
                self,
                "pos",
                nn.initializers.normal(0.02),
                (self.max_seq_len, self.d_model),
                self.param_dtype,
                held_shape=(self.max_seq_len, self.table_row_width),
            )
        if not self.tie_embeddings:
            self.head = _param(
                self,
                "head",
                nn.initializers.lecun_normal(),
                (self.d_model, self.vocab_size),
                self.param_dtype,
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
                num_kv_heads=self.num_kv_heads,
                head_dim=self.head_size,
                rope_theta=self.rope_theta if rope else 0.0,
                rope_yarn=() if windowed else self.rope_yarn,
                window=self.window if windowed else 0,
                mlp=(
                    "swiglu" if i < self.dense_layers or self.moe_shortcut
                    else self.mlp
                ),
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                expert_dim=self.expert_dim,
                param_dtype=self.param_dtype,
                mlp_dim=self.mlp_dim,
                norm_eps=self.norm_eps,
                ssm=self.ssm,
                multipliers=self.multipliers,
                kda=None if attends else self.kda,
                attention_gate=self.attention_gate,
                held_experts=self.held_experts,
                shared_expert_dim=self.shared_expert_dim,
                mla=self.mla,
                renormalize_experts=self.moe_renormalize,
                router_bias=self.moe_router_bias,
                routed_scale=self.moe_scale,
                zero_experts=self.moe_zero_experts,
                moe_branch=self.moe_shortcut and i % 2 == 0,
                moe_join=self.moe_shortcut and i % 2 == 1,
                name=f"block{i}",
            )
            for i, (windowed, attends) in enumerate(
                zip(self.window_layers, self.attention_layers)
            )
        ]
        self.final_norm = RMSNorm(
            dtype=self.dtype, param_dtype=self.param_dtype, eps=self.norm_eps,
            name="RMSNorm_0",
        )

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def table_row_width(self) -> int:
        """``d_model`` rounded up to whole 128-lane tiles: the row a
        serving tree holds the gathered tables in (GPT-2 XL's 1600 ->
        1664), the rule of the page pool's rows."""
        return kv_row_width(1, self.d_model)

    @property
    def window_layers(self) -> Tuple[bool, ...]:
        """Per layer, whether it is a sliding-window layer."""
        if not self.layer_types:
            return (False,) * self.num_layers
        return tuple(t == "window" for t in self.layer_types)

    @property
    def attention_layers(self) -> Tuple[bool, ...]:
        """Per layer, whether it has softmax attention and so K/V rows a
        token; a "kda" layer has neither."""
        if not self.layer_types:
            return (True,) * self.num_layers
        return tuple(t != "kda" for t in self.layer_types)

    def _embed(self, tokens, positions=None):
        """The residual stream's start: the token table's rows, plus
        the position table's where the model has one (rotary positions
        act inside the blocks). ``positions`` None: a whole sequence
        from 0, the table's leading slice. A table held with padded
        rows (:meth:`serving_leaf`) gives its first ``d_model`` columns;
        of one held as bound that slice is the rows themselves."""
        d = self.d_model
        x = self.embed[tokens][..., :d]
        x = _scaled(x, self.multipliers.embedding)
        if self.positions == "learned":
            if positions is None:
                x = x + self.pos[None, : tokens.shape[1], :d]
            else:
                x = x + self.pos[
                    jnp.clip(positions, 0, self.max_seq_len - 1)
                ][..., :d]
        return x.astype(self.dtype)

    def _pin(self) -> bool:
        return _auto_pin_activations(self.attention, self.pin_activations)

    def _logits(self, x):
        x = self.final_norm(x)
        if not self.tie_embeddings:
            # A head of its own, multiplied as it is held (no float32
            # copy of a table that may be half a gigabyte), float32 out.
            logits = jnp.einsum(
                "bsd,dv->bsv", x, self.head.astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
            return _scaled(logits, self.multipliers.lm_head)
        # Weight-tied LM head: logits in fp32 (the loss reduction dtype).
        return jnp.einsum(
            "bsd,vd->bsv",
            x.astype(jnp.float32),
            self.tied_head.astype(jnp.float32),
        )

    def serving_leaf(self, path, leaf):
        """The rule of :meth:`serving_variables` for one leaf at its
        ``jax.tree_util`` key path. Two things are held otherwise than
        bound, each for what the chip does with it on every call.

        Cast to the compute dtype: exactly the
        leaves every traced method reads as ``leaf.astype(self.dtype)``
        into a matmul and in no other type — the ``kernel`` of a block's
        dense layers (``qkv``, ``proj``, ``up``, ``down``, ``gate``,
        ``ssm_in``, ``ssm_out``: ``promote_dtype`` in :class:`_Dense`), a
        block's ``experts_gate``
        / ``experts_up`` / ``experts_down`` (``ops/moe.py``:
        ``rhs.astype(lhs.dtype)``) and an untied ``head``
        (:meth:`_logits`). A leaf already in the
        compute dtype is returned as the same array, and so is one the
        compute dtype would widen (the program's convert reads fewer
        bytes than a held copy would).

        Rows padded with zeros to whole 128-lane tiles
        (:attr:`table_row_width`): ``embed`` and ``pos``, the tables
        :meth:`_embed` indexes by row. The TPU holds a 2-D array in
        the layout that wastes fewest tile bytes, which for rows of 12.5
        tiles (GPT-2 XL's 1600) is the one whose rows are not contiguous,
        and every program that gathers rows then re-lays the whole table
        out first (docs/DESIGN.md §15). A width of whole tiles is the
        same array.

        Everything else read in float32 stays as it is: every norm's
        ``scale``, the ``router``, the state-space mixer's convolution
        and per-head vectors, and ``tied_head``
        (:meth:`serving_tree`)."""
        names = tuple(str(getattr(k, "key", k)) for k in path)
        if names in (("params", "embed"), ("params", "pos")):
            pad = self.table_row_width - leaf.shape[-1]
            return jnp.pad(leaf, ((0, 0), (0, pad))) if pad else leaf
        matmul_only = names == ("params", "head") or (
            len(names) > 2
            and names[0] == "params"
            and names[1].startswith("block")
            and names[2:] in _BLOCK_MATMUL_LEAVES
        )
        dtype = jnp.dtype(self.dtype)
        if not matmul_only or dtype.itemsize >= leaf.dtype.itemsize:
            return leaf
        return leaf.astype(dtype)

    def serving_tree(self, variables):
        """The tree :meth:`serving_leaf` is mapped over: ``variables``,
        and where a tied head's table has rows that :meth:`serving_leaf`
        pads, the same array a second time as ``params/tied_head``. The
        head multiplies the table as bound (the layout it arrives in is
        the one its matmul reads in place), the gather reads the padded
        one: two uses, two homes."""
        if not self.tie_embeddings or self.d_model == self.table_row_width:
            return variables
        params = variables["params"]
        return {
            **variables, "params": {**params, "tied_head": params["embed"]}
        }

    def slot_state_spec(
        self,
    ) -> Tuple[Dict[str, Tuple[Tuple[int, ...], Any]], ...]:
        """What each layer keeps a sequence as a fixed block (not rows a
        token), a layer: ``{name: (shape, dtype)}`` in the order
        ``prefill`` returns the leaves (after ``k`` and ``v`` where the
        layer has them, :attr:`attention_layers`), which are also the
        names ``decode_step_paged`` reads and writes in a cache layer.
        ``{}`` for a layer without a recurrent mixer. The cache manager
        is generic over this."""
        beside = {} if self.ssm is None else self.ssm.slot_state(self.dtype)
        return tuple(
            beside if attends else self.kda.slot_state(self.dtype)
            for attends in self.attention_layers
        )

    def serving_variables(self, variables):
        """The tree the serving methods (``prefill``,
        ``decode_step_paged``, ``decode_verify_paged``) should be given
        for ``variables``: each matmul kernel held once in the type the
        programs multiply in and each gathered table with rows of whole
        lane tiles, so that no compiled program converts or re-lays one
        again on every call (:meth:`serving_leaf` has the rule). The
        values every matmul and every sum sees are the same either way,
        so every output is bit for bit what ``variables`` gives. Where
        the parameters already are the compute dtype and ``d_model`` is
        whole tiles this is the tree it was given."""
        return jax.tree_util.tree_map_with_path(
            self.serving_leaf, self.serving_tree(variables)
        )

    def _backbone(
        self, tokens, training: bool, collect_kv: bool, lengths=None
    ):
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
        x = self._embed(tokens)
        if self._pin():
            x = constrain_batch_sharded(x)
        kv = []
        for block in self.blocks:
            if collect_kv:
                x, layer_kv = block(
                    x, training, return_kv=True, lengths=lengths
                )
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
        cache; a model with the state-space mixer returns ``(k, v, ssm,
        conv)`` a layer and a "kda" layer ``(kda, kda_conv)`` alone, the
        mixer's state after each sequence's LAST
        REAL token (the leaves of :meth:`slot_state_spec`, in its order:
        right padding does not advance the recurrence), for the caller
        to write at the sequence's slot. Numerically the same program as ``__call__`` —
        the first emitted token is the full-context oracle's."""
        x, kv = self._backbone(
            tokens, False, collect_kv=True,
            lengths=lengths if any(self.slot_state_spec()) else None,
        )
        # The head reads the one row that is asked for: every row's
        # logits would be [s, vocab] float32 a sequence.
        idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        return self._logits(last)[:, 0], tuple(kv)

    def decode_step_paged(
        self, tokens, lengths, cache, page_table, attention_override=None
    ):
        """One incremental token per sequence over a SHARED page pool
        (docs/DESIGN.md §20). ``tokens [b] int`` are the CURRENT input
        tokens (each sits at position ``lengths``), ``cache`` is a
        per-layer tuple of pool dicts (``k``/``v`` ``[num_pages,
        head_shards, page_size, row_width]``, plus
        ``k_scale``/``v_scale`` for int8 pools), ``page_table [b,
        max_pages] int32`` resolves each sequence's logical pages.
        Returns ``(logits [b, vocab], new_cache)`` — the caller owns
        length bookkeeping and feeds ``argmax(logits)`` back as the
        next step's ``tokens``; the new K/V row is written (through
        the table) before attending. ``attention_override``
        (``callable(q, k_pool, v_pool, page_table, lengths, *,
        k_scale=None, v_scale=None)``) selects the attention for THIS
        trace — the seam the decode engine threads its kernel (or the
        mesh-composed sharded wrapper) through without rebuilding the
        module; None is the reference."""
        if len(cache) != self.num_layers:
            raise ValueError(
                f"cache has {len(cache)} layers, model has "
                f"{self.num_layers}."
            )
        x = self._embed(tokens, lengths)[:, None, :]
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
        """``w`` tokens per sequence over the page pool in ONE
        dispatch — the speculative-decode verify/append program
        (docs/DESIGN.md §18). ``tokens [b, w] int`` are the window's
        input tokens (token ``j`` sits at position ``lengths + j``);
        they scatter through the page table (windows cross page
        boundaries freely). Returns ``(logits [b, w, vocab],
        new_cache)``; ``logits[:, j]`` is the next-token distribution
        AFTER consuming token ``j`` — the verify scores for greedy
        acceptance. The caller owns length bookkeeping: advancing
        ``lengths`` by only the accepted prefix is the whole rollback
        contract (rejected rows stay at ``j >= length`` where every
        attention path masks them). Positions past the table clamp
        like ``decode_step_paged``'s — the scheduler never COMMITS past
        ``token_limit``, so a clamped row is never attended. At ``w ==
        1`` this computes exactly what ``decode_step_paged`` computes.
        ALSO the warm-prefix extend program (docs/DESIGN.md §20): a
        prompt whose prefix is cache-resident enters here with the
        SUFFIX as the window (``valid [b]`` = true suffix lengths;
        padding rows write nowhere), each suffix position attending the
        shared prefix pages it never recomputed — which is the entire
        TTFT win."""
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
        x = self._embed(tokens, lengths[:, None] + jnp.arange(w)[None, :])
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
    #: Positional-table capacity. -1 (the default) sizes it to the
    #: sequence length ``build()`` receives — the common case, and it
    #: keeps one ``seq_len`` knob sufficient in CLI tasks. Set
    #: explicitly to train short now and run longer contexts later
    #: without a table reshape; build() raises if the configured
    #: sequence exceeds an explicit capacity.
    max_seq_len: int = Field(-1)
    # -- what differs by model; every default is the GPT-2 shape --------
    #: Key/value heads (grouped-query attention: query head ``j`` reads
    #: key/value head ``j // (num_heads / num_kv_heads)``). -1: as many
    #: as ``num_heads``.
    num_kv_heads: int = Field(-1)
    #: Size of one head. -1: ``d_model / num_heads``; set it where the
    #: heads' total is not the model's width (32 x 128 on 2304).
    head_dim: int = Field(-1)
    #: "learned" (a position table added to the embedding), "rope"
    #: (rotary positions on q and k over the whole head, rotate-half) or
    #: "none" (no table and no rotation: the order comes from the causal
    #: mask and from recurrent layers).
    positions: str = Field("learned")
    rope_theta: float = Field(10000.0)
    #: YaRN scaling of the FULL layers' rotary table; 1.0 is plain RoPE.
    #: Window layers always take the plain table.
    yarn_factor: float = Field(1.0)
    yarn_original_len: int = Field(8192)
    yarn_beta_fast: float = Field(32.0)
    yarn_beta_slow: float = Field(1.0)
    #: One of "full" / "window" / "kda" a layer, repeated cyclically to
    #: ``num_layers`` (so a period is enough); empty: every layer full.
    #: A "kda" layer has gated delta-rule linear attention (``kda_*``)
    #: as its only mixer and keeps no K/V rows.
    layer_types: Sequence[str] = Field(())
    #: Keys a window layer attends, the query's own included.
    window: int = Field(0)
    #: "gelu" (the dense MLP of ``mlp_ratio``) or "moe" (sparse SwiGLU
    #: experts: ``num_experts`` of width ``expert_dim``, the
    #: ``experts_per_token`` largest router weights renormalised).
    mlp: str = Field("gelu")
    num_experts: int = Field(0)
    experts_per_token: int = Field(0)
    expert_dim: int = Field(0)
    #: The chip's share of the routed experts, ``(first, count)``: the
    #: router scores all ``num_experts``, the expert leaves hold those
    #: ``count`` and the layer's result is the part they give
    #: (``ops/moe.py``). Empty: all of them.
    held_experts: Sequence[int] = Field(())
    #: Width of a shared SwiGLU expert every token takes beside the
    #: routed ones (``mlp="moe"``); 0: none.
    shared_expert_dim: int = Field(0)
    #: A sigmoid gate a channel on attention's heads before the output
    #: projection, read from the block's normed input.
    attention_gate: bool = Field(False)
    #: The "kda" layers' mixer (``ops/kda.py``): ``kda_heads`` heads of
    #: ``kda_head_dim`` keys and values, a causal convolution of
    #: ``kda_conv_taps`` taps on q, k and v, low-rank decay and output
    #: gates of ``kda_gate_rank``, prefilled in chunks of ``kda_chunk``;
    #: ``kda_neg_eigval``: beta in (0, 2), else (0, 1).
    kda_heads: int = Field(0)
    kda_head_dim: int = Field(0)
    kda_conv_taps: int = Field(4)
    kda_gate_rank: int = Field(128)
    kda_chunk: int = Field(64)
    kda_neg_eigval: bool = Field(True)
    #: False: a head of its own beside the embedding.
    tie_embeddings: bool = Field(True)
    #: The type the parameters are held in ("bfloat16" for a model
    #: published and served that way: half the bytes, read as they are).
    param_dtype: str = Field("float32")
    #: Width of the dense gated MLP (``mlp="swiglu"``: ``down(up(h) *
    #: silu(gate(h)))``).
    mlp_dim: int = Field(0)
    #: Epsilon of every RMSNorm.
    norm_eps: float = Field(1e-6)
    #: A Mamba-2 state-space mixer beside attention in every block, on
    #: the same normed input, its output added to the stream with
    #: attention's: ``ssm_heads`` heads of ``ssm_head_dim`` with a state
    #: of ``ssm_state`` a channel, ``B`` and ``C`` shared by the heads of
    #: each of ``ssm_groups`` groups, a causal convolution of
    #: ``SSM_CONV_TAPS`` taps, prefilled in chunks of ``ssm_chunk`` tokens.
    #: 0 heads: no mixer.
    ssm_heads: int = Field(0)
    ssm_head_dim: int = Field(0)
    ssm_state: int = Field(0)
    ssm_groups: int = Field(1)
    ssm_chunk: int = Field(128)
    #: Scalar multipliers (maximal-update parametrisation): on the
    #: embedding, the logits, attention's output, the state-space
    #: mixer's input and output, the keys (before the rotation), the five
    #: segments of the state-space projection (gate, x, B, C, dt) and the
    #: gated MLP (its gate, its output). 1.0, and an empty sequence,
    #: trace nothing.
    embedding_multiplier: float = Field(1.0)
    lm_head_multiplier: float = Field(1.0)
    attention_out_multiplier: float = Field(1.0)
    key_multiplier: float = Field(1.0)
    ssm_in_multiplier: float = Field(1.0)
    ssm_out_multiplier: float = Field(1.0)
    ssm_multipliers: Sequence[float] = Field(())
    mlp_multipliers: Sequence[float] = Field(())
    #: Multi-head latent attention in every layer (DeepSeek-V2,
    #: LongCat-Flash; ``MLASpec``): a ``mla_kv_rank``-wide latent a token,
    #: heads of ``mla_nope_dim`` + ``mla_rope_dim`` query/key and
    #: ``mla_v_dim`` value, the rotary key shared by the heads. 0: none.
    mla_kv_rank: int = Field(0)
    mla_nope_dim: int = Field(0)
    mla_rope_dim: int = Field(0)
    mla_v_dim: int = Field(0)
    #: DeepSeek's YaRN pair ``(mscale, mscale_all_dim)``: given (both >
    #: 0), cos and sin take ``m(mscale) / m(mscale_all_dim)`` and the
    #: softmax scale ``m(mscale_all_dim) ** 2``; 0 (a config without
    #: them), cos and sin take ``0.1 ln(yarn_factor) + 1``
    #: (``rope_inv_freq``).
    yarn_mscale: float = Field(0.0)
    yarn_mscale_all_dim: float = Field(0.0)
    #: The leading layers of a model of experts whose MLP is the dense
    #: SwiGLU of ``mlp_dim`` (a config's ``first_k_dense_replace``).
    dense_layers: int = Field(0)
    #: Whether the top-k router weights are renormalised to sum 1 (a
    #: config's ``norm_topk_prob``); False uses the softmax's own.
    moe_renormalize: bool = Field(True)
    #: The query's low rank (a config's ``q_lora_rank``; 0: one
    #: projection), with an RMSNorm between its two halves.
    mla_q_rank: int = Field(0)
    #: LongCat-Flash's ``mla_scale_q_lora`` and ``mla_scale_kv_lora``:
    #: the whole query times ``sqrt(d_model / mla_q_rank)`` and the normed
    #: latent times ``sqrt(d_model / mla_kv_rank)``.
    mla_lora_scales: bool = Field(False)
    #: A correction bias a router column that joins the scores in the
    #: choice of the top-k and not in their weights (DeepSeek-V3's and
    #: LongCat-Flash's ``e_score_correction_bias``).
    moe_router_bias: bool = Field(False)
    #: What the routed weights are multiplied by (``routed_scaling_factor``).
    moe_scale: float = Field(1.0)
    #: Identity ("zero-computation") experts past ``num_experts``: router
    #: columns whose expert's output is its input (``ops/moe.py``).
    moe_zero_experts: int = Field(0)
    #: LongCat-Flash's double layer: layers in pairs, each with its dense
    #: SwiGLU of ``mlp_dim``; the first of a pair also routes its MLP's
    #: normed input through the experts and the second adds their sum at
    #: its end. ``num_layers`` counts the sub-layers (twice the model's).
    moe_shortcut: bool = Field(False)

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
        num_kv_heads = (
            self.num_heads if self.num_kv_heads == -1 else self.num_kv_heads
        )
        if self.head_dim == -1:
            if self.d_model % self.num_heads != 0:
                raise ValueError(
                    f"d_model={self.d_model} not divisible by "
                    f"num_heads={self.num_heads}; set head_dim for heads "
                    "whose total is not the model's width."
                )
            head_dim = self.d_model // self.num_heads
        else:
            head_dim = self.head_dim
        if head_dim < 1 or num_kv_heads < 1 or (
            self.num_heads % num_kv_heads
        ):
            raise ValueError(
                f"num_heads={self.num_heads}, num_kv_heads={num_kv_heads}, "
                f"head_dim={head_dim}: the query heads must be a multiple "
                "of the key/value heads."
            )
        if self.positions not in ("learned", "rope", "none"):
            raise ValueError(
                f"positions={self.positions!r}: expected 'learned', "
                "'rope' or 'none'."
            )
        if self.positions == "rope" and head_dim % 2:
            raise ValueError(f"rope needs an even head_dim, got {head_dim}.")
        period = tuple(str(t) for t in self.layer_types)
        if any(t not in ("full", "window", "kda") for t in period):
            raise ValueError(
                f"layer_types={period!r}: each is 'full', 'window' or "
                "'kda'."
            )
        layer_types = tuple(
            period[i % len(period)] for i in range(self.num_layers)
        ) if period else ()
        if "window" in layer_types and self.window < 1:
            raise ValueError("window layers need window >= 1.")
        if "kda" in layer_types and (
            min(
                self.kda_heads, self.kda_head_dim, self.kda_gate_rank,
                self.kda_chunk,
            ) < 1
            or self.kda_conv_taps < 2
        ):
            raise ValueError(
                "kda layers need kda_heads, kda_head_dim, kda_gate_rank, "
                "kda_chunk >= 1 and kda_conv_taps >= 2."
            )
        if "kda" in layer_types and self.ssm_heads:
            raise ValueError(
                "a state-space mixer beside kda layers is not implemented."
            )
        held = tuple(int(n) for n in self.held_experts)
        if held and not (
            self.mlp == "moe" and len(held) == 2
            and 0 <= held[0] and 1 <= held[1]
            and held[0] + held[1] <= self.num_experts
        ):
            raise ValueError(
                f"held_experts={held!r}: expected (first, count) inside "
                f"mlp='moe''s num_experts ({self.num_experts})."
            )
        if self.shared_expert_dim and self.mlp != "moe":
            raise ValueError("shared_expert_dim needs mlp='moe'.")
        if self.mlp not in ("gelu", "moe", "swiglu"):
            raise ValueError(
                f"mlp={self.mlp!r}: expected 'gelu', 'moe' or 'swiglu'."
            )
        if self.mlp == "swiglu" and self.mlp_dim < 1:
            raise ValueError("mlp='swiglu' needs mlp_dim >= 1.")
        if self.dense_layers and not (
            self.mlp == "moe" and self.mlp_dim >= 1
            and 0 < self.dense_layers < self.num_layers
        ):
            raise ValueError(
                f"dense_layers={self.dense_layers} needs mlp='moe', mlp_dim "
                f">= 1 and fewer than num_layers ({self.num_layers})."
            )
        mla = None
        if self.mla_kv_rank:
            if min(self.mla_nope_dim, self.mla_rope_dim, self.mla_v_dim) < 1 or (
                self.mla_rope_dim % 2
            ):
                raise ValueError(
                    "latent attention needs mla_nope_dim, mla_v_dim >= 1 and "
                    "an even mla_rope_dim >= 2."
                )
            if self.positions != "rope" or layer_types or self.ssm_heads or (
                attention not in ("flash", "dense")
            ):
                raise ValueError(
                    "latent attention (mla_kv_rank > 0) is implemented with "
                    "positions='rope', every layer full (no layer_types), no "
                    "state-space mixer and attention 'flash' or 'dense'."
                )
        if self.mla_q_rank and not self.mla_kv_rank:
            raise ValueError("mla_q_rank needs latent attention (mla_kv_rank).")
        if self.mla_lora_scales and not self.mla_q_rank:
            raise ValueError("mla_lora_scales needs mla_q_rank.")
        if (
            self.moe_router_bias or self.moe_zero_experts or self.moe_shortcut
            or self.moe_scale != 1.0
        ) and self.mlp != "moe":
            raise ValueError(
                "moe_router_bias, moe_scale, moe_zero_experts and "
                "moe_shortcut need mlp='moe'."
            )
        if self.moe_shortcut and (
            self.num_layers % 2 or self.dense_layers or self.mlp_dim < 1
        ):
            raise ValueError(
                "moe_shortcut pairs the layers: it needs an even num_layers "
                f"({self.num_layers}), no dense_layers and mlp_dim >= 1."
            )
        if self.lm_head_multiplier != 1.0 and self.tie_embeddings:
            raise ValueError(
                "lm_head_multiplier needs a head of its own "
                "(tie_embeddings=false)."
            )
        if self.ssm_heads:
            if min(
                self.ssm_head_dim, self.ssm_state, self.ssm_groups,
                self.ssm_chunk,
            ) < 1 or self.ssm_heads % self.ssm_groups:
                raise ValueError(
                    f"ssm_heads={self.ssm_heads} needs ssm_head_dim, "
                    "ssm_state, ssm_chunk >= 1 and ssm_groups "
                    f"({self.ssm_groups}) dividing the heads."
                )
            if "window" in layer_types:
                raise ValueError(
                    "a state-space mixer beside window layers is not "
                    "implemented."
                )
        for name, want in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            if len(getattr(self, name)) not in (0, want):
                raise ValueError(
                    f"{name}={getattr(self, name)!r}: expected {want} "
                    "numbers or none."
                )
        if self.mlp == "moe" and not (
            1 <= self.experts_per_token <= self.num_experts
            and self.expert_dim >= 1
        ):
            raise ValueError(
                f"mlp='moe' needs 1 <= experts_per_token "
                f"({self.experts_per_token}) <= num_experts "
                f"({self.num_experts}) and expert_dim >= 1 "
                f"({self.expert_dim})."
            )
        rope_yarn = ()
        if self.positions == "rope" and self.yarn_factor != 1.0:
            rope_yarn = (
                float(self.yarn_factor), int(self.yarn_original_len),
                float(self.yarn_beta_fast), float(self.yarn_beta_slow),
            )
            if (self.yarn_mscale > 0) != (self.yarn_mscale_all_dim > 0):
                raise ValueError(
                    "yarn_mscale and yarn_mscale_all_dim are given together."
                )
            if self.yarn_mscale > 0:
                rope_yarn += (
                    float(self.yarn_mscale), float(self.yarn_mscale_all_dim)
                )
        if self.mla_kv_rank:
            qk = self.mla_nope_dim + self.mla_rope_dim
            lora = self.mla_lora_scales
            mla = MLASpec(
                kv_rank=int(self.mla_kv_rank),
                nope_dim=int(self.mla_nope_dim),
                rope_dim=int(self.mla_rope_dim),
                v_dim=int(self.mla_v_dim),
                scale=qk ** -0.5 * yarn_score_factor(rope_yarn),
                q_rank=int(self.mla_q_rank),
                q_scale=(self.d_model / self.mla_q_rank) ** 0.5 if lora else 1.0,
                kv_scale=(self.d_model / self.mla_kv_rank) ** 0.5 if lora else 1.0,
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
            num_kv_heads=num_kv_heads,
            head_size=head_dim,
            positions=self.positions,
            rope_theta=float(self.rope_theta),
            rope_yarn=rope_yarn,
            layer_types=layer_types,
            window=int(self.window),
            mlp=self.mlp,
            num_experts=int(self.num_experts),
            experts_per_token=int(self.experts_per_token),
            expert_dim=int(self.expert_dim),
            tie_embeddings=bool(self.tie_embeddings),
            param_dtype=jnp.dtype(self.param_dtype),
            mlp_dim=int(self.mlp_dim),
            norm_eps=float(self.norm_eps),
            ssm=SSMSpec(
                heads=int(self.ssm_heads),
                head_dim=int(self.ssm_head_dim),
                state=int(self.ssm_state),
                groups=int(self.ssm_groups),
                chunk=int(self.ssm_chunk),
                in_multiplier=float(self.ssm_in_multiplier),
                out_multiplier=float(self.ssm_out_multiplier),
                multipliers=tuple(float(m) for m in self.ssm_multipliers),
            ) if self.ssm_heads else None,
            multipliers=Multipliers(
                embedding=float(self.embedding_multiplier),
                lm_head=float(self.lm_head_multiplier),
                attention_out=float(self.attention_out_multiplier),
                key=float(self.key_multiplier),
                mlp=tuple(float(m) for m in self.mlp_multipliers),
            ),
            kda=KDASpec(
                heads=int(self.kda_heads),
                head_dim=int(self.kda_head_dim),
                conv_taps=int(self.kda_conv_taps),
                gate_rank=int(self.kda_gate_rank),
                chunk=int(self.kda_chunk),
                neg_eigval=bool(self.kda_neg_eigval),
            ) if "kda" in layer_types else None,
            attention_gate=bool(self.attention_gate),
            held_experts=held,
            shared_expert_dim=int(self.shared_expert_dim),
            mla=mla,
            dense_layers=int(self.dense_layers),
            moe_renormalize=bool(self.moe_renormalize),
            moe_router_bias=bool(self.moe_router_bias),
            moe_scale=float(self.moe_scale),
            moe_zero_experts=int(self.moe_zero_experts),
            moe_shortcut=bool(self.moe_shortcut),
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
