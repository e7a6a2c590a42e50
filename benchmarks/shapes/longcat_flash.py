"""Operations and bytes of the LongCat-Flash-shaped decoder (double layers:
two latent-attention sub-layers and two dense SwiGLUs a layer, and one
shortcut MoE of real and identity experts a layer, this chip holding a
share of the real ones; a head of its own), from shapes alone.

With ``d = hidden_size``, ``H = num_attention_heads``, ``Q =
q_lora_rank``, ``n = qk_nope_head_dim``, ``p = qk_rope_head_dim``, ``v =
v_head_dim``, ``r = kv_lora_rank``, ``q = n + p`` (a head's query/key
width), ``R = r + p`` (a token's cached latent row), ``I =
ffn_hidden_size``, ``f = expert_ffn_hidden_size``, ``E = router_experts``
(the real experts the router scores), ``Z = zero_expert_num`` (its
identity experts), ``e = held_experts[1]`` (the real experts held here),
``k = moe_topk``, ``V = vocab_size``, ``L = num_layers`` LongCat layers as
run (``2 L`` sub-layers):

- matrix products of one token through a sub-layer's attention: the
  query's low rank ``d x Q`` and its expansion ``Q x H q``, the latent
  with the rotary key ``d x R``, the latent's expansion to every head's key
  and value ``r x H (n + v)``, the output ``H v x d``: ``2 (d Q + Q H q + d
  R + r H (n + v) + H v d)``. The decode step runs the expansion absorbed
  (``q_nope W_uk^T`` and ``o_lat W_uv``): the same count;
- through a sub-layer's dense SwiGLU: ``6 d I``;
- through a layer's MoE: the router ``2 d (E + Z)``, and ``6 d f`` a held
  choice. A token's choices that fall to the held experts are ``k e / (E +
  Z)`` under even routing (``12 x 16 / 768 = 0.25`` here; the run's own
  share is the counter ``moe_held_choice_share``); the identity choices
  (``k Z / (E + Z)`` = 4) cost a multiply-add an element, no product, and
  are left out;
- the head of its own: ``2 d V`` for each position whose logits are needed
  (every output token, the last position of each prompt);
- attention, the model's own count (the EXPANDED form): a token that
  attends ``c`` rows (its own included) takes ``2 c H (q + v)`` a
  sub-layer, and a window of positions ``cached .. m - 1`` of a sequence
  ``H (q + v) (m (m + 1) - cached (cached + 1))`` a sub-layer (causal);
- the ABSORBED decode kernel's own work for a token over ``c`` rows:
  scores over the whole row ``2 c H R`` and the latent average ``2 c H r``
  a sub-layer;
- the norms, SiLU, the rotation, the softmax, the sort of the routed pairs,
  the identity experts and the residual adds are a few operations per
  element and are left out: the counts are a lower bound of the needed
  work;
- the cache: ONE latent row of ``R`` values a token a sub-layer, 2 bytes
  each (the pool pads it to whole 128-lane registers, 576 -> 640; the
  padding is the kernel's waste and is not counted);
- bytes the decode kernel must move for a slot over ``c`` rows a
  sub-layer: the rows once (``2 R c``), its query in (``2 H R``) and its
  latent average out (float32, ``4 H r``);
- bytes the prefill kernel must move for a window of ``w`` queries over
  ``m`` rows a sub-layer: queries in and outputs out (``2 w H (q + v)``),
  the expanded keys and values once (``2 m H (q + v)``).
"""

from typing import Dict, Iterable


def dims(model: Dict):
    """``(d, L, V)``: the width, the LongCat layers as run, the vocabulary."""
    return (
        int(model["hidden_size"]), int(model["num_layers"]), int(model["vocab_size"]),
    )


def sublayers(model: Dict) -> int:
    """``2 L``: the latent-attention sub-layers (and dense SwiGLUs)."""
    return 2 * dims(model)[1]


def mla_sizes(model: Dict):
    """``(H, Q, n, p, v, r)``."""
    return (
        int(model["num_attention_heads"]), int(model["q_lora_rank"]),
        int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"]),
        int(model["v_head_dim"]), int(model["kv_lora_rank"]),
    )


def latent_row(model: Dict) -> int:
    """``R = r + p``: a token's cached values a sub-layer."""
    _, _, _, p, _, r = mla_sizes(model)
    return r + p


def router_width(model: Dict) -> int:
    """``E + Z``: the router's columns, real and identity."""
    return int(model["router_experts"]) + int(model["zero_expert_num"])


def held(model: Dict) -> int:
    """``e``: the real experts held here."""
    return int(model["held_experts"][1])


def attention_weight_elements(model: Dict) -> float:
    """One sub-layer's attention matrices."""
    d, _, _ = dims(model)
    h, qr, n, p, v, r = mla_sizes(model)
    return d * qr + qr * h * (n + p) + d * (r + p) + r * h * (n + v) + h * v * d


def dense_weight_elements(model: Dict) -> float:
    """Every matrix of the stack outside the experts and the head: each
    sub-layer's attention and dense SwiGLU, each layer's router."""
    d, layers, _ = dims(model)
    ffn = 3 * d * int(model["ffn_hidden_size"])
    return sublayers(model) * (attention_weight_elements(model) + ffn) + (
        layers * d * router_width(model)
    )


def expert_elements(model: Dict) -> float:
    """One real expert's three matrices: ``3 d f``."""
    d, _, _ = dims(model)
    return 3.0 * d * int(model["expert_ffn_hidden_size"])


def expert_ops_per_choice(model: Dict) -> float:
    """One held expert for one token: ``6 d f``."""
    return 2.0 * expert_elements(model)


def expert_bytes(model: Dict, choices: float, bytes_per_element: int = 2) -> float:
    """One layer's held expert matrices that ``choices`` held choices can
    touch: ``min(e, choices)`` experts of ``3 d f`` elements."""
    return min(float(held(model)), choices) * expert_elements(model) * bytes_per_element


def held_choices_per_token(model: Dict) -> float:
    """``k e / (E + Z)``: a token's choices that fall to the held experts
    under even routing."""
    return int(model["moe_topk"]) * held(model) / router_width(model)


def matmul_ops_per_token(model: Dict) -> float:
    _, layers, _ = dims(model)
    routed = layers * held_choices_per_token(model) * expert_ops_per_choice(model)
    return 2.0 * dense_weight_elements(model) + routed


def head_ops(model: Dict) -> float:
    d, _, vocab = dims(model)
    return 2.0 * d * vocab


def window_attention_ops(model: Dict, m: int, cached: int = 0) -> float:
    """Causal attention of positions ``cached .. m - 1`` over rows ``0 ..``
    their own, every sub-layer, the expanded form."""
    h, _, n, p, v, _ = mla_sizes(model)
    return sublayers(model) * h * (n + p + v) * (
        m * (m + 1.0) - cached * (cached + 1.0)
    )


def prompt_ops(model: Dict, m: int, cached: int = 0) -> float:
    """A prompt of ``m`` tokens whose first ``cached`` came from the prefix
    cache: its new tokens' projections, their attention, one head row."""
    return (
        (m - cached) * matmul_ops_per_token(model)
        + window_attention_ops(model, m, cached) + head_ops(model)
    )


def output_token_ops(model: Dict, context: int) -> float:
    """A decoded token that attends ``context`` rows."""
    h, _, n, p, v, _ = mla_sizes(model)
    return (
        matmul_ops_per_token(model) + head_ops(model)
        + 2.0 * sublayers(model) * context * h * (n + p + v)
    )


def decode_kernel_ops(model: Dict, context: int) -> float:
    """The absorbed decode kernel for one slot over ``context`` rows, every
    sub-layer: ``2 c H (R + r)`` a sub-layer."""
    h, _, _, _, _, r = mla_sizes(model)
    return 2.0 * sublayers(model) * context * h * (latent_row(model) + r)


def decode_kernel_bytes(model: Dict, context: int) -> float:
    """What the decode kernel must move for one slot over ``context`` rows,
    every sub-layer: the rows, the query in, the latent average out."""
    h, _, _, _, _, r = mla_sizes(model)
    row = latent_row(model)
    return sublayers(model) * (2.0 * row * context + 2.0 * h * row + 4.0 * h * r)


def least_window_kernel_seconds(
    model: Dict, m: int, cached: int, peaks: Dict
) -> float:
    """The prefill kernel for positions ``cached .. m - 1`` of one
    sequence, every sub-layer: the larger of the causal expanded form's
    operations over the bf16 peak and its bytes over HBM (padding rows,
    padding queries and the blocks past the diagonal are the kernel's
    waste)."""
    h, _, n, p, v, _ = mla_sizes(model)
    nbytes = sublayers(model) * 2.0 * h * (n + p + v) * ((m - cached) + m)
    return max(
        window_attention_ops(model, m, cached) / peaks["bf16_flops_per_s"],
        nbytes / peaks["hbm_bytes_per_s"],
    )


def parameter_elements(model: Dict) -> float:
    """Every parameter held here: the stack, the held experts, the
    embedding and the head."""
    d, layers, vocab = dims(model)
    return (
        dense_weight_elements(model) + layers * held(model) * expert_elements(model)
        + 2.0 * vocab * d
    )


def live_latent_bytes(model: Dict, lengths: Iterable[int]) -> float:
    """The latent rows one decode step attends, every sub-layer
    (unpadded)."""
    return 2.0 * sublayers(model) * latent_row(model) * sum(lengths)
