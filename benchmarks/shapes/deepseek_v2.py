"""Operations and bytes of the DeepSeek-V2-shaped decoder (latent attention,
MLA, in every layer; ``first_k_dense_replace`` dense SwiGLU layers, then
layers of routed experts beside shared ones; a head of its own), from
shapes alone.

With ``d = hidden_size``, ``H = num_attention_heads``, ``n =
qk_nope_head_dim``, ``p = qk_rope_head_dim``, ``v = v_head_dim``, ``r =
kv_lora_rank``, ``q = n + p`` (a head's query/key width), ``R = r + p``
(a token's cached latent row), ``I = intermediate_size`` (the dense
layers' SwiGLU), ``f = moe_intermediate_size``, ``s = n_shared_experts``,
``E = n_routed_experts``, ``k = num_experts_per_tok``, ``V = vocab_size``,
``L`` layers as run of which ``Ld = first_k_dense_replace`` are dense:

- matrix products of one token through a layer's attention: the query
  ``d x H q``, the latent with the rotary key ``d x R``, the latent's
  expansion to every head's key and value ``r x H (n + v)``, the output
  ``H v x d``: ``2 d (H q + R) + 2 r H (n + v) + 2 H v d``. The decode
  step runs the expansion absorbed, ``q_nope W_uk^T`` (``2 H n r``) and
  ``o_lat W_uv`` (``2 H r v``): the same count;
- through a dense layer's MLP: ``6 d I``; through an expert layer's: the
  router ``d x E``, the shared experts as one SwiGLU of ``s f`` (``6 d s
  f``) and ``k`` routed choices of ``6 d f``: ``2 d E + 6 d f (s + k)``;
- the head of its own: ``2 d V`` for each position whose logits are needed
  (every output token, the last position of each prompt);
- attention, the model's own count (the EXPANDED form): a token that
  attends ``c`` rows (its own included) takes ``2 c H (q + v)`` a layer,
  and a window of positions ``cached .. m - 1`` of a sequence ``H (q + v)
  (m (m + 1) - cached (cached + 1))`` a layer (causal);
- the ABSORBED decode kernel's own work for a token over ``c`` rows:
  scores over the whole row ``2 c H R`` and the latent average ``2 c H r``
  a layer;
- the norms, SiLU, the rotation, the softmax, the sort of the routed pairs
  and the residual adds are a few operations per element and are left
  out: the counts are a lower bound of the needed work;
- the cache: ONE latent row of ``R`` values a token a layer, 2 bytes each
  (the pool pads it to whole 128-lane registers, 576 -> 640; the padding
  is the kernel's waste and is not counted);
- bytes the decode kernel must move for a slot over ``c`` rows a layer:
  the rows once (``2 R c``), its query in (``2 H R``) and its latent
  average out (float32, ``4 H r``);
- bytes the extend kernel must move for a window of ``w`` queries over ``m``
  rows a layer: queries in and outputs out (``2 w H (q + v)``), the
  expanded keys and values once (``2 m H (q + v)``).
"""

from typing import Dict, Iterable, Sequence, Tuple


def dims(model: Dict) -> Tuple[int, int, int]:
    return (
        int(model["hidden_size"]), int(model["num_hidden_layers"]),
        int(model["vocab_size"]),
    )


def mla_sizes(model: Dict) -> Tuple[int, int, int, int, int]:
    """``(H, n, p, v, r)``."""
    return (
        int(model["num_attention_heads"]), int(model["qk_nope_head_dim"]),
        int(model["qk_rope_head_dim"]), int(model["v_head_dim"]),
        int(model["kv_lora_rank"]),
    )


def layer_counts(model: Dict) -> Tuple[int, int]:
    """``(Ld, Le)``: dense layers and expert layers as run."""
    _, layers, _ = dims(model)
    dense = min(int(model["first_k_dense_replace"]), layers)
    return dense, layers - dense


def latent_row(model: Dict) -> int:
    """``R = r + p``: a token's cached values a layer."""
    _, _, p, _, r = mla_sizes(model)
    return r + p


def attention_weight_elements(model: Dict) -> float:
    """One layer's attention matrices."""
    d, _, _ = dims(model)
    h, n, p, v, r = mla_sizes(model)
    return d * (h * (n + p) + r + p) + r * h * (n + v) + h * v * d


def expert_ops_per_choice(model: Dict) -> float:
    """One routed expert for one token: ``6 d f``."""
    d, _, _ = dims(model)
    return 6.0 * d * int(model["moe_intermediate_size"])


def expert_bytes(model: Dict, choices: float, bytes_per_element: int = 2) -> float:
    """One layer's routed expert matrices that ``choices`` routed choices
    can touch: ``min(E, choices)`` experts of ``3 d f`` elements."""
    d, _, _ = dims(model)
    touched = min(float(model["n_routed_experts"]), choices)
    return 3.0 * touched * d * int(model["moe_intermediate_size"]) * bytes_per_element


def dense_weight_elements(model: Dict) -> float:
    """Every matrix of the stack outside the routed experts and the head."""
    d, _, _ = dims(model)
    ld, le = layer_counts(model)
    f = int(model["moe_intermediate_size"])
    mlp = ld * 3 * d * int(model["intermediate_size"]) + le * (
        d * int(model["n_routed_experts"]) + 3 * d * f * int(model["n_shared_experts"])
    )
    return (ld + le) * attention_weight_elements(model) + mlp


def matmul_ops_per_token(model: Dict) -> float:
    _, le = layer_counts(model)
    routed = le * int(model["num_experts_per_tok"]) * expert_ops_per_choice(model)
    return 2.0 * dense_weight_elements(model) + routed


def head_ops(model: Dict) -> float:
    d, _, vocab = dims(model)
    return 2.0 * d * vocab


def window_attention_ops(model: Dict, m: int, cached: int = 0) -> float:
    """Causal attention of positions ``cached .. m - 1`` over rows ``0 ..``
    their own, every layer, the expanded form."""
    _, layers, _ = dims(model)
    h, n, p, v, _ = mla_sizes(model)
    return layers * h * (n + p + v) * (m * (m + 1.0) - cached * (cached + 1.0))


def prompt_ops(model: Dict, m: int, cached: int = 0) -> float:
    """A prompt of ``m`` tokens whose first ``cached`` came from the prefix
    cache: its new tokens' projections, their attention, one head row."""
    return (
        (m - cached) * matmul_ops_per_token(model)
        + window_attention_ops(model, m, cached) + head_ops(model)
    )


def output_token_ops(model: Dict, context: int) -> float:
    """A decoded token that attends ``context`` rows."""
    _, layers, _ = dims(model)
    h, n, p, v, _ = mla_sizes(model)
    return (
        matmul_ops_per_token(model) + head_ops(model)
        + 2.0 * layers * context * h * (n + p + v)
    )


def decode_kernel_ops(model: Dict, context: int) -> float:
    """The absorbed decode kernel for one slot over ``context`` rows, every
    layer: ``2 c H (R + r)`` a layer."""
    _, layers, _ = dims(model)
    h, _, _, _, r = mla_sizes(model)
    return 2.0 * layers * context * h * (latent_row(model) + r)


def decode_kernel_bytes(model: Dict, context: int) -> float:
    """What the decode kernel must move for one slot over ``context`` rows,
    every layer: the rows, the query in, the latent average out."""
    _, layers, _ = dims(model)
    h, _, _, _, r = mla_sizes(model)
    row = latent_row(model)
    return layers * (2.0 * row * context + 2.0 * h * row + 4.0 * h * r)


def least_decode_kernel_seconds(
    model: Dict, contexts: Sequence[int], peaks: Dict
) -> float:
    """One decode step's kernel calls (every layer) over ``contexts``, the
    larger of operations over the bf16 peak and bytes over HBM."""
    ops = sum(decode_kernel_ops(model, c) for c in contexts)
    nbytes = sum(decode_kernel_bytes(model, c) for c in contexts)
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def least_window_kernel_seconds(
    model: Dict, m: int, cached: int, peaks: Dict
) -> float:
    """The prefill/extend kernel for positions ``cached .. m - 1`` of one
    sequence, every layer: the larger of the causal expanded form's
    operations over the bf16 peak and its bytes over HBM (padding rows,
    padding queries and the blocks past the diagonal are the kernel's
    waste)."""
    _, layers, _ = dims(model)
    h, n, p, v, _ = mla_sizes(model)
    nbytes = layers * 2.0 * h * (n + p + v) * ((m - cached) + m)
    return max(
        window_attention_ops(model, m, cached) / peaks["bf16_flops_per_s"],
        nbytes / peaks["hbm_bytes_per_s"],
    )


def weight_bytes(model: Dict, tokens: int = 128, bytes_per_element: int = 2) -> float:
    """What a decode step of ``tokens`` sequences reads of the weights."""
    d, _, vocab = dims(model)
    _, le = layer_counts(model)
    routed = le * expert_bytes(
        model, tokens * int(model["num_experts_per_tok"]), bytes_per_element
    )
    return (dense_weight_elements(model) + vocab * d) * bytes_per_element + routed


def live_latent_bytes(model: Dict, lengths: Iterable[int]) -> float:
    """The latent rows one decode step attends, every layer (unpadded)."""
    _, layers, _ = dims(model)
    return 2.0 * layers * latent_row(model) * sum(lengths)
