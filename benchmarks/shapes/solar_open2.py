"""Operations and bytes of the Solar-Open2-shaped decoder as one chip of
several holds it (gated delta-rule linear attention, KDA, in the layers
that are not in ``gqa_layers`` and gated softmax attention without
positions in those that are; the chip's share of the routed experts
beside a shared expert; a head of its own over the chip's slice of the
vocabulary), from shapes alone.

With ``d = hidden_size``, ``H = num_attention_heads * head_dim`` (the
query width), ``G = num_key_value_heads * head_dim`` (the key and the
value width), ``V = vocab_size`` (the slice), ``f =
moe_intermediate_size``, ``k = num_experts_per_tok``, ``E =
router_experts`` (the router's width), ``e = held_experts[1]`` (the
experts held here), ``La`` attention layers and ``Lk`` KDA layers as run,
and for KDA ``h = linear_attn_config.num_heads``, ``p =
linear_attn_config.head_dim`` (keys and values both), ``I = h p``, ``r =
kda_gate_rank``, ``Q = kda_chunk``:

- matrix products of one token through an attention layer: q, k and v ``d
  x (H + 2G)``, the output gate ``d x H``, the output projection ``H x
  d``: ``2 d (H + 2G) + 4 H d`` operations;
- through a KDA layer: q, k and v ``d x 3 I``, the output projection ``I x
  d``, the two low-rank gates ``d x r`` then ``r x I`` each, ``beta`` ``d
  x h``: ``8 d I + 4 r (d + I) + 2 d h``;
- through every layer's expert part: the router ``d x E``, the shared
  expert's three matrices ``d x f`` and the HELD choices' three each. A
  token makes ``k`` choices over ``E`` experts of which ``e`` are here: ``k
  e / E`` held choices a token under even routing (1 at the published
  sizes; the run's own share is the counter ``moe_held_choice_share``):
  ``2 d E + 6 d f (1 + k e / E)``. The choices that fall to the 280
  absent experts cost this chip nothing;
- the head of its own: ``2 d V`` for each position whose logits are needed
  (every output token, the last position of each prompt);
- causal attention of a token that attends ``c`` keys: scores ``2 c H``
  and values ``2 c H`` an attention layer; a whole prompt of ``n`` tokens
  ``2 n (n + 1) H`` an attention layer;
- the delta rule, one token a KDA layer, as a decode step computes it on
  the ``[p, p]`` state of each head: the rows scaled by the decay (1 an
  element), ``k^T S`` (2), the rank-1 correction (2), ``q^T S`` (2): ``7 h
  p^2``;
- the delta rule, a prompt's token a KDA layer, in the chunked form (a
  chunk of ``Q`` tokens, a head): ``kf kb^T``, ``qf kb^T`` and ``T
  Diag(beta) kf`` ``2 Q p`` each, ``T Diag(beta) V`` ``2 Q p``, and the
  four products against the carried state (``Kbar S``, ``qf S``, ``kend^T
  W`` ``2 p^2`` each, ``P W`` ``2 Q p``): ``h (10 Q p + 6 p^2)``. The
  Pallas kernel runs the last four alone, ``h (2 Q p + 6 p^2)`` a token
  (:func:`kda_kernel_ops_per_token`); the triangular solve itself (``Q^2``
  a row inside blocks of 16) and what prepares the decays are XLA's;
- the convolution (4 taps over ``3 I`` channels), the norms, SiLU,
  softplus, the sigmoids, the L2 norms, the sort of the routed pairs and
  the residual adds are a few operations per element and are left out: the
  counts are a lower bound of the needed work;
- keys and values: ``2 G`` elements a token an ATTENTION layer, 2 bytes
  each; a KDA layer keeps none;
- the KDA state: ``h p^2`` float32 a sequence a KDA layer (4.19 MB at the
  published sizes), read and written once a decode step, with ``3 x 3 I``
  bfloat16 convolution rows;
- bytes a decode step must move at the least: every matrix outside the
  routed experts once (2 bytes an element; the embedding is gathered a row
  a token), the held experts its tokens are routed to (at most ``min(e,
  held choices)`` of a layer's ``e``, three matrices each), the head, the
  keys and values of every live page of the attention layers, and the
  live sequences' KDA state once in and once out;
- bytes the chunked kernel of a prompt's ``n`` tokens must move a KDA
  layer: ``qf``, ``Kbar``, ``kend`` in (2 bytes a key channel each),
  ``Ubar`` in and the outputs out (float32 a value channel each), ``P``
  (``2 Q`` a token a head), the state out: ``n h (6 p + 8 p + 2 Q) + 4 h
  p^2``.
"""

import math
from typing import Dict, Iterable, List, Sequence, Tuple


def dims(model: Dict) -> Tuple[int, int, int]:
    return int(model["hidden_size"]), int(model["num_hidden_layers"]), int(model["vocab_size"])


def widths(model: Dict) -> Tuple[int, int]:
    """``(H, G)``: the query width and the key (or value) width."""
    hd = int(model["head_dim"])
    return int(model["num_attention_heads"]) * hd, int(model["num_key_value_heads"]) * hd


def attention_layers(model: Dict) -> List[bool]:
    """Per layer as run, whether it is an attention layer (else KDA)."""
    attention = set(int(l) for l in model["gqa_layers"])
    return [l in attention for l in range(int(model["num_hidden_layers"]))]


def layer_counts(model: Dict) -> Tuple[int, int]:
    """``(La, Lk)``: attention layers and KDA layers as run."""
    kinds = attention_layers(model)
    return sum(kinds), len(kinds) - sum(kinds)


def kda_sizes(model: Dict) -> Tuple[int, int, int, int]:
    """``(h, p, r, Q)`` of the KDA mixer."""
    linear = model["linear_attn_config"]
    return (
        int(linear["num_heads"]), int(linear["head_dim"]),
        int(model["kda_gate_rank"]), int(model["kda_chunk"]),
    )


def held_choices_per_token(model: Dict) -> float:
    """``k e / E``: a token's routed choices that fall to the experts held
    here, under even routing."""
    return (
        int(model["num_experts_per_tok"]) * int(model["held_experts"][1])
        / int(model["router_experts"])
    )


def expert_ops_per_choice(model: Dict) -> float:
    """One expert for one token: ``6 d f``."""
    d, _, _ = dims(model)
    return 6.0 * d * int(model["moe_intermediate_size"])


def expert_bytes(model: Dict, choices: float, bytes_per_element: int = 2) -> float:
    """One layer's held expert matrices that ``choices`` held choices can
    touch: ``min(e, choices)`` experts of ``3 d f`` elements."""
    d, _, _ = dims(model)
    touched = min(float(model["held_experts"][1]), choices)
    return 3.0 * touched * d * int(model["moe_intermediate_size"]) * bytes_per_element


def dense_weight_elements(model: Dict) -> float:
    """Every matrix of the stack outside the routed experts."""
    d, _, _ = dims(model)
    q, kv = widths(model)
    h, p, r, _ = kda_sizes(model)
    la, lk = layer_counts(model)
    attention = d * (q + 2 * kv) + 2 * q * d
    kda = 4 * d * h * p + 2 * r * (d + h * p) + d * h
    experts = d * int(model["router_experts"]) + 3 * d * int(model["moe_intermediate_size"])
    return la * attention + lk * kda + (la + lk) * experts


def matmul_ops_per_token(model: Dict) -> float:
    _, layers, _ = dims(model)
    routed = layers * held_choices_per_token(model) * expert_ops_per_choice(model)
    return 2.0 * dense_weight_elements(model) + routed


def head_ops(model: Dict) -> float:
    d, _, vocab = dims(model)
    return 2.0 * d * vocab


def attention_ops_at(model: Dict, context: int) -> float:
    q, _ = widths(model)
    return 4.0 * q * context * layer_counts(model)[0]


def prompt_attention_ops(model: Dict, n: int) -> float:
    """Causal attention over a whole prompt of ``n`` tokens, the attention
    layers."""
    q, _ = widths(model)
    return 2.0 * n * (n + 1) * q * layer_counts(model)[0]


def kda_step_ops(model: Dict) -> float:
    """The one-token update of one sequence, the KDA layers: ``7 h p^2``."""
    h, p, _, _ = kda_sizes(model)
    return layer_counts(model)[1] * 7.0 * h * p * p


def kda_scan_ops_per_token(model: Dict) -> float:
    """The chunked form, a token, the KDA layers."""
    h, p, _, q = kda_sizes(model)
    return layer_counts(model)[1] * h * (10.0 * q * p + 6.0 * p * p)


def kda_kernel_ops_per_token(model: Dict) -> float:
    """The four products against the carried state, a token, the KDA
    layers: what the chunked kernel itself runs."""
    h, p, _, q = kda_sizes(model)
    return layer_counts(model)[1] * h * (2.0 * q * p + 6.0 * p * p)


def prompt_ops(model: Dict, n: int, cached: int = 0) -> float:
    new = n - cached
    attention = prompt_attention_ops(model, n) - prompt_attention_ops(model, cached)
    return (
        new * (matmul_ops_per_token(model) + kda_scan_ops_per_token(model))
        + attention + head_ops(model)
    )


def output_token_ops(model: Dict, context: int) -> float:
    return (
        matmul_ops_per_token(model) + kda_step_ops(model) + head_ops(model)
        + attention_ops_at(model, context)
    )


def weight_bytes(model: Dict, tokens: int = 128, bytes_per_element: int = 2) -> float:
    """What a decode step of ``tokens`` sequences reads of the weights."""
    d, layers, vocab = dims(model)
    held = layers * expert_bytes(
        model, tokens * held_choices_per_token(model), bytes_per_element
    )
    return (dense_weight_elements(model) + vocab * d) * bytes_per_element + held


def kv_bytes_per_token(model: Dict, bytes_per_element: int = 2) -> float:
    """The attention layers: a key and a value vector over the key/value
    heads each."""
    _, kv = widths(model)
    return 2.0 * kv * layer_counts(model)[0] * bytes_per_element


def live_kv_bytes(model: Dict, lengths: Iterable[int], page_size: int) -> float:
    """Keys and values of the live pages of one decode step (the attention
    layers)."""
    tokens = sum(math.ceil(n / page_size) * page_size for n in lengths)
    return tokens * kv_bytes_per_token(model)


def kda_state_bytes(model: Dict) -> float:
    """One sequence's KDA state, the KDA layers, float32."""
    h, p, _, _ = kda_sizes(model)
    return layer_counts(model)[1] * 4.0 * h * p * p


def kda_step_bytes(model: Dict, sequences: int) -> float:
    """What one decode step must move of the state: the live sequences'
    blocks once in and once out."""
    return 2.0 * sequences * kda_state_bytes(model)


def kda_scan_bytes(model: Dict, n: int) -> float:
    """What the chunked kernel of a prompt of ``n`` tokens must move, the
    KDA layers."""
    h, p, _, q = kda_sizes(model)
    return layer_counts(model)[1] * (n * h * (14.0 * p + 2.0 * q) + 4.0 * h * p * p)


def least_kda_scan_seconds(model: Dict, n: int, peaks: Dict) -> float:
    """A prompt's chunked kernel: the larger of its operations over the
    bf16 peak and its bytes over HBM bandwidth."""
    compute = n * kda_kernel_ops_per_token(model) / peaks["bf16_flops_per_s"]
    memory = kda_scan_bytes(model, n) / peaks["hbm_bytes_per_s"]
    return max(compute, memory)


#: The names ``layer_metrics/ssm_kernel_roofline.py`` reads a recurrent
#: mixer's two kernels under.
ssm_step_bytes = kda_step_bytes
least_ssm_scan_seconds = least_kda_scan_seconds


def least_decode_step_seconds(
    model: Dict, lengths: Sequence[int], page_size: int, peaks: Dict
) -> Dict[str, float]:
    ops = sum(output_token_ops(model, n) for n in lengths)
    nbytes = (
        weight_bytes(model, len(lengths)) + live_kv_bytes(model, lengths, page_size)
        + kda_step_bytes(model, len(lengths))
    )
    compute = ops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory)}
