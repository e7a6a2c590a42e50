"""Operations and bytes of the Mellum2-shaped decoder, from shapes alone.

With ``d = hidden_size``, ``H = num_attention_heads * head_dim`` (the
query width), ``G = num_key_value_heads * head_dim`` (the key and the value
width), ``E = num_experts``, ``k = num_experts_per_tok``, ``f =
moe_intermediate_size``, ``V = vocab_size``, ``W = sliding_window`` and the
layers as run (``num_hidden_layers`` of ``layer_types``: window or full):

- matrix products of one token through one layer: q, k and v ``d x (H +
  2G)``, the output projection ``H x d``, the router ``d x E``, and the
  ``k`` ACTIVE experts of three matrices ``d x f`` each (gate, up, down):
  ``2 d (H + 2G) + 2 H d + 2 d E + 6 k d f`` operations; the 56 experts a
  token is not routed to cost it nothing;
- the head of its own: ``2 d V`` for each position whose logits are needed
  (every output token, the last position of each prompt);
- attention of a token that attends ``c`` keys: scores ``2 c H`` and values
  ``2 c H`` a layer. A full layer attends the whole context; a window layer
  at most ``W`` keys (the band). A whole prompt of ``n`` tokens: full
  ``sum_{c=1..n} 4 c H = 2 n (n + 1) H``; window ``4 H (sum_{c=1..n} min(c,
  W))`` with ``sum = n (n + 1) / 2`` up to ``W`` and ``W (W + 1) / 2 + (n -
  W) W`` past it;
- keys and values: ``2 G`` elements a token a layer, 2 bytes each in
  bfloat16 (2,048 bytes at the published widths);
- bytes a decode step must read at the least: every matrix outside the
  experts once, the experts its tokens are routed to (at most ``min(E,
  tokens * k)`` of a layer's ``E``, three matrices each), the head, and
  the keys and values it attends: a full layer's live pages, a window
  layer's capped at the window.

Norms, rotary positions, SiLU, the softmaxes, the sort and the residual
adds are a few operations per element and are left out: the counts are a
lower bound of the needed work.
"""

import math
from typing import Dict, Iterable, List, Sequence, Tuple


def dims(model: Dict) -> Tuple[int, int, int]:
    return int(model["hidden_size"]), int(model["num_hidden_layers"]), int(model["vocab_size"])


def widths(model: Dict) -> Tuple[int, int]:
    """``(H, G)``: the query width and the key (or value) width."""
    hd = int(model["head_dim"])
    return int(model["num_attention_heads"]) * hd, int(model["num_key_value_heads"]) * hd


def window_layers(model: Dict) -> List[bool]:
    return [t == "sliding_attention" for t in model["layer_types"]][: int(model["num_hidden_layers"])]


def expert_ops_per_token(model: Dict) -> float:
    """One layer's active experts for one token: ``6 k d f``."""
    d, _, _ = dims(model)
    return 6.0 * int(model["num_experts_per_tok"]) * d * int(model["moe_intermediate_size"])


def expert_bytes(model: Dict, tokens: int, bytes_per_element: int = 2) -> float:
    """One layer's expert matrices that ``tokens`` routed tokens can touch:
    ``min(E, tokens k)`` experts of ``3 d f`` elements."""
    d, _, _ = dims(model)
    touched = min(int(model["num_experts"]), tokens * int(model["num_experts_per_tok"]))
    return 3.0 * touched * d * int(model["moe_intermediate_size"]) * bytes_per_element


def matmul_ops_per_token(model: Dict) -> float:
    d, layers, _ = dims(model)
    q, kv = widths(model)
    dense = 2.0 * d * (q + 2 * kv) + 2.0 * q * d + 2.0 * d * int(model["num_experts"])
    return layers * (dense + expert_ops_per_token(model))


def head_ops(model: Dict) -> float:
    d, _, vocab = dims(model)
    return 2.0 * d * vocab


def keys_attended(model: Dict, context: int) -> float:
    """Summed over the layers, the keys a token at ``context`` attends."""
    window = int(model["sliding_window"])
    return float(sum(min(context, window) if w else context for w in window_layers(model)))


def attention_ops_at(model: Dict, context: int) -> float:
    q, _ = widths(model)
    return 4.0 * q * keys_attended(model, context)


def prompt_attention_ops(model: Dict, n: int) -> float:
    """Banded causal attention over a whole prompt of ``n`` tokens, all
    layers."""
    q, _ = widths(model)
    window = int(model["sliding_window"])
    full = n * (n + 1) / 2.0
    banded = full if n <= window else window * (window + 1) / 2.0 + (n - window) * window
    return 4.0 * q * sum(banded if w else full for w in window_layers(model))


def prompt_ops(model: Dict, n: int, cached: int = 0) -> float:
    new = n - cached
    attention = prompt_attention_ops(model, n) - prompt_attention_ops(model, cached)
    return new * matmul_ops_per_token(model) + attention + head_ops(model)


def output_token_ops(model: Dict, context: int) -> float:
    return matmul_ops_per_token(model) + head_ops(model) + attention_ops_at(model, context)


def weight_bytes(model: Dict, tokens: int = 64, bytes_per_element: int = 2) -> float:
    """What a decode step of ``tokens`` sequences reads of the weights."""
    d, layers, vocab = dims(model)
    q, kv = widths(model)
    dense = d * (q + 2 * kv) + q * d + d * int(model["num_experts"])
    return layers * (dense * bytes_per_element + expert_bytes(model, tokens, bytes_per_element)) \
        + vocab * d * bytes_per_element


def kv_bytes_per_token(model: Dict, bytes_per_element: int = 2) -> float:
    """One layer: a key and a value vector over the key/value heads."""
    _, kv = widths(model)
    return 2.0 * kv * bytes_per_element


def live_kv_bytes(model: Dict, lengths: Iterable[int], page_size: int) -> float:
    """Keys and values one decode step attends, all layers: a full layer's
    live pages, a window layer's capped at the window."""
    window = int(model["sliding_window"])
    kinds = window_layers(model)
    tokens = 0
    for n in lengths:
        live = math.ceil(n / page_size) * page_size
        tokens += sum(min(live, window) if w else live for w in kinds)
    return tokens * kv_bytes_per_token(model)


def least_decode_step_seconds(
    model: Dict, lengths: Sequence[int], page_size: int, peaks: Dict
) -> Dict[str, float]:
    ops = sum(output_token_ops(model, n) for n in lengths)
    nbytes = weight_bytes(model, len(lengths)) + live_kv_bytes(model, lengths, page_size)
    compute = ops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory)}


def least_expert_seconds(model: Dict, tokens: int, peaks: Dict) -> float:
    """The expert layers of one call over ``tokens`` tokens, all layers:
    the larger of their operations over the bf16 peak and the expert bytes
    touched over HBM bandwidth."""
    _, layers, _ = dims(model)
    compute = tokens * expert_ops_per_token(model) / peaks["bf16_flops_per_s"]
    memory = expert_bytes(model, tokens) / peaks["hbm_bytes_per_s"]
    return layers * max(compute, memory)
