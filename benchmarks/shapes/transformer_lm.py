"""Operations and bytes of a GPT-2-shaped decoder, from shapes alone.

With ``d = n_embd``, ``L = n_layer``, ``V = vocab_size`` and an MLP of
``4d`` (GPT-2):

- matrix products of one token through one layer: qkv ``d x 3d``, proj
  ``d x d``, up ``d x 4d``, down ``4d x d``: ``12 d^2`` multiply-
  accumulates, ``24 d^2`` operations; through the stack ``24 d^2 L``;
- the tied head: ``2 d V`` operations for each position whose logits are
  needed: every output token, and the last position of each prompt;
- causal attention of a token at context ``c`` (it attends ``c`` keys):
  scores ``2 c d`` and values ``2 c d`` operations a layer, ``4 c d L`` in
  all; a whole prompt of ``n`` tokens: ``sum_{c=1..n} 4 c d L =
  2 n (n + 1) d L``;
- bytes a decode step must read at the least: every matrix of the stack
  and the token table once in the served type (2 bytes an element:
  ``(12 d^2 L + V d) * 2``), and the keys and values of every live page:
  a page holds ``page_size`` tokens, a token ``2 d`` elements a layer (one
  key and one value vector over all heads), 2 bytes each in bf16.

Norms, GELU, residual adds and the position table are a few operations per
element and are left out: the counts are a lower bound of the needed work.
"""

import math
from typing import Dict, Iterable, Sequence, Tuple


def dims(model: Dict) -> Tuple[int, int, int]:
    return int(model["n_embd"]), int(model["n_layer"]), int(model["vocab_size"])


def matmul_ops_per_token(model: Dict) -> float:
    d, layers, _ = dims(model)
    return 24.0 * d * d * layers


def head_ops(model: Dict) -> float:
    d, _, vocab = dims(model)
    return 2.0 * d * vocab


def attention_ops_at(model: Dict, context: int) -> float:
    d, layers, _ = dims(model)
    return 4.0 * context * d * layers


def prompt_attention_ops(model: Dict, n: int) -> float:
    """Causal attention over a whole prompt of ``n`` tokens, all layers."""
    d, layers, _ = dims(model)
    return 2.0 * n * (n + 1) * d * layers


def prompt_ops(model: Dict, n: int, cached: int = 0) -> float:
    """Operations to prefill a prompt of ``n`` tokens of which the first
    ``cached`` are served from the prefix cache: the products of the
    computed tokens, their attention over everything before them, and one
    head."""
    new = n - cached
    attention = prompt_attention_ops(model, n) - prompt_attention_ops(model, cached)
    return new * matmul_ops_per_token(model) + attention + head_ops(model)


def output_token_ops(model: Dict, context: int) -> float:
    return (
        matmul_ops_per_token(model) + head_ops(model)
        + attention_ops_at(model, context)
    )


def weight_bytes(model: Dict, bytes_per_element: int = 2) -> float:
    d, layers, vocab = dims(model)
    return (12.0 * d * d * layers + vocab * d) * bytes_per_element


def kv_bytes_per_token(model: Dict, bytes_per_element: int = 2) -> float:
    """All layers: a key and a value vector of ``d`` elements each."""
    d, layers, _ = dims(model)
    return 2.0 * d * layers * bytes_per_element


def live_kv_bytes(model: Dict, lengths: Iterable[int], page_size: int) -> float:
    """Keys and values of the live pages of one decode step (all layers)."""
    tokens = sum(math.ceil(n / page_size) * page_size for n in lengths)
    return tokens * kv_bytes_per_token(model)


def least_decode_step_seconds(
    model: Dict, lengths: Sequence[int], page_size: int, peaks: Dict
) -> Dict[str, float]:
    """One decode step over slots at contexts ``lengths``: the larger of
    its operations over the bf16 peak and its needed bytes over HBM."""
    ops = sum(output_token_ops(model, n) for n in lengths)
    nbytes = weight_bytes(model) + live_kv_bytes(model, lengths, page_size)
    compute = ops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory)}
