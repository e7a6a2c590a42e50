"""Operations and bytes of the Falcon-H1-shaped decoder (a Mamba-2
state-space mixer beside grouped attention in every block, a dense gated
MLP, a head of its own), from shapes alone.

With ``d = hidden_size``, ``H = num_attention_heads * head_dim`` (the
query width), ``G = num_key_value_heads * head_dim`` (the key and the
value width), ``f = intermediate_size``, ``V = vocab_size``, ``L =
num_hidden_layers`` as run, and for the mixer ``h = mamba_n_heads``, ``p =
mamba_d_head``, ``I = h p`` (its inner width), ``n = mamba_d_state``, ``g
= mamba_n_groups``, ``Q = mamba_chunk_size``:

- matrix products of one token through one layer: q, k and v ``d x (H +
  2G)``, the output projection ``H x d``, the mixer's input ``d x (2 I + 2
  g n + h)`` (gate, x, B, C, dt) and output ``I x d``, the gated MLP's
  three ``d x f``: ``2 d (H + 2G) + 2 H d + 2 d (2 I + 2 g n + h) + 2 I d
  + 6 d f`` operations;
- the head of its own: ``2 d V`` for each position whose logits are needed
  (every output token, the last position of each prompt);
- causal attention of a token that attends ``c`` keys: scores ``2 c H``
  and values ``2 c H`` a layer; a whole prompt of ``n`` tokens ``2 n (n +
  1) H`` a layer;
- the recurrence, one token a layer, as a decode step computes it: ``S =
  decay S + (dt x) B^T`` is three operations an element of the ``[p, n]``
  state and ``y = S C`` two: ``5 h p n``;
- the recurrence, a prompt's token a layer, in the chunked form (a chunk
  of ``Q`` tokens): ``C B^T`` once a group ``2 Q^2 n``, and a head ``(C
  B^T * L) x`` ``2 Q^2 p``, the chunk's state ``2 Q p n`` and the carried
  state's part of the outputs ``2 Q n p``; a token: ``2 Q n g + h (2 Q p +
  4 p n)``;
- the convolution (4 taps over ``I + 2 g n`` channels), the norms, SiLU,
  softplus, the rotations and the residual adds are a few operations per
  element and are left out: the counts are a lower bound of the needed
  work;
- keys and values: ``2 G`` elements a token a layer, 2 bytes each;
- the recurrent state: ``h p n`` float32 a sequence a layer (4.19 MB at
  the published sizes), read and written once a decode step, with ``(conv
  taps - 1) (I + 2 g n)`` bfloat16 convolution rows;
- bytes a decode step must move at the least: every matrix of the stack
  and the head once (2 bytes an element; the embedding is gathered a row
  a token), the keys and values of every live page, and the live
  sequences' state once in and once out;
- bytes the chunked scan of a prompt's ``n`` tokens must move a layer:
  ``x``, ``B``, ``C`` in (2 bytes), the outputs (float32) and the state
  out: ``n (2 I + 4 g n + 4 I) + 4 h p n``.
"""

import math
from typing import Dict, Iterable, Sequence, Tuple


def dims(model: Dict) -> Tuple[int, int, int]:
    return int(model["hidden_size"]), int(model["num_hidden_layers"]), int(model["vocab_size"])


def widths(model: Dict) -> Tuple[int, int]:
    """``(H, G)``: the query width and the key (or value) width."""
    hd = int(model["head_dim"])
    return int(model["num_attention_heads"]) * hd, int(model["num_key_value_heads"]) * hd


def ssm_sizes(model: Dict) -> Tuple[int, int, int, int, int]:
    """``(h, p, n, g, Q)`` of the state-space mixer."""
    return (
        int(model["mamba_n_heads"]), int(model["mamba_d_head"]),
        int(model["mamba_d_state"]), int(model["mamba_n_groups"]),
        int(model["mamba_chunk_size"]),
    )


def matmul_ops_per_token(model: Dict) -> float:
    d, layers, _ = dims(model)
    q, kv = widths(model)
    h, p, n, g, _ = ssm_sizes(model)
    inner = h * p
    per_layer = (
        2.0 * d * (q + 2 * kv) + 2.0 * q * d
        + 2.0 * d * (2 * inner + 2 * g * n + h) + 2.0 * inner * d
        + 6.0 * d * int(model["intermediate_size"])
    )
    return layers * per_layer


def head_ops(model: Dict) -> float:
    d, _, vocab = dims(model)
    return 2.0 * d * vocab


def attention_ops_at(model: Dict, context: int) -> float:
    _, layers, _ = dims(model)
    q, _ = widths(model)
    return 4.0 * q * context * layers


def prompt_attention_ops(model: Dict, n: int) -> float:
    """Causal attention over a whole prompt of ``n`` tokens, all layers."""
    _, layers, _ = dims(model)
    q, _ = widths(model)
    return 2.0 * n * (n + 1) * q * layers


def ssm_step_ops(model: Dict) -> float:
    """The one-token update of one sequence, all layers: ``5 h p n``."""
    _, layers, _ = dims(model)
    h, p, n, _, _ = ssm_sizes(model)
    return layers * 5.0 * h * p * n


def ssm_scan_ops_per_token(model: Dict) -> float:
    """The chunked form, a token, all layers."""
    _, layers, _ = dims(model)
    h, p, n, g, q = ssm_sizes(model)
    return layers * (2.0 * q * n * g + h * (2.0 * q * p + 4.0 * p * n))


def prompt_ops(model: Dict, n: int, cached: int = 0) -> float:
    new = n - cached
    attention = prompt_attention_ops(model, n) - prompt_attention_ops(model, cached)
    return (
        new * (matmul_ops_per_token(model) + ssm_scan_ops_per_token(model))
        + attention + head_ops(model)
    )


def output_token_ops(model: Dict, context: int) -> float:
    return (
        matmul_ops_per_token(model) + ssm_step_ops(model) + head_ops(model)
        + attention_ops_at(model, context)
    )


def weight_bytes(model: Dict, bytes_per_element: int = 2) -> float:
    """What a decode step reads of the weights: the stack and the head."""
    d, _, vocab = dims(model)
    return (matmul_ops_per_token(model) / 2.0 + vocab * d) * bytes_per_element


def kv_bytes_per_token(model: Dict, bytes_per_element: int = 2) -> float:
    """All layers: a key and a value vector over the key/value heads."""
    _, layers, _ = dims(model)
    _, kv = widths(model)
    return 2.0 * kv * layers * bytes_per_element


def live_kv_bytes(model: Dict, lengths: Iterable[int], page_size: int) -> float:
    """Keys and values of the live pages of one decode step (all layers)."""
    tokens = sum(math.ceil(n / page_size) * page_size for n in lengths)
    return tokens * kv_bytes_per_token(model)


def ssm_state_bytes(model: Dict) -> float:
    """One sequence's recurrent state, all layers, float32."""
    _, layers, _ = dims(model)
    h, p, n, _, _ = ssm_sizes(model)
    return layers * 4.0 * h * p * n


def ssm_step_bytes(model: Dict, sequences: int) -> float:
    """What one decode step must move of the state: the live sequences'
    blocks once in and once out."""
    return 2.0 * sequences * ssm_state_bytes(model)


def ssm_scan_bytes(model: Dict, n: int) -> float:
    """What the chunked scan of a prompt of ``n`` tokens must move, all
    layers."""
    _, layers, _ = dims(model)
    h, p, state, g, _ = ssm_sizes(model)
    inner = h * p
    return layers * (n * (2.0 * inner + 4.0 * g * state + 4.0 * inner) + 4.0 * h * p * state)


def least_ssm_scan_seconds(model: Dict, n: int, peaks: Dict) -> float:
    """A prompt's chunked scan: the larger of its operations over the bf16
    peak and its bytes over HBM bandwidth."""
    compute = n * ssm_scan_ops_per_token(model) / peaks["bf16_flops_per_s"]
    memory = ssm_scan_bytes(model, n) / peaks["hbm_bytes_per_s"]
    return max(compute, memory)


def least_decode_step_seconds(
    model: Dict, lengths: Sequence[int], page_size: int, peaks: Dict
) -> Dict[str, float]:
    ops = sum(output_token_ops(model, n) for n in lengths)
    nbytes = (
        weight_bytes(model) + live_kv_bytes(model, lengths, page_size)
        + ssm_step_bytes(model, len(lengths))
    )
    compute = ops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory)}
