"""Plain reference of Mellum2-12B-A2.5B-Instruct as served: one full forward
pass over a prompt with its served tokens, float32 at the highest matmul
precision, no cache, no kernels, no batching, no program code.

The layer, from the model's public ``config.json`` (``x`` the residual
stream, ``i`` a query's position, ``p`` a key's):

1. ``x = E[tokens]``: no position table, no scaling of the embedding.
2. ``h = RMSNorm(x; g1, eps 1e-6)``; ``q = h Wq`` (hidden -> heads x
   head_dim), ``k = h Wk``, ``v = h Wv`` (hidden -> kv_heads x head_dim),
   no biases. (The program holds the three as one matrix ``qkv``, query
   columns first, then key, then value.)
3. Rotary positions on ``q`` and ``k`` over the whole head, rotate-half
   convention, ``inv_i = theta ** (-2 i / head_dim)``. Window layers
   (``rope_type: default``): angles ``pos * inv_i``. Full layers
   (``rope_type: yarn``): ``d(b) = head_dim ln(original / (2 pi b)) /
   (2 ln theta)``, ``low = max(floor(d(beta_fast)), 0)``, ``high =
   min(ceil(d(beta_slow)), head_dim - 1)``, ``ramp_i = clip((i - low) /
   (high - low), 0, 1)``, ``inv'_i = inv_i (1 - ramp_i) + (inv_i /
   factor) ramp_i``; cos and sin times ``attention_factor``.
4. Scores ``q . k / sqrt(head_dim)``, query head ``j`` reads key/value
   head ``j // (heads / kv_heads)``; float32 softmax; mask ``p <= i`` and,
   in a window layer, ``i - p < sliding_window``.
5. ``x = x + concat(heads) Wo``.
6. ``h2 = RMSNorm(x; g2)``; router ``softmax(h2 Wr)`` in float32; the
   ``num_experts_per_tok`` largest, renormalised to sum 1; ``y = sum_e w_e
   Wd_e (silu(Wg_e h2) * Wu_e h2)``; ``x = x + y``. Every expert is
   computed for every token and the sum is masked by the routing weights
   (zero for the experts a token was not routed to).
7. Final RMSNorm; logits ``x Wh`` with a head of its own, float32.

So that 7.6 GB of bfloat16 weights and a sequence of 8,192 positions fit
one 16 GB chip beside each other: the layers run one after another from
the leaves as the program holds them, each leaf cast to float32 where it
is used (an expert at a time); attention runs over blocks of query rows
(each block sees every key, so the arithmetic is the plain one); the head
runs over blocks of positions and keeps, for each, the best logit, the
logit of the token that came next, and the first choice.

``lowp`` is the control (``PERF.md``): what the configuration keeps in
bfloat16 is rounded to float8 e4m3 (per-tensor scaled) instead: both
operands of every matrix product but the router's (which the
configuration keeps in float32) and the residual stream between blocks.

Weights arrive as the nested dict the benchmark made (``zkbench/
weights.py``) under flax's names for the repo's module: ``embed``,
``head``, ``RMSNorm_0``, ``block{i}/{RMSNorm_0,qkv,proj,RMSNorm_1,router,
experts_gate,experts_up,experts_down}``; expert ``e``'s matrices are column
block ``e`` of the three ``experts_*`` leaves (``[hidden, experts * f]``
twice, ``[f, experts * hidden]``).
"""

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, lowp):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * gain.astype(jnp.float32)


def rope_tables(model: Dict):
    """``{"full": (inv_freq [head_dim/2], factor), "window": ...}`` from
    the config's ``rope_parameters`` (step 3 of the docstring)."""
    hd = int(model["head_dim"])
    out = {}
    for kind, key in (("full", "full_attention"), ("window", "sliding_attention")):
        r = model["rope_parameters"][key]
        theta = float(r["rope_theta"])
        i = np.arange(hd // 2, dtype=np.float64)
        inv = theta ** (-2.0 * i / hd)
        factor = 1.0
        if r["rope_type"] == "yarn":
            def dim_of(turns):
                return hd * math.log(
                    r["original_max_position_embeddings"] / (2 * math.pi * turns)
                ) / (2 * math.log(theta))

            low = max(math.floor(dim_of(r["beta_fast"])), 0)
            high = min(math.ceil(dim_of(r["beta_slow"])), hd - 1)
            ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
            inv = inv * (1 - ramp) + inv / float(r["factor"]) * ramp
            factor = float(r["attention_factor"])
        elif r["rope_type"] != "default":
            raise ValueError(f"rope_type {r['rope_type']!r}")
        out[kind] = (np.asarray(inv, np.float32), factor)
    return out


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_kinds(model: Dict) -> List[bool]:
    """Per layer as run, whether it is a sliding-window layer."""
    return [t == "sliding_attention" for t in model["layer_types"]][: int(model["num_hidden_layers"])]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "top_k", "window", "eps", "lowp", "lowp_experts"))
def layer_forward(x, w, inv_freq, rope_factor, windowed, *, heads, kv_heads,
                  head_dim, top_k, window, eps, lowp, lowp_experts=False):
    """Steps 2-6 for one layer: ``x [s, hidden] float32 -> [s, hidden]``.
    ``windowed`` (a traced flag) and the layer's rotary table are
    operands, so the layers of both kinds share one compiled program."""
    s, d = x.shape
    group = heads // kv_heads
    h = _rms(x, w["RMSNorm_0"]["scale"], eps)
    qkv = _mm("sd,de->se", h, w["qkv"]["kernel"], lowp)
    q = qkv[:, : heads * head_dim].reshape(s, heads, head_dim)
    k = qkv[:, heads * head_dim : (heads + kv_heads) * head_dim].reshape(s, kv_heads, head_dim)
    v = qkv[:, (heads + kv_heads) * head_dim :].reshape(s, kv_heads, head_dim)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * rope_factor)[:, None, :]
    sin = (jnp.sin(angles) * rope_factor)[:, None, :]
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    # query head j reads key/value head j // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    if lowp:
        k, v = _fp8(k), _fp8(v)
    p_idx = jnp.arange(s)[None, :]

    def attend(block):
        qb, i_idx = block  # [rows, heads, head_dim], [rows]
        if lowp:
            qb = _fp8(qb)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * (head_dim ** -0.5)
        i = i_idx[:, None]
        keep = (p_idx <= i) & (~windowed | (i - p_idx < window))
        scores = jnp.where(keep[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        if lowp:
            p = _fp8(p)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    rows = min(QUERY_BLOCK, s)
    blocks = s // rows
    o = jax.lax.map(
        attend,
        (q.reshape(blocks, rows, heads, head_dim), jnp.arange(s).reshape(blocks, rows)),
    ).reshape(s, heads * head_dim)
    x = x + _mm("se,ed->sd", o, w["proj"]["kernel"], lowp)

    h2 = _rms(x, w["RMSNorm_1"]["scale"], eps)
    probs = jax.nn.softmax(
        jnp.einsum("sd,de->se", h2, w["router"].astype(jnp.float32), precision=HIGHEST),
        axis=-1,
    )
    top_w, top_e = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    num_experts = probs.shape[-1]
    # [s, experts]: the renormalised weight of a token's routed experts,
    # zero elsewhere
    routing = jnp.sum(
        jax.nn.one_hot(top_e, num_experts, dtype=jnp.float32) * top_w[..., None], axis=1
    )

    # expert e's matrices are column block e of each leaf
    f, d = w["experts_down"].shape[0], x.shape[1]

    def block(leaf, e, width):
        return jax.lax.dynamic_slice_in_dim(leaf, e * width, width, axis=1)

    low = lowp or lowp_experts

    def expert(y, e):
        gate = _mm("sd,df->sf", h2, block(w["experts_gate"], e, f), low)
        up = _mm("sd,df->sf", h2, block(w["experts_up"], e, f), low)
        out = _mm(
            "sf,fd->sd", jax.nn.silu(gate) * up,
            block(w["experts_down"], e, d), low,
        )
        return y + routing[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(num_experts))
    x = x + y
    return _fp8(x) if lowp else x


@partial(jax.jit, static_argnames=("eps", "lowp"))
def read_head(x, gain, head, nxt, *, eps, lowp):
    """Step 7 over blocks of positions: ``(best [s], got [s], choice
    [s])``: the best logit, the logit of the token that came next, and
    the token this pass puts first."""
    s = x.shape[0]
    x = _rms(x, gain, eps)
    head = head.astype(jnp.float32)
    if lowp:
        head = _fp8(head)
    rows = min(QUERY_BLOCK, s)

    def block(args):
        xb, nb = args
        if lowp:
            xb = _fp8(xb)
        logits = jnp.einsum("sd,dv->sv", xb, head, precision=HIGHEST)
        got = jnp.take_along_axis(logits, nb[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1), got, jnp.argmax(logits, axis=-1)

    best, got, choice = jax.lax.map(
        block, (x.reshape(s // rows, rows, -1), nxt.reshape(s // rows, rows))
    )
    return best.reshape(s), got.reshape(s), choice.reshape(s)


def hidden_states(params: Dict, model: Dict, tokens, lowp: bool = False,
                  lowp_experts: bool = False, experts_dropped: int = 0):
    """Steps 1-6 through every layer: ``tokens [s] -> x [s, hidden]``.
    ``lowp_experts`` rounds the operands of the experts' three matrix
    products alone to float8 (the second control); ``experts_dropped``
    routes each token to that many experts fewer than the config says
    (the planted fault)."""
    tables = rope_tables(model)
    static = dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        top_k=int(model["num_experts_per_tok"]) - int(experts_dropped),
        window=int(model["sliding_window"]),
        eps=float(model["rms_norm_eps"]),
        lowp=bool(lowp),
        lowp_experts=bool(lowp_experts),
    )
    x = params["embed"][tokens].astype(jnp.float32)
    if lowp:
        x = _fp8(x)
    for i, windowed in enumerate(layer_kinds(model)):
        inv_freq, factor = tables["window" if windowed else "full"]
        x = layer_forward(
            x, params[f"block{i}"], jnp.asarray(inv_freq), jnp.float32(factor),
            jnp.asarray(windowed), **static,
        )
    return x


def forward(params: Dict, model: Dict, tokens, lowp: bool = False):
    """All seven steps: ``tokens [s] -> logits [s, vocab]`` (for the CPU
    tests; the benchmark reads the head in blocks, ``read_head``)."""
    x = hidden_states(params, model, tokens, lowp)
    x = _rms(x, params["RMSNorm_0"]["scale"], float(model["rms_norm_eps"]))
    return _mm("sd,dv->sv", x, params["head"], lowp)


#: The share of the served tokens the compared gap covers (see
#: :func:`served_token_gaps`).
GAP_QUANTILE = 90.0

#: The controls and the planted fault ``--with-control`` judges in the
#: program's place: keyword arguments of :func:`hidden_states`.
CONTROLS = {
    "all_fp8": {"lowp": True},
    "experts_fp8": {"lowp_experts": True},
    "expert_dropped": {"experts_dropped": 1},
}


def served_token_gaps(
    params: Dict,
    model: Dict,
    sequences: List[Dict],
    pad_to: int,
    lowp_control: bool = False,
) -> Dict[str, float]:
    """For each sequence (``prompt`` and ``served`` token arrays), one
    reference pass over the prompt followed by its served tokens, padded
    to ``pad_to`` (one compiled program whatever the lengths; padding lies
    after every compared position and the mask is causal). A served
    token's gap is how far its reference logit lies below the reference's
    best at its position (0 where it is the reference's own choice).

    ``widest_gap``, the number the run compares with its limit, is the
    gap that ``GAP_QUANTILE`` percent of the served tokens stay within,
    not the largest: a sparse model decides a token's last expert by a
    margin that is often smaller than bfloat16's rounding of the router's
    input (64 router logits of unit scale: the 8th and 9th lie within
    0.02 of each other for a quarter of the tokens of each layer), and a
    token whose router chose otherwise in one of 8 layers lands up to 0.5
    from the reference's choice, in the sound program as under any
    control. The largest gap therefore reads how unlucky the worst token
    was, the same under bfloat16 and float8 (``max_gap``, reported); the
    quantile reads the precision every token shares (PERF.md section 2).

    With ``lowp_control`` the same number for the token each of
    ``CONTROLS`` puts first at those positions: everything the
    configuration keeps in bfloat16 rounded to float8, the experts' three
    matrix products alone in float8, and the planted fault (every token's
    last routed expert dropped). ``control_widest_gap``, the one the run
    judges, is the smallest of the three: the limit has to catch each."""
    eps = float(model["rms_norm_eps"])
    gain, head = params["RMSNorm_0"]["scale"], params["head"]
    gaps: List[np.ndarray] = []
    control_gaps: Dict[str, List[np.ndarray]] = {
        name: [] for name in (CONTROLS if lowp_control else ())
    }
    for seq in sequences:
        prompt = np.asarray(seq["prompt"], np.int32)
        served = np.asarray(seq["served"], np.int32)
        if len(served) == 0:
            continue
        full = np.concatenate([prompt, served])[:pad_to]
        padded = np.zeros((pad_to,), np.int32)
        padded[: len(full)] = full
        nxt = np.roll(padded, -1)  # position i predicts token i + 1
        # the served tokens are predicted at positions
        # len(prompt)-1 ... len(full)-2
        span = slice(len(prompt) - 1, len(full) - 1)
        tokens, nxt = jnp.asarray(padded), jnp.asarray(nxt)
        x = hidden_states(params, model, tokens)
        best, got, _ = (
            np.asarray(a) for a in read_head(x, gain, head, nxt, eps=eps, lowp=False)
        )
        gaps.append((best - got)[span])
        for name, found in control_gaps.items():
            # the reference's own logit of the token the control puts first
            x_low = hidden_states(params, model, tokens, **CONTROLS[name])
            lowp = bool(CONTROLS[name].get("lowp"))
            _, _, choice = read_head(x_low, gain, head, nxt, eps=eps, lowp=lowp)
            del x_low
            _, got_low, _ = read_head(x, gain, head, choice, eps=eps, lowp=False)
            found.append((best - np.asarray(got_low))[span])

    def quantile(parts):
        return float(np.percentile(np.concatenate(parts), GAP_QUANTILE))

    if not gaps:
        return {"widest_gap": 0.0, "tokens_compared": 0}
    every = np.concatenate(gaps)
    out = {
        "widest_gap": quantile(gaps),
        "max_gap": float(every.max()),
        "mean_gap": float(every.mean()),
        "tokens_compared": int(every.shape[0]),
        "tokens_not_reference_choice": int((every > 0).sum()),
    }
    for name, found in control_gaps.items():
        out[f"control_{name}_widest_gap"] = quantile(found)
        out[f"control_{name}_not_reference_choice"] = int(
            (np.concatenate(found) > 0).sum()
        )
    if control_gaps:
        out["control_widest_gap"] = min(
            out[f"control_{name}_widest_gap"] for name in control_gaps
        )
    return out
