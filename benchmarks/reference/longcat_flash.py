"""Plain reference of LongCat-Flash (-Chat): one full forward pass over a
prompt with its served tokens, float32 at the highest matmul precision, no
cache, no kernels, no batching, the EXPANDED form of latent attention.

One LongCat layer (a "double layer"), from the model's public
``config.json`` and its modelling code (``x`` the residual stream; every
norm an RMSNorm with ``rms_norm_eps`` [1e-5]; the numbers in brackets are
LongCat-Flash-Chat's):

1. ``x = E[tokens]``; no position table (the rotation acts in attention).
2. ``h1 = x + MLA_0(norm_in0(x))``;
3. ``u = norm_post0(h1)``, ONE normed input read twice: ``m = MoE(u)`` (the
   shortcut branch) and ``h2 = h1 + FFN_0(u)``, a dense SwiGLU of
   ``ffn_hidden_size`` [12,288];
4. ``h3 = h2 + MLA_1(norm_in1(h2))``;
5. ``x = h3 + FFN_1(norm_post1(h3)) + m``: the branch joins at the
   layer's end.

``MLA`` [64 heads; ``q_lora_rank`` 1,536, ``kv_lora_rank`` 512, nope 128,
rope 64, v 128]: ``q = s_q W_qb(norm_q(W_qa u))`` with ``s_q =
sqrt(hidden_size / q_lora_rank)`` [2.0] on the whole query
(``mla_scale_q_lora``); ``[c, k_pe] = u W_kva``, ``c = s_kv norm_kv(c)``
with ``s_kv = sqrt(hidden_size / kv_lora_rank)`` [sqrt 12]
(``mla_scale_kv_lora``); ``[k_nope_h, v_h] = c W_kvb``; ``q_pe`` and
``k_pe`` rotated (rotate-half over the 64 columns, ``rope_theta`` [1e7], no
YaRN key; ``k_pe`` one head that every head shares); scores ``q_h . k_h /
sqrt(nope + rope)``, causal, float32 softmax; ``concat_h(softmax v_h)
W_o``.

``MoE(u)``: ``p = softmax(u W_r)`` over ``router_experts + zero_expert_num``
[512 + 256] columns in float32; the ``moe_topk`` [12] largest of ``p + b``
(``router_bias``: the correction bias picks, it does not weigh); ``m =
sum_{e chosen} routed_scaling_factor [6] * p_e * E_e(u)``, not
renormalised. ``E_e`` is a SwiGLU of ``expert_ffn_hidden_size`` [2,048] for
a real expert and ``E_e(u) = u`` for an identity one (``e >= 512``).
**Held** are the real experts ``held_experts = [first, count]`` [0, 16]:
the sum runs over a token's chosen experts that are held or identity; what
the absent experts would add is left out, here as in the program, and that
partial result goes on. Only the rows routed to a held expert are computed
(sorted by expert, a fixed capacity an expert, the rest masked); the
identity part for its chosen rows only (a weight that is 0 elsewhere).

6. Final RMSNorm; ``logits = x Wh``, a head of its own, float32.

So that the pass fits beside 10.35 GB of bfloat16 weights at 6,144
positions: the sub-layers run one after another from the leaves as the
program holds them, each leaf cast to float32 where it is used (an expert
at a time); attention runs over blocks of query rows; the head only over
the positions that predict served tokens.

``lowp`` is the control: both operands of every matrix product but the
router's, ``q``, ``k`` and ``v``, the softmax's output and the residual
stream between sub-layers rounded to float8 e4m3 (per-tensor scaled). The
planted faults, for ``--with-control``: ``zero_dropped`` (the identity
experts add nothing), ``bias_ignored`` (the choice by ``p`` alone),
``topk_renormalised``, ``scale_off`` (no ``routed_scaling_factor``),
``moe_from_second`` (the MoE fed ``norm_post1(h3)``), ``kv_scale_off``
(no ``s_kv``), ``q_scale_off`` (no ``s_q``), ``q_norm_off`` (no
``norm_q``).

Weights arrive as the nested dict the benchmark made (``zkbench/
weights.py``) under flax's names for the repo's module, sub-layer ``i`` of
the program being ``block{i}`` (LongCat layer ``i // 2``): ``embed``,
``head``, ``RMSNorm_0`` (final), ``block{i}/{RMSNorm_0, mla_q_a,
mla_q_norm, mla_q_b, mla_kv_a, mla_kv_norm, mla_kv_b, proj, RMSNorm_1,
gate, up, down}`` and the first of a pair's ``router``, ``router_bias``,
``experts_gate``, ``experts_up``, ``experts_down`` (dense layers hold a
``kernel [in, out]``, norms a ``scale``; ``mla_kv_b`` is a bare ``[r,
heads x (n + v)]``; held expert ``e``'s matrices are column block ``e`` of
the three ``experts_*`` leaves).
"""

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
#: Rows of the served positions the head reads at once (one program).
HEAD_ROWS = 2048
#: An expert's rows are computed in blocks of this many (its capacity is
#: the largest count rounded up to it).
CAPACITY_ROUND = 256


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


def sizes(model: Dict) -> Dict:
    """The static sizes of the attention, from the config's own keys."""
    d = int(model["hidden_size"])
    q_rank, rank = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    return dict(
        heads=int(model["num_attention_heads"]),
        nope=int(model["qk_nope_head_dim"]),
        rope=int(model["qk_rope_head_dim"]),
        v_dim=int(model["v_head_dim"]),
        rank=rank,
        eps=float(model["rms_norm_eps"]),
        q_scale=(d / q_rank) ** 0.5 if model["mla_scale_q_lora"] else 1.0,
        kv_scale=(d / rank) ** 0.5 if model["mla_scale_kv_lora"] else 1.0,
    )


def _rotate(x, cos, sin):
    """Rotate-half over the last axis: ``x [s, ..., p]``, ``cos``/``sin``
    ``[s, p / 2]``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1] // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, w, cos, sin, *, heads, nope, rope, v_dim, rank, eps,
              q_scale, kv_scale, lowp, q_norm):
    """The mixer on the normed ``u [s, d]``."""
    s = u.shape[0]
    pos = jnp.arange(s)
    q_a = _mm("sd,dr->sr", u, w["mla_q_a"]["kernel"], lowp)
    if q_norm:
        q_a = _rms(q_a, w["mla_q_norm"]["scale"], eps)
    q = q_scale * _mm("sr,re->se", q_a, w["mla_q_b"]["kernel"], lowp)
    q = q.reshape(s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)], axis=-1)
    kv_a = _mm("sd,de->se", u, w["mla_kv_a"]["kernel"], lowp)
    c = kv_scale * _rms(kv_a[:, :rank], w["mla_kv_norm"]["scale"], eps)
    k_pe = _rotate(kv_a[:, rank:], cos, sin)
    kv = _mm("sr,re->se", c, w["mla_kv_b"], lowp).reshape(s, heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (s, heads, rope))], axis=-1
    )
    v = kv[..., nope:]
    if lowp:
        k, v = _fp8(k), _fp8(v)
    scale = (nope + rope) ** -0.5

    def attend(block):
        qb, i_idx = block
        if lowp:
            qb = _fp8(qb)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        keep = pos[None, :] <= i_idx[:, None]
        scores = jnp.where(keep[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        if lowp:
            p = _fp8(p)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    rows = min(QUERY_BLOCK, s)
    o = jax.lax.map(
        attend, (q.reshape(s // rows, rows, heads, nope + rope), pos.reshape(s // rows, rows))
    ).reshape(s, heads * v_dim)
    return _mm("se,ed->sd", o, w["proj"]["kernel"], lowp)


_ATTN_STATIC = (
    "heads", "nope", "rope", "v_dim", "rank", "eps", "q_scale", "kv_scale",
    "lowp", "q_norm",
)


@partial(jax.jit, static_argnames=_ATTN_STATIC)
def attention_half(x, w, cos, sin, **static):
    """Steps 2 and 4: ``x -> x + MLA(norm_in(x))``."""
    u = _rms(x, w["RMSNorm_0"]["scale"], static["eps"])
    x = x + attention(u, w, cos, sin, **static)
    return _fp8(x) if static["lowp"] else x


def _swiglu(u, gate, up, down, lowp):
    hidden = jax.nn.silu(_mm("sd,df->sf", u, gate, lowp)) * _mm("sd,df->sf", u, up, lowp)
    return _mm("sf,fd->sd", hidden, down, lowp)


@partial(jax.jit, static_argnames=("eps",))
def post_norm(h, w, *, eps):
    """A sub-layer's MLP input: ``norm_post(h)``."""
    return _rms(h, w["RMSNorm_1"]["scale"], eps)


@partial(jax.jit, static_argnames=("lowp",))
def dense_half(h, u, w, carried, *, lowp):
    """A sub-layer's dense SwiGLU on its normed ``u``: ``h + FFN(u) +
    carried`` (the branch's sum at the second sub-layer, 0 at the first)."""
    x = h + _swiglu(u, w["gate"]["kernel"], w["up"]["kernel"], w["down"]["kernel"], lowp)
    x = x + carried
    return _fp8(x) if lowp else x


@partial(jax.jit, static_argnames=("top_k", "renormalize", "bias", "scale", "real"))
def route(u, w, *, top_k, renormalize, bias, scale, real):
    """The router on the normed ``u``: ``(weights [s, k], experts [s, k],
    rows a column [E])``; experts at or past ``real`` are identity ones."""
    probs = jax.nn.softmax(
        jnp.einsum("sd,de->se", u, w["router"].astype(jnp.float32), precision=HIGHEST),
        axis=-1,
    )
    score = probs + w["router_bias"].astype(jnp.float32) if bias else probs
    _, top_e = jax.lax.top_k(score, top_k)
    top_w = jnp.take_along_axis(probs, top_e, axis=-1)
    if renormalize:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    counts = jnp.bincount(top_e.reshape(-1), length=probs.shape[-1])
    return scale * top_w, top_e, counts


@partial(jax.jit, static_argnames=("cap", "lowp", "held", "real", "identity"))
def experts(u, top_w, top_e, w, *, cap, lowp, held, real, identity):
    """The MoE's held and identity part for the normed ``u [s, d]``: the
    routed pairs sorted by expert; held expert ``e`` computes the ``cap``
    rows from its first pair (the rows past its own count are masked), its
    weighted output added to its tokens' rows; a token's weight on the
    identity experts times its own ``u``."""
    s, d = u.shape
    k = top_e.shape[1]
    first, count = held
    f = w["experts_down"].shape[0]
    flat = top_e.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tokens = jnp.concatenate([(jnp.arange(s * k) // k)[order], jnp.zeros((cap,), jnp.int32)])
    weights = jnp.concatenate([top_w.reshape(-1)[order], jnp.zeros((cap,), jnp.float32)])
    counts = jnp.bincount(flat, length=w["router"].shape[1])
    starts = jnp.cumsum(counts) - counts

    def block(leaf, e, width):
        return jax.lax.dynamic_slice_in_dim(leaf, e * width, width, axis=1)

    def expert(y, e):  # e: the held expert's place in the leaves
        g = first + e
        rows = jax.lax.dynamic_slice_in_dim(tokens, starts[g], cap)
        wt = jax.lax.dynamic_slice_in_dim(weights, starts[g], cap)
        wt = jnp.where(jnp.arange(cap) < counts[g], wt, 0.0)
        out = _swiglu(
            u[rows], block(w["experts_gate"], e, f), block(w["experts_up"], e, f),
            block(w["experts_down"], e, d), lowp,
        )
        return y.at[rows].add(wt[:, None] * out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(u), jnp.arange(count))
    if identity:
        zero = jnp.sum(jnp.where(top_e >= real, top_w, 0.0), axis=1)
        y = y + zero[:, None] * u
    return y


def hidden_states(
    params: Dict, model: Dict, tokens, lowp: bool = False, *,
    zero_dropped: bool = False, bias_ignored: bool = False,
    topk_renormalised: bool = False, scale_off: bool = False,
    moe_from_second: bool = False, kv_scale_off: bool = False,
    q_scale_off: bool = False, q_norm_off: bool = False,
):
    """Steps 1-5 through every layer: ``tokens [s] -> x [s, hidden]``. The
    keyword arguments are the planted faults."""
    s = tokens.shape[0]
    static = sizes(model)
    if kv_scale_off:
        static["kv_scale"] = 1.0
    if q_scale_off:
        static["q_scale"] = 1.0
    eps = static["eps"]
    p = static["rope"]
    inv = 1.0 / float(model["rope_theta"]) ** (np.arange(0, p, 2) / p)
    angles = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)
    sin = jnp.asarray(np.sin(angles), jnp.float32)
    real = int(model["router_experts"])
    held = tuple(int(n) for n in model["held_experts"])
    top_k = int(model["moe_topk"])
    attn = dict(static, lowp=bool(lowp), q_norm=not q_norm_off)
    x = params["embed"][tokens].astype(jnp.float32)
    if lowp:
        x = _fp8(x)
    for i in range(int(model["num_layers"])):
        w0, w1 = params[f"block{2 * i}"], params[f"block{2 * i + 1}"]
        h1 = attention_half(x, w0, cos, sin, **attn)
        u = post_norm(h1, w0, eps=eps)
        h2 = dense_half(h1, u, w0, jnp.zeros_like(h1), lowp=bool(lowp))
        h3 = attention_half(h2, w1, cos, sin, **attn)
        u1 = post_norm(h3, w1, eps=eps)
        source = u1 if moe_from_second else u
        top_w, top_e, counts = route(
            source, w0, top_k=top_k, renormalize=topk_renormalised,
            bias=not bias_ignored,
            scale=1.0 if scale_off else float(model["routed_scaling_factor"]),
            real=real,
        )
        most = int(np.max(np.asarray(counts)[held[0]: held[0] + held[1]]))
        cap = max(CAPACITY_ROUND, -(-most // CAPACITY_ROUND) * CAPACITY_ROUND)
        m = experts(
            source, top_w, top_e, w0, cap=cap, lowp=bool(lowp), held=held,
            real=real, identity=not zero_dropped,
        )
        x = dense_half(h3, u1, w1, m, lowp=bool(lowp))
    return x


@partial(jax.jit, static_argnames=("eps", "lowp"))
def read_head(x, gain, head, nxt, *, eps, lowp):
    """Step 6 over ``x [rows, d]``: ``(best, got, choice)`` a row: the best
    logit, the logit of the token that came next, and the token this pass
    puts first."""
    x = _rms(x, gain, eps)
    head = head.astype(jnp.float32)
    if lowp:
        x, head = _fp8(x), _fp8(head)
    logits = jnp.einsum("sd,dv->sv", x, head, precision=HIGHEST)
    got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1), got, jnp.argmax(logits, axis=-1)


def forward(params: Dict, model: Dict, tokens, lowp: bool = False, **faults):
    """Every step: ``tokens [s] -> logits [s, vocab]`` (for the CPU tests;
    the benchmark reads the head over the served positions,
    ``read_head``)."""
    x = hidden_states(params, model, tokens, lowp, **faults)
    x = _rms(x, params["RMSNorm_0"]["scale"], float(model["rms_norm_eps"]))
    return _mm("sd,dv->sv", x, params["head"], lowp)


#: The share of the served tokens the compared gap covers (a sparse
#: model: see :func:`served_token_gaps`).
GAP_QUANTILE = 90.0

#: The control and the planted faults ``--with-control`` judges in the
#: program's place: keyword arguments of :func:`hidden_states`.
CONTROLS = {
    "all_fp8": {"lowp": True},
    "zero_dropped": {"zero_dropped": True},
    "bias_ignored": {"bias_ignored": True},
    "topk_renormalised": {"topk_renormalised": True},
    "scale_off": {"scale_off": True},
    "moe_from_second": {"moe_from_second": True},
    "kv_scale_off": {"kv_scale_off": True},
    "q_scale_off": {"q_scale_off": True},
    "q_norm_off": {"q_norm_off": True},
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
    after every compared position and every step is causal). A served
    token's gap is how far its reference logit lies below the reference's
    best at its position (0 where it is the reference's own choice).

    ``widest_gap``, the number the run compares with its limit, is the
    gap that ``GAP_QUANTILE`` percent of the served tokens stay within,
    not the largest: a sparse model decides a token's last expert by a
    margin often smaller than bfloat16's rounding of the router's input
    (``reference/mellum2.py`` has the argument; ``max_gap`` is reported).

    With ``lowp_control`` the same number for the token each of
    ``CONTROLS`` puts first at those positions; ``control_widest_gap`` is
    the smallest of them: the limit has to catch each."""
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
        rows = min(HEAD_ROWS, pad_to)
        lo = min(len(prompt) - 1, pad_to - rows)
        span = slice(len(prompt) - 1 - lo, min(len(full) - 1 - lo, rows))
        rows_nxt = jnp.asarray(nxt[lo : lo + rows])
        tokens = jnp.asarray(padded)
        x = hidden_states(params, model, tokens)[lo : lo + rows]
        best, got, _ = (
            np.asarray(a) for a in read_head(x, gain, head, rows_nxt, eps=eps, lowp=False)
        )
        gaps.append((best - got)[span])
        for name, found in control_gaps.items():
            kwargs = CONTROLS[name]
            x_low = hidden_states(params, model, tokens, **kwargs)[lo : lo + rows]
            lowp = bool(kwargs.get("lowp"))
            _, _, choice = read_head(x_low, gain, head, rows_nxt, eps=eps, lowp=lowp)
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
        out[f"control_{name}_max_gap"] = float(np.concatenate(found).max())
        out[f"control_{name}_not_reference_choice"] = int(
            (np.concatenate(found) > 0).sum()
        )
    if control_gaps:
        out["control_widest_gap"] = min(
            out[f"control_{name}_widest_gap"] for name in control_gaps
        )
    return out
