"""Plain reference of DeepSeek-V2 (-Lite): one full forward pass over a
prompt with its served tokens, float32 at the highest matmul precision, no
cache, no kernels, no batching, the EXPANDED form of latent attention.

The layer, from the model's public ``config.json`` (``x`` the residual
stream; the numbers in brackets are DeepSeek-V2-Lite's):

1. ``x = E[tokens]``; no position table (the rotation acts in attention).
2. ``u = RMSNorm(x; g1, eps)`` [1e-6]; ``h = x + MLA(u)``:
   ``q = u Wq`` [16 heads of ``qk_nope_head_dim + qk_rope_head_dim`` = 128
   + 64; no ``q_lora_rank``], ``q = [q_nope, q_pe]``; ``[c, k_pe] = u
   Wkv_a`` [``kv_lora_rank`` 512 + 64], ``c = RMSNorm(c; g_kv, 1e-6)``;
   ``q_pe`` and ``k_pe`` rotated at their positions (rotate-half over the 64
   columns; ``k_pe`` one head that every head shares); ``[k_nope_h, v_h] =
   c Wkv_b`` [a head: 128 then ``v_head_dim`` 128]; ``k_h = [k_nope_h,
   k_pe]``; scores ``q_h . k_h * softmax_scale``, causal, float32
   softmax; ``concat_h(softmax v_h) Wo``.
3. YaRN (``rope_scaling``) [factor 40, original 4,096, beta_fast 32,
   beta_slow 1, mscale = mscale_all_dim = 0.707, theta 10,000], as
   DeepSeek's own modelling code has it and computed HERE from the config
   (no program code): with ``d(b) = p ln(original / (2 pi b)) / (2 ln
   theta)``, ``low = max(floor(d(beta_fast)), 0)``, ``high =
   min(ceil(d(beta_slow)), p - 1)``, ``ramp_i = clip((i - low) / (high -
   low), 0, 1)``, ``inv_i = theta^(-2i/p) (1 - ramp_i) + theta^(-2i/p) /
   factor ramp_i``; ``m(s) = 0.1 s ln(factor) + 1``; cos and sin times
   ``m(mscale) / m(mscale_all_dim)`` (1.0 here), ``softmax_scale = (128 +
   64)^-0.5 m(mscale_all_dim)^2`` (the factor 1.2608^2 = 1.5896).
4. ``u2 = RMSNorm(h; g2)``; the first ``first_k_dense_replace`` [1] layers:
   ``y = Wd (silu(Wg u2) * Wu u2)`` of ``intermediate_size`` [10,944]; the
   others: router ``p = softmax(u2 Wr)`` over ``n_routed_experts`` [64] in
   float32, the ``num_experts_per_tok`` [6] largest, NOT renormalised
   (``norm_topk_prob`` false; ``routed_scaling_factor`` 1); ``y = sum_e p_e
   E_e(u2) + S(u2)``, every routed expert a SwiGLU of
   ``moe_intermediate_size`` [1,408], ``S`` the ``n_shared_experts`` [2]
   as ONE SwiGLU of 2 x 1,408 (two SwiGLUs summed are one of their
   concatenated widths). Only the rows routed to an expert are computed
   (sorted by expert, a fixed capacity an expert, the rest masked).
   ``x = h + y``.
5. Final RMSNorm; ``logits = x Wh``, a head of its own, float32.

So that the pass fits beside 6.9 GB of bfloat16 weights at 18,432
positions: the layers run one after another from the leaves as the
program holds them, each leaf cast to float32 where it is used (an expert
at a time); attention runs over blocks of query rows; the head only over
the positions that predict served tokens.

``lowp`` is the control (``PERF.md``): both operands of every matrix
product but the router's, ``q``, ``k`` and ``v``, the softmax's output and
the residual stream between blocks rounded to float8 e4m3 (per-tensor
scaled). The planted faults, for ``--with-control``: ``mscale_off`` (the
score without ``m(mscale_all_dim)^2``), ``yarn_on_rotation`` (mellum2's
convention: cos and sin times ``0.1 ln(factor) + 1`` and the score
without the factor), ``k_pe_unrotated``, ``kv_norm_off``,
``topk_renormalised``, ``shared_dropped``, ``prefix_skipped`` (the
positions past the prompt, which the decode step computes, attend no row
of the shared prefix's whole pages: the decode kernel skipping them).

Weights arrive as the nested dict the benchmark made (``zkbench/
weights.py``) under flax's names for the repo's module: ``embed``,
``head``, ``RMSNorm_0``, ``block{i}/{RMSNorm_0, RMSNorm_1, mla_q, mla_kv_a,
mla_kv_norm, mla_kv_b, proj}`` and a dense layer's ``gate, up, down`` or
an expert layer's ``router, experts_gate, experts_up, experts_down,
shared_gate, shared_up, shared_down`` (dense layers hold a ``kernel [in,
out]``, norms a ``scale``; ``mla_kv_b`` is a bare ``[r, heads x (n +
v)]``; expert ``e``'s matrices are column block ``e`` of the three
``experts_*`` leaves).
"""

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
#: Rows of the served positions the head reads at once (one program).
HEAD_ROWS = 1024
#: The configuration's page size: what ``prefix_skipped`` drops is the
#: shared prefix's whole pages.
PAGE = 16


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


def yarn(model: Dict):
    """``(inv_freq [p / 2] float64, cos/sin factor, softmax scale)`` of step
    3, from the config's keys."""
    p = int(model["qk_rope_head_dim"])
    theta = float(model["rope_theta"])
    rs = model["rope_scaling"]
    factor = float(rs["factor"])
    original = float(rs["original_max_position_embeddings"])

    def dim_of(turns):
        return p * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rs["beta_slow"]))), p - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(p // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, p, 2) / p)
    inv = extra * (1.0 - ramp) + extra / factor * ramp

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    on_rotation = m(float(rs["mscale"])) / m(float(rs["mscale_all_dim"]))
    qk = int(model["qk_nope_head_dim"]) + p
    return inv, on_rotation, qk ** -0.5 * m(float(rs["mscale_all_dim"])) ** 2


def sizes(model: Dict) -> Dict:
    """The static sizes of steps 2-4, from the config's own keys."""
    return dict(
        heads=int(model["num_attention_heads"]),
        nope=int(model["qk_nope_head_dim"]),
        rope=int(model["qk_rope_head_dim"]),
        v_dim=int(model["v_head_dim"]),
        rank=int(model["kv_lora_rank"]),
        eps=float(model["rms_norm_eps"]),
    )


def _rotate(x, cos, sin):
    """Rotate-half over the last axis: ``x [s, ..., p]``, ``cos``/``sin``
    ``[s, p / 2]``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1] // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, w, cos, sin, skip, *, heads, nope, rope, v_dim, rank, eps,
              scale, lowp, rotate_k, kv_norm):
    """Step 2's mixer on the normed ``u [s, d]``. ``skip = (from, below)``:
    queries at or past ``from`` attend no row below ``below`` (the
    planted fault; ``from`` = ``s``: never)."""
    s = u.shape[0]
    pos = jnp.arange(s)
    q = _mm("sd,de->se", u, w["mla_q"]["kernel"], lowp).reshape(s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)], axis=-1)
    kv_a = _mm("sd,de->se", u, w["mla_kv_a"]["kernel"], lowp)
    c, k_pe = kv_a[:, :rank], kv_a[:, rank:]
    if kv_norm:
        c = _rms(c, w["mla_kv_norm"]["scale"], eps)
    if rotate_k:
        k_pe = _rotate(k_pe, cos, sin)
    kv = _mm("sr,re->se", c, w["mla_kv_b"], lowp).reshape(s, heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (s, heads, rope))], axis=-1
    )
    v = kv[..., nope:]
    if lowp:
        k, v = _fp8(k), _fp8(v)

    def attend(block):
        qb, i_idx = block
        if lowp:
            qb = _fp8(qb)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        keep = pos[None, :] <= i_idx[:, None]
        keep = keep & ~((i_idx[:, None] >= skip[0]) & (pos[None, :] < skip[1]))
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
    "heads", "nope", "rope", "v_dim", "rank", "eps", "scale", "lowp",
    "rotate_k", "kv_norm",
)


@partial(jax.jit, static_argnames=_ATTN_STATIC)
def attention_half(x, w, cos, sin, skip, **static):
    """Step 2: ``x -> h``."""
    u = _rms(x, w["RMSNorm_0"]["scale"], static["eps"])
    return x + attention(u, w, cos, sin, skip, **static)


def _swiglu(u, gate, up, down, lowp):
    hidden = jax.nn.silu(_mm("sd,df->sf", u, gate, lowp)) * _mm("sd,df->sf", u, up, lowp)
    return _mm("sf,fd->sd", hidden, down, lowp)


@partial(jax.jit, static_argnames=("eps", "lowp"))
def dense_half(h, w, *, eps, lowp):
    """Step 4 of a dense layer: ``h -> x``."""
    u2 = _rms(h, w["RMSNorm_1"]["scale"], eps)
    y = _swiglu(u2, w["gate"]["kernel"], w["up"]["kernel"], w["down"]["kernel"], lowp)
    x = h + y
    return _fp8(x) if lowp else x


@partial(jax.jit, static_argnames=("eps", "top_k", "renormalize"))
def route(h, w, *, eps, top_k, renormalize):
    """Step 4's router: ``(u2, weights [s, k], experts [s, k], rows an
    expert [E])``."""
    u2 = _rms(h, w["RMSNorm_1"]["scale"], eps)
    probs = jax.nn.softmax(
        jnp.einsum("sd,de->se", u2, w["router"].astype(jnp.float32), precision=HIGHEST),
        axis=-1,
    )
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    counts = jnp.bincount(top_e.reshape(-1), length=probs.shape[-1])
    return u2, top_w, top_e, counts


@partial(jax.jit, static_argnames=("cap", "lowp", "shared"))
def expert_half(h, u2, top_w, top_e, w, *, cap, lowp, shared):
    """Step 4 of an expert layer: ``h -> x``. The routed pairs sorted by
    expert; expert ``e`` computes the ``cap`` rows from its first pair (the
    rows past its own count are masked), its weighted output added to its
    tokens' rows."""
    s, d = u2.shape
    k = top_e.shape[1]
    experts = w["router"].shape[1]
    f = w["experts_down"].shape[0]
    flat = top_e.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tokens = jnp.concatenate([(jnp.arange(s * k) // k)[order], jnp.zeros((cap,), jnp.int32)])
    weights = jnp.concatenate([top_w.reshape(-1)[order], jnp.zeros((cap,), jnp.float32)])
    counts = jnp.bincount(flat, length=experts)
    starts = jnp.cumsum(counts) - counts

    def block(leaf, e, width):
        return jax.lax.dynamic_slice_in_dim(leaf, e * width, width, axis=1)

    def expert(y, e):
        rows = jax.lax.dynamic_slice_in_dim(tokens, starts[e], cap)
        wt = jax.lax.dynamic_slice_in_dim(weights, starts[e], cap)
        wt = jnp.where(jnp.arange(cap) < counts[e], wt, 0.0)
        out = _swiglu(
            u2[rows], block(w["experts_gate"], e, f), block(w["experts_up"], e, f),
            block(w["experts_down"], e, d), lowp,
        )
        return y.at[rows].add(wt[:, None] * out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(u2), jnp.arange(experts))
    if shared:
        y = y + _swiglu(
            u2, w["shared_gate"]["kernel"], w["shared_up"]["kernel"],
            w["shared_down"]["kernel"], lowp,
        )
    x = h + y
    return _fp8(x) if lowp else x


def hidden_states(
    params: Dict, model: Dict, tokens, lowp: bool = False, *,
    mscale_off: bool = False, yarn_on_rotation: bool = False,
    k_pe_unrotated: bool = False, kv_norm_off: bool = False,
    topk_renormalised: bool = False, shared_dropped: bool = False,
    prefix_skipped=None,
):
    """Steps 1-4 through every layer: ``tokens [s] -> x [s, hidden]``. The
    keyword arguments are the planted faults; ``prefix_skipped = (from,
    below)`` or None."""
    s = tokens.shape[0]
    static = sizes(model)
    inv, on_rotation, scale = yarn(model)
    rs = model["rope_scaling"]
    if yarn_on_rotation:
        on_rotation = 0.1 * math.log(float(rs["factor"])) + 1.0
    if mscale_off or yarn_on_rotation:
        scale = (static["nope"] + static["rope"]) ** -0.5
    angles = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angles) * on_rotation, jnp.float32)
    sin = jnp.asarray(np.sin(angles) * on_rotation, jnp.float32)
    skip = jnp.asarray(prefix_skipped if prefix_skipped else (s, 0), jnp.int32)
    dense = int(model["first_k_dense_replace"])
    x = params["embed"][tokens].astype(jnp.float32)
    if lowp:
        x = _fp8(x)
    for i in range(int(model["num_hidden_layers"])):
        w = params[f"block{i}"]
        h = attention_half(
            x, w, cos, sin, skip, scale=float(scale), lowp=bool(lowp),
            rotate_k=not k_pe_unrotated, kv_norm=not kv_norm_off, **static,
        )
        if i < dense:
            x = dense_half(h, w, eps=static["eps"], lowp=bool(lowp))
            continue
        u2, top_w, top_e, counts = route(
            h, w, eps=static["eps"], top_k=int(model["num_experts_per_tok"]),
            renormalize=bool(model["norm_topk_prob"]) or topk_renormalised,
        )
        cap = -(-int(np.max(np.asarray(counts))) // 512) * 512
        x = expert_half(
            h, u2, top_w, top_e, w, cap=cap, lowp=bool(lowp),
            shared=not shared_dropped,
        )
    return x


@partial(jax.jit, static_argnames=("eps", "lowp"))
def read_head(x, gain, head, nxt, *, eps, lowp):
    """Step 5 over ``x [rows, d]``: ``(best, got, choice)`` a row: the best
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
    """All five steps: ``tokens [s] -> logits [s, vocab]`` (for the CPU
    tests; the benchmark reads the head over the served positions,
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
    "mscale_off": {"mscale_off": True},
    "yarn_on_rotation": {"yarn_on_rotation": True},
    "k_pe_unrotated": {"k_pe_unrotated": True},
    "kv_norm_off": {"kv_norm_off": True},
    "topk_renormalised": {"topk_renormalised": True},
    "shared_dropped": {"shared_dropped": True},
    "prefix_skipped": {"prefix_skipped": "decode_past_prefix"},
}


def _shared_prefix(sequences: List[Dict]) -> int:
    """The prompts' longest common prefix, down to whole pages."""
    prompts = [np.asarray(s["prompt"]) for s in sequences]
    n = min(len(p) for p in prompts)
    for p in prompts[1:]:
        differ = np.flatnonzero(p[:n] != prompts[0][:n])
        if len(differ):
            n = int(differ[0])
    return n // PAGE * PAGE


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
    prefix = _shared_prefix(sequences) if len(sequences) > 1 else 0
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
        # the served tokens are predicted at positions len(prompt) - 1 ...
        # len(full) - 2: the head reads HEAD_ROWS rows from the first
        rows = min(HEAD_ROWS, pad_to)
        lo = min(len(prompt) - 1, pad_to - rows)
        span = slice(len(prompt) - 1 - lo, len(full) - 1 - lo)
        rows_nxt = jnp.asarray(nxt[lo : lo + rows])
        tokens = jnp.asarray(padded)
        x = hidden_states(params, model, tokens)[lo : lo + rows]
        best, got, _ = (
            np.asarray(a) for a in read_head(x, gain, head, rows_nxt, eps=eps, lowp=False)
        )
        gaps.append((best - got)[span])
        for name, found in control_gaps.items():
            kwargs = dict(CONTROLS[name])
            if kwargs.get("prefix_skipped"):
                kwargs["prefix_skipped"] = (len(prompt), prefix)
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
        "shared_prefix": prefix,
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
