"""Plain reference of Solar-Open2-250B as one chip of eight serves it: one
full forward pass over a prompt with its served tokens, float32 at the
highest matmul precision, no cache, no kernels, no batching, no chunking,
no program code.

The layer, from the model's public ``config.json`` and what the
configuration's file lists under ``assumed`` (``x`` the residual stream;
the numbers in brackets are the published values):

1. ``x = E[tokens]``: no position table and no rotation anywhere
   (``use_rope`` false): the order comes from the causal mask and from the
   recurrent layers.
2. ``u = RMSNorm(x; g1, eps)`` [1e-5]; ``h = x + Mixer_l(u)``. Layer ``l``
   is an attention layer if ``l`` is in ``gqa_layers`` [0, 4, 8, ...], else
   a KDA layer.
3. Attention layer: ``q = u Wq`` [64 heads of 128], ``k = u Wk``, ``v = u
   Wv`` [8 heads of 128] (the program holds the three as one matrix
   ``qkv``: query columns, then key, then value), scores ``q . k /
   sqrt(head_dim)``, causal, float32 softmax, query head ``j`` reads
   key/value head ``j // (heads / kv_heads)``; ``(concat(heads) *
   sigmoid(u W_gate)) Wo`` (``use_gqa_gate``: a gate a channel).
4. KDA layer (gated delta-rule linear attention, a decay a channel;
   arXiv:2510.26692) [64 heads, keys and values 128 wide]: ``q, k, v =
   SiLU(conv(u Wq)), SiLU(conv(u Wk)), SiLU(conv(u Wv))`` (one matrix
   ``kda_qkv``; ``conv`` a causal depthwise convolution of 4 taps, tap 3
   on the current row, zeros before the sequence's start, no bias); ``q``
   and ``k`` L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q``
   times ``head_dim ** -0.5``. Log decay a channel of the key: ``g_t =
   -exp(A_log_h) softplus((u W_f1) W_f2 + dt_bias)``; ``beta_t = 2
   sigmoid(u w_beta)`` a head (``kda_allow_neg_eigval``). The recurrence,
   a head, **as a ``lax.scan`` over tokens** (``S [dk, dv]`` from zeros):
   ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
   v_t^T``, ``o_t = S_t^T q_t``. Out: ``(RMSNorm_head(o_t; g) *
   sigmoid((u W_g1) W_g2)) Wo`` (the norm over a head's 128 channels, its
   gain shared by the heads).
5. ``u2 = RMSNorm(h; g2)``; router ``p = softmax(u2 Wr)`` over all
   ``router_experts`` [320] in float32, the ``num_experts_per_tok`` [8]
   largest, renormalised to sum 1 (``norm_topk_prob``;
   ``routed_scaling_factor`` 1); ``y = sum_{e in top8, e held} w_e E_e(u2)
   + E_shared(u2)``, every expert ``Wd (silu(Wg u) * Wu u)`` of width
   1280. **Held** are the experts ``held_experts = [first, count]`` [0,
   40]: the chip's share; what the absent 280 would have added is left
   out, and that partial result goes on (``x = h + y``). Every held expert
   is computed for every token and the sum is masked by the routing
   weights.
6. Final RMSNorm; ``logits = x Wh`` over the chip's slice of the
   vocabulary [24,576 of 196,608 rows], a head of its own, float32.

So that 6.6 GB of bfloat16 weights stay on a 16 GB chip beside the pass:
the layers run one after another from the leaves as the program holds
them, each leaf cast to float32 where it is used (an expert at a time);
attention runs over blocks of query rows; the head over blocks of
positions.

``lowp`` is the control (``PERF.md``): what the configuration keeps in
bfloat16 is rounded to float8 e4m3 (per-tensor scaled): both operands of
every matrix product but the router's, the rule's ``q``, ``k`` and ``v``,
the convolution's input rows and the residual stream between blocks. The
state, the decays and ``beta`` stay float32, as the configuration keeps
them. Four planted faults, for ``--with-control``: ``state_lost`` zeroes
every KDA layer's state at the prompt's end (a prefill that never wrote
the slot's block), ``beta_halved`` leaves ``beta`` without its factor 2,
``decay_a_head`` replaces a head's 128 log decays by their mean (a decay a
head, not a channel), ``shared_dropped`` leaves the shared expert out.

Weights arrive as the nested dict the benchmark made (``zkbench/
weights.py``) under flax's names for the repo's module: ``embed``,
``head``, ``RMSNorm_0``, ``block{i}/{RMSNorm_0, RMSNorm_1, router,
experts_gate, experts_up, experts_down, shared_gate, shared_up,
shared_down}`` and, an attention layer, ``qkv, proj, attn_gate``, a KDA
layer ``kda_qkv, kda_f1, kda_f2, kda_g1, kda_g2, kda_beta, kda_out,
kda_norm, kda_conv_kernel, kda_dt_bias, kda_A_log`` (dense layers hold a
``kernel [in, out]``, norms a ``scale``; held expert ``e``'s matrices are
column block ``e`` of the three ``experts_*`` leaves; ``kda_conv_kernel``
is ``[taps, 3 x heads x head_dim]``).
"""

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


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def sizes(model: Dict) -> Dict:
    """The static sizes of steps 2-5, from the config's own keys."""
    linear = model["linear_attn_config"]
    return dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        kda_heads=int(linear["num_heads"]),
        kda_head_dim=int(linear["head_dim"]),
        top_k=int(model["num_experts_per_tok"]),
        held=tuple(int(n) for n in model["held_experts"]),
        eps=float(model["rms_norm_eps"]),
        beta_scale=2.0 if model["kda_allow_neg_eigval"] else 1.0,
    )


def layer_kinds(model: Dict) -> List[bool]:
    """Per layer as run, whether it is an attention layer."""
    attention = set(int(l) for l in model["gqa_layers"])
    return [l in attention for l in range(int(model["num_hidden_layers"]))]


def delta_rule(q, k, v, g, beta, state_lost_at=None):
    """Step 4's recurrence as a ``lax.scan`` over tokens, float32: ``q``,
    ``k``, ``g [s, heads, dk]``, ``v [s, heads, dv]``, ``beta [s, heads]``
    -> ``(o [s, heads, dv]``, the last state ``[heads, dk, dv])``. The
    state entering position ``state_lost_at`` is zeroed (the planted fault;
    None: never)."""
    s, heads, dk = k.shape
    lost = jnp.int32(s if state_lost_at is None else state_lost_at)

    def token(S, step):
        t, q_t, k_t, v_t, g_t, beta_t = step
        S = jnp.where(t == lost, 0.0, S)
        S = jnp.exp(g_t)[:, :, None] * S
        seen = jnp.einsum("hkv,hk->hv", S, k_t, precision=HIGHEST)
        S = S + k_t[:, :, None] * (beta_t[:, None] * (v_t - seen))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    last, o = jax.lax.scan(
        token, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
        (jnp.arange(s), q, k, v, g, beta),
    )
    return o, last


def attention_mixer(u, w, *, heads, kv_heads, head_dim, lowp):
    """Step 3: ``u [s, hidden]`` (normed) -> the mixer's output."""
    s = u.shape[0]
    pos = jnp.arange(s)
    group = heads // kv_heads
    qkv = _mm("sd,de->se", u, w["qkv"]["kernel"], lowp)
    q = qkv[:, : heads * head_dim].reshape(s, heads, head_dim)
    k = qkv[:, heads * head_dim : (heads + kv_heads) * head_dim]
    k = k.reshape(s, kv_heads, head_dim)
    v = qkv[:, (heads + kv_heads) * head_dim :].reshape(s, kv_heads, head_dim)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if lowp:
        k, v = _fp8(k), _fp8(v)

    def attend(block):
        qb, i_idx = block
        if lowp:
            qb = _fp8(qb)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * (head_dim ** -0.5)
        scores = jnp.where((pos[None, :] <= i_idx[:, None])[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        if lowp:
            p = _fp8(p)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    rows = min(QUERY_BLOCK, s)
    o = jax.lax.map(
        attend, (q.reshape(s // rows, rows, heads, head_dim), pos.reshape(s // rows, rows))
    ).reshape(s, heads * head_dim)
    gate = jax.nn.sigmoid(_mm("sd,de->se", u, w["attn_gate"]["kernel"], lowp))
    return _mm("se,ed->sd", o * gate, w["proj"]["kernel"], lowp)


def kda_mixer(u, w, state_lost_at, *, heads, head_dim, eps, beta_scale, lowp,
              decay_a_head):
    """Step 4: ``u [s, hidden]`` (normed) -> the mixer's output."""
    s = u.shape[0]
    inner = heads * head_dim
    qkv = _mm("sd,de->se", u, w["kda_qkv"]["kernel"], lowp)
    if lowp:
        qkv = _fp8(qkv)
    kernel = w["kda_conv_kernel"].astype(jnp.float32)  # [taps, channels]
    taps = kernel.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3 * inner)), qkv])
    qkv = jax.nn.silu(sum(kernel[j] * padded[j : j + s] for j in range(taps)))
    q, k, v = (x.reshape(s, heads, head_dim) for x in jnp.split(qkv, 3, axis=-1))
    q, k = _unit(q) * head_dim ** -0.5, _unit(k)
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    decay = _mm("sr,re->se", _mm("sd,dr->sr", u, w["kda_f1"]["kernel"], lowp),
                w["kda_f2"]["kernel"], lowp)
    g = -jnp.exp(w["kda_A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        decay + w["kda_dt_bias"].astype(jnp.float32)
    ).reshape(s, heads, head_dim)
    if decay_a_head:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = beta_scale * jax.nn.sigmoid(_mm("sd,dh->sh", u, w["kda_beta"]["kernel"], lowp))
    o, _ = delta_rule(q, k, v, g, beta, state_lost_at)
    o = _rms(o, w["kda_norm"]["scale"], eps).reshape(s, inner)
    gate = jax.nn.sigmoid(
        _mm("sr,re->se", _mm("sd,dr->sr", u, w["kda_g1"]["kernel"], lowp),
            w["kda_g2"]["kernel"], lowp)
    )
    return _mm("se,ed->sd", o * gate, w["kda_out"]["kernel"], lowp)


def experts(u2, w, *, top_k, held, lowp, shared: bool = True):
    """Step 5: ``u2 [s, hidden]`` (normed) -> the held experts' part of the
    routed sum plus the shared expert."""
    s, d = u2.shape
    probs = jax.nn.softmax(
        jnp.einsum("sd,de->se", u2, w["router"].astype(jnp.float32), precision=HIGHEST),
        axis=-1,
    )
    top_w, top_e = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # [s, experts]: the renormalised weight of a token's routed experts,
    # zero elsewhere
    routing = jnp.sum(
        jax.nn.one_hot(top_e, probs.shape[-1], dtype=jnp.float32) * top_w[..., None], axis=1
    )
    first, count = held
    f = w["experts_down"].shape[0]

    def block(leaf, e, width):
        return jax.lax.dynamic_slice_in_dim(leaf, e * width, width, axis=1)

    def swiglu(gate, up, down):
        hidden = jax.nn.silu(_mm("sd,df->sf", u2, gate, lowp)) * _mm("sd,df->sf", u2, up, lowp)
        return _mm("sf,fd->sd", hidden, down, lowp)

    def expert(y, e):  # e: the held expert's place in the leaves
        out = swiglu(
            block(w["experts_gate"], e, f), block(w["experts_up"], e, f),
            block(w["experts_down"], e, d),
        )
        weight = jax.lax.dynamic_index_in_dim(routing, first + e, axis=1)
        return y + weight * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(u2), jnp.arange(count))
    if shared:
        y = y + swiglu(
            w["shared_gate"]["kernel"], w["shared_up"]["kernel"], w["shared_down"]["kernel"]
        )
    return y


_STATIC = (
    "attends", "heads", "kv_heads", "head_dim", "kda_heads", "kda_head_dim",
    "top_k", "held", "eps", "beta_scale", "lowp", "decay_a_head", "shared",
)


@partial(jax.jit, static_argnames=_STATIC)
def layer_forward(
    x, w, state_lost_at, *, attends, heads, kv_heads, head_dim, kda_heads,
    kda_head_dim, top_k, held, eps, beta_scale, lowp, decay_a_head=False,
    shared=True,
):
    """Steps 2-5 for one layer: ``x [s, hidden] float32 -> [s, hidden]``.
    ``state_lost_at`` (a traced position; ``s`` or more: never),
    ``beta_scale``, ``decay_a_head`` and ``shared`` are the planted
    faults."""
    u = _rms(x, w["RMSNorm_0"]["scale"], eps)
    if attends:
        x = x + attention_mixer(
            u, w, heads=heads, kv_heads=kv_heads, head_dim=head_dim, lowp=lowp
        )
    else:
        x = x + kda_mixer(
            u, w, state_lost_at, heads=kda_heads, head_dim=kda_head_dim, eps=eps,
            beta_scale=beta_scale, lowp=lowp, decay_a_head=decay_a_head,
        )
    u2 = _rms(x, w["RMSNorm_1"]["scale"], eps)
    x = x + experts(u2, w, top_k=top_k, held=held, lowp=lowp, shared=shared)
    return _fp8(x) if lowp else x


@partial(jax.jit, static_argnames=("eps", "lowp"))
def read_head(x, gain, head, nxt, *, eps, lowp):
    """Step 6 over blocks of positions: ``(best [s], got [s], choice
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
                  state_lost_at=None, beta_halved: bool = False,
                  decay_a_head: bool = False, shared_dropped: bool = False):
    """Steps 1-5 through every layer: ``tokens [s] -> x [s, hidden]``. The
    keyword arguments past ``lowp`` are the planted faults of
    :func:`layer_forward`."""
    s = tokens.shape[0]
    state_lost_at = jnp.int32(s if state_lost_at is None else state_lost_at)
    static = sizes(model)
    if beta_halved:
        static["beta_scale"] = static["beta_scale"] / 2.0
    x = params["embed"][tokens].astype(jnp.float32)
    if lowp:
        x = _fp8(x)
    for i, attends in enumerate(layer_kinds(model)):
        x = layer_forward(
            x, params[f"block{i}"], state_lost_at, attends=attends,
            lowp=bool(lowp), decay_a_head=bool(decay_a_head),
            shared=not shared_dropped, **static,
        )
    return x


def forward(params: Dict, model: Dict, tokens, lowp: bool = False, **faults):
    """All six steps: ``tokens [s] -> logits [s, vocab]`` (for the CPU
    tests; the benchmark reads the head in blocks, ``read_head``)."""
    x = hidden_states(params, model, tokens, lowp, **faults)
    x = _rms(x, params["RMSNorm_0"]["scale"], float(model["rms_norm_eps"]))
    return _mm("sd,dv->sv", x, params["head"], lowp)


#: The share of the served tokens the compared gap covers (see
#: :func:`served_token_gaps`).
GAP_QUANTILE = 90.0

#: The control and the planted faults ``--with-control`` judges in the
#: program's place: keyword arguments of :func:`hidden_states`, a value
#: ``"prompt_end"`` standing for the prompt's length.
CONTROLS = {
    "all_fp8": {"lowp": True},
    "state_lost": {"state_lost_at": "prompt_end"},
    "beta_halved": {"beta_halved": True},
    "decay_a_head": {"decay_a_head": True},
    "shared_dropped": {"shared_dropped": True},
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
    margin that is often smaller than bfloat16's rounding of the router's
    input, and a token whose router chose otherwise lands far from the
    reference's choice in the sound program as under any control
    (``max_gap``, reported; ``reference/mellum2.py`` has the argument).

    With ``lowp_control`` the same number for the token each of
    ``CONTROLS`` puts first at those positions. ``control_widest_gap``,
    the one the run judges, is the smallest of them: the limit has to
    catch each."""
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
            kwargs = {
                k: (len(prompt) if v == "prompt_end" else v)
                for k, v in CONTROLS[name].items()
            }
            x_low = hidden_states(params, model, tokens, **kwargs)
            lowp = bool(kwargs.get("lowp"))
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
        out[f"control_{name}_max_gap"] = float(np.concatenate(found).max())
        out[f"control_{name}_not_reference_choice"] = int(
            (np.concatenate(found) > 0).sum()
        )
    if control_gaps:
        out["control_widest_gap"] = min(
            out[f"control_{name}_widest_gap"] for name in control_gaps
        )
    return out
