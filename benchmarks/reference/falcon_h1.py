"""Plain reference of Falcon-H1-34B-Instruct as served: one full forward
pass over a prompt with its served tokens, float32 at the highest matmul
precision, no cache, no kernels, no batching, no chunking, no program code.

The layer, from the model's public ``config.json`` (``x`` the residual
stream; the numbers in brackets are the published 34B values):

1. ``x = E[tokens] * embedding_multiplier`` [5.657]; no position table.
2. ``h = RMSNorm(x; g1, eps)`` [1e-5]. Both mixers read this one ``h`` and
   their outputs are summed into the stream:
   ``x = x + attention_out_multiplier * Attn(attention_in_multiplier * h)
   + ssm_out_multiplier * SSM(ssm_in_multiplier * h)`` [0.0375, 1;
   0.0884, 0.25].
3. ``Attn(u)``: ``q = u Wq`` (hidden -> heads x head_dim), ``k = (u Wk) *
   key_multiplier`` [0.01105], ``v = u Wv`` (-> kv_heads x head_dim), no
   biases (the program holds the three as one matrix ``qkv``: query
   columns, then key, then value); rotary positions on ``q`` and ``k``
   over the whole head, rotate-half, ``inv_i = theta ** (-2 i /
   head_dim)`` [theta 1e11], no scaling; scores ``q . k / sqrt(head_dim)``,
   causal, float32 softmax, query head ``j`` reads key/value head ``j //
   (heads / kv_heads)``; ``concat(heads) Wo``.
4. ``SSM(u)``: ``p = (u W_in) * m``; ``W_in``'s columns are, in order, the
   gate ``z`` [4096], ``x`` [4096], ``B`` and ``C`` [groups x state = 512
   each], ``dt`` [32 heads], and ``m`` multiplies those five segments by
   ``ssm_multipliers[0..4]``. ``x``, ``B``, ``C`` together go through a
   causal depthwise convolution of ``mamba_d_conv`` [4] taps with a bias
   (tap 3 meets the current row; rows before the sequence's start are
   zero), then ``silu``, then split into ``x [heads, head_dim]``, ``B``,
   ``C [groups, state]`` (a group serves ``heads / groups`` heads). ``dt =
   softplus(dt + dt_bias)`` a head, not clamped; ``A = -exp(A_log)`` a
   head. The recurrence, a head, **as a ``lax.scan`` over tokens**:
   ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (``S`` is ``[head_dim,
   state]``, zero before the first token), ``y_t = S_t C_t + D x_t``. Then
   (``mamba_rms_norm`` true, ``mamba_norm_before_gate`` false) ``y =
   RMSNorm_grouped(y * silu(z); g)``: statistics over each group's
   channels [2 x 2048], eps as above. ``y W_out``, no bias.
5. ``h2 = RMSNorm(x; g2)``; ``x = x + (W_down (W_up h2 * silu(W_gate h2 *
   mlp_multipliers[0]))) * mlp_multipliers[1]``, no biases.
6. Final RMSNorm; ``logits = (x W_head) * lm_head_multiplier`` [1/128], a
   head of its own, float32.

Departures and what the config's keys do not pin down (the configuration
file lists them under ``assumed``): the order of ``W_in``'s segments, that
``key_multiplier`` scales keys before the rotation, that the gated norm's
statistics are a group's, no clamp on ``dt``; RMSNorm is ``x / sqrt(mean
x^2 + eps) * g``.

So that 8.8 GB of bfloat16 weights stay on a 16 GB chip beside the pass:
the layers run one after another from the leaves as the program holds
them, each leaf cast to float32 where it is used; attention runs over
blocks of query rows; the head runs over blocks of vocabulary columns
(its float32 copy alone would be 5.35 GB) and keeps, for each position,
the best logit, the logit of the token that came next, and the first
choice. No table is ever cast whole.

``lowp`` is the control (``PERF.md``): what the configuration keeps in
bfloat16 is rounded to float8 e4m3 (per-tensor scaled): both operands of
every matrix product, the recurrence's ``x``, ``B`` and ``C``, the
convolution's input rows and the residual stream between blocks. The
recurrence's state, ``dt``, ``A`` and the decay stay float32, as the
configuration keeps them. Two planted faults, for ``--with-control``:
``state_lost`` zeroes every layer's state at the prompt's end (a prefill
that never wrote the slot's state), ``conv_lost`` zeroes the
convolution's rows there (its 3-row carry).

Weights arrive as the nested dict the benchmark made (``zkbench/
weights.py``) under flax's names for the repo's module: ``embed``,
``head``, ``RMSNorm_0``, ``block{i}/{RMSNorm_0, qkv, proj, RMSNorm_1,
gate, up, down, ssm_in, ssm_out, ssm_conv_kernel, ssm_conv_bias, ssm_norm,
A_log, D, dt_bias}`` (dense layers hold a ``kernel [in, out]``, norms a
``scale``, ``ssm_conv_kernel`` is ``[taps, channels]``).
"""

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
VOCAB_BLOCK = 16384


def _fp8(x, amax=None):
    amax = jnp.max(jnp.abs(x)) if amax is None else amax
    scale = jnp.where(amax > 0, amax / 448.0, 1.0).astype(jnp.float32)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, lowp):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, gain, eps, groups: int = 1):
    s, c = x.shape
    xg = x.reshape(s, groups, c // groups)
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return xg.reshape(s, c) * gain.astype(jnp.float32)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sizes(model: Dict) -> Dict:
    """The static sizes and multipliers of steps 1-6, from the config's
    own keys."""
    return dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        ssm_heads=int(model["mamba_n_heads"]),
        ssm_head_dim=int(model["mamba_d_head"]),
        ssm_state=int(model["mamba_d_state"]),
        ssm_groups=int(model["mamba_n_groups"]),
        eps=float(model["rms_norm_eps"]),
        theta=float(model["rope_theta"]),
        attn_in=float(model["attention_in_multiplier"]),
        attn_out=float(model["attention_out_multiplier"]),
        key_mult=float(model["key_multiplier"]),
        ssm_in=float(model["ssm_in_multiplier"]),
        ssm_out=float(model["ssm_out_multiplier"]),
        ssm_mults=tuple(float(m) for m in model["ssm_multipliers"]),
        mlp_mults=tuple(float(m) for m in model["mlp_multipliers"]),
    )


def recurrence(xs, Bs, Cs, dt, A, state_lost_at=None):
    """Step 4's recurrence as a ``lax.scan`` over tokens, float32: ``xs
    [s, heads, head_dim]``, ``Bs``, ``Cs [s, heads, state]`` (a head's
    own: its group's, repeated), ``dt [s, heads]``, ``A [heads]`` ->
    ``(y [s, heads, head_dim]`` without the ``D`` skip, the last state
    ``[heads, head_dim, state])``. The state entering position
    ``state_lost_at`` is zeroed (the planted fault; None: never)."""
    s, heads, head_dim = xs.shape
    lost = jnp.int32(s if state_lost_at is None else state_lost_at)

    def token(S, step):
        t, x_t, B_t, C_t, dt_t = step
        S = jnp.where(t == lost, 0.0, S)
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        )
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    last, y = jax.lax.scan(
        token, jnp.zeros((heads, head_dim, Bs.shape[-1]), jnp.float32),
        (jnp.arange(s), xs, Bs, Cs, dt),
    )
    return y, last


_STATIC = (
    "heads", "kv_heads", "head_dim", "ssm_heads", "ssm_head_dim",
    "ssm_state", "ssm_groups", "eps", "theta", "attn_in", "attn_out",
    "key_mult", "ssm_in", "ssm_out", "ssm_mults", "mlp_mults", "lowp",
)


@partial(jax.jit, static_argnames=_STATIC)
def layer_forward(
    x, w, state_lost_at, conv_lost_at, *, heads, kv_heads, head_dim,
    ssm_heads, ssm_head_dim, ssm_state, ssm_groups, eps, theta, attn_in,
    attn_out, key_mult, ssm_in, ssm_out, ssm_mults, mlp_mults, lowp,
):
    """Steps 2-5 for one layer: ``x [s, hidden] float32 -> [s, hidden]``.
    ``state_lost_at`` / ``conv_lost_at`` (traced positions; ``s`` or more:
    never) are the planted faults: from that position on the recurrence
    starts from a zero state, and the convolution sees zeros for the rows
    before it."""
    s, _ = x.shape
    pos = jnp.arange(s)
    h = _rms(x, w["RMSNorm_0"]["scale"], eps)

    # -- step 3: attention -------------------------------------------------
    group = heads // kv_heads
    qkv = _mm("sd,de->se", h * attn_in, w["qkv"]["kernel"], lowp)
    q = qkv[:, : heads * head_dim].reshape(s, heads, head_dim)
    k = qkv[:, heads * head_dim : (heads + kv_heads) * head_dim]
    k = (k * key_mult).reshape(s, kv_heads, head_dim)
    v = qkv[:, (heads + kv_heads) * head_dim :].reshape(s, kv_heads, head_dim)
    inv = theta ** (-jnp.arange(head_dim // 2, dtype=jnp.float32) * 2.0 / head_dim)
    angles = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
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
    attn = _mm("se,ed->sd", o, w["proj"]["kernel"], lowp)

    # -- step 4: the state-space mixer ------------------------------------
    d_ssm, gn = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
    p = _mm("sd,de->se", h * ssm_in, w["ssm_in"]["kernel"], lowp)
    edges = np.cumsum([d_ssm, d_ssm, gn, gn, ssm_heads])
    m = np.repeat(np.asarray(ssm_mults, np.float32), np.diff(np.concatenate([[0], edges])))
    p = p * m
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm : edges[3]], p[:, edges[3] :]
    if lowp:
        xbc = _fp8(xbc)
    kernel = w["ssm_conv_kernel"].astype(jnp.float32)  # [taps, channels]
    taps = kernel.shape[0]

    def conv(rows_in):
        padded = jnp.concatenate([jnp.zeros((taps - 1, rows_in.shape[1])), rows_in])
        return sum(kernel[j] * padded[j : j + s] for j in range(taps))

    lost = (pos >= conv_lost_at)[:, None]
    conved = jnp.where(lost, conv(jnp.where(lost, xbc, 0.0)), conv(xbc))
    xbc = jax.nn.silu(conved + w["ssm_conv_bias"].astype(jnp.float32))
    if lowp:
        xbc = _fp8(xbc)
    xs = xbc[:, :d_ssm].reshape(s, ssm_heads, ssm_head_dim)
    Bs = xbc[:, d_ssm : d_ssm + gn].reshape(s, ssm_groups, ssm_state)
    Cs = xbc[:, d_ssm + gn :].reshape(s, ssm_groups, ssm_state)
    per = ssm_heads // ssm_groups
    Bs, Cs = jnp.repeat(Bs, per, axis=1), jnp.repeat(Cs, per, axis=1)  # a head
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))  # [s, heads]
    A = -jnp.exp(w["A_log"].astype(jnp.float32))

    y, _ = recurrence(xs, Bs, Cs, dt, A, state_lost_at)
    y = y + w["D"].astype(jnp.float32)[None, :, None] * xs
    y = y.reshape(s, d_ssm) * jax.nn.silu(z)
    y = _rms(y, w["ssm_norm"]["scale"], eps, groups=ssm_groups)
    ssm = _mm("se,ed->sd", y, w["ssm_out"]["kernel"], lowp)

    x = x + attn_out * attn + ssm_out * ssm

    # -- step 5: the gated MLP ----------------------------------------------
    h2 = _rms(x, w["RMSNorm_1"]["scale"], eps)
    gate = _mm("sd,df->sf", h2, w["gate"]["kernel"], lowp) * mlp_mults[0]
    up = _mm("sd,df->sf", h2, w["up"]["kernel"], lowp)
    x = x + _mm("sf,fd->sd", up * jax.nn.silu(gate), w["down"]["kernel"], lowp) * mlp_mults[1]
    return _fp8(x) if lowp else x


def _vocab_block(vocab: int) -> int:
    """The largest divisor of ``vocab`` that is at most ``VOCAB_BLOCK``."""
    return max(c for c in range(1, min(vocab, VOCAB_BLOCK) + 1) if vocab % c == 0)


@partial(jax.jit, static_argnames=("eps", "multiplier", "lowp"))
def read_head(x, gain, head, nxt, *, eps, multiplier, lowp):
    """Step 6 over blocks of vocabulary columns: ``(best [s], got [s],
    choice [s])``: the best logit, the logit of the token that came next,
    and the token this pass puts first. A block of the head is cast to
    float32 at a time."""
    s = x.shape[0]
    vocab = head.shape[1]
    cols = _vocab_block(vocab)
    x = _rms(x, gain, eps)
    amax = jnp.max(jnp.abs(head)).astype(jnp.float32)
    if lowp:
        x = _fp8(x)

    def block(carry, i):
        best, got, choice = carry
        w = jax.lax.dynamic_slice_in_dim(head, i * cols, cols, axis=1).astype(jnp.float32)
        if lowp:
            w = _fp8(w, amax)
        logits = jnp.einsum("sd,dv->sv", x, w, precision=HIGHEST) * multiplier
        local = nxt - i * cols
        mine = (local >= 0) & (local < cols)
        here = jnp.take_along_axis(logits, jnp.clip(local, 0, cols - 1)[:, None], axis=-1)[:, 0]
        got = jnp.where(mine, here, got)
        top = jnp.max(logits, axis=-1)
        better = top > best
        choice = jnp.where(better, i * cols + jnp.argmax(logits, axis=-1), choice)
        return (jnp.where(better, top, best), got, choice), None

    start = (jnp.full((s,), -jnp.inf), jnp.zeros((s,)), jnp.zeros((s,), jnp.int32))
    (best, got, choice), _ = jax.lax.scan(block, start, jnp.arange(vocab // cols))
    return best, got, choice


def hidden_states(params: Dict, model: Dict, tokens, lowp: bool = False,
                  state_lost_at=None, conv_lost_at=None):
    """Steps 1-5 through every layer: ``tokens [s] -> x [s, hidden]``. The
    two positions are the planted faults of :func:`layer_forward` (None:
    never)."""
    s = tokens.shape[0]
    never = jnp.int32(s)
    state_lost_at = never if state_lost_at is None else jnp.int32(state_lost_at)
    conv_lost_at = never if conv_lost_at is None else jnp.int32(conv_lost_at)
    static = sizes(model)
    x = params["embed"][tokens].astype(jnp.float32) * float(model["embedding_multiplier"])
    if lowp:
        x = _fp8(x)
    for i in range(int(model["num_hidden_layers"])):
        x = layer_forward(
            x, params[f"block{i}"], state_lost_at, conv_lost_at, lowp=bool(lowp), **static
        )
    return x


def forward(params: Dict, model: Dict, tokens, lowp: bool = False):
    """All six steps: ``tokens [s] -> logits [s, vocab]`` (for the CPU
    tests; the benchmark reads the head in blocks, ``read_head``)."""
    x = hidden_states(params, model, tokens, lowp)
    x = _rms(x, params["RMSNorm_0"]["scale"], float(model["rms_norm_eps"]))
    return _mm("sd,dv->sv", x, params["head"], lowp) * float(model["lm_head_multiplier"])


#: The control and the planted faults ``--with-control`` judges in the
#: program's place: keyword arguments of :func:`hidden_states`, a value
#: ``"prompt_end"`` standing for the prompt's length.
CONTROLS = {
    "all_fp8": {"lowp": True},
    "state_lost": {"state_lost_at": "prompt_end"},
    "conv_lost": {"conv_lost_at": "prompt_end"},
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
    best at its position (0 where it is the reference's own choice);
    ``widest_gap``, the number the run compares with its limit, is the
    largest (a dense model: no routing near-ties).

    With ``lowp_control`` the same number for the token each of
    ``CONTROLS`` puts first at those positions: everything the
    configuration keeps in bfloat16 rounded to float8, every layer's state
    zeroed at the prompt's end, and the convolution's rows zeroed there.
    ``control_widest_gap``, the one the run judges, is the smallest of the
    three: the limit has to catch each."""
    head_args = dict(
        eps=float(model["rms_norm_eps"]),
        multiplier=float(model["lm_head_multiplier"]),
    )
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
            np.asarray(a) for a in read_head(x, gain, head, nxt, lowp=False, **head_args)
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
            _, _, choice = read_head(x_low, gain, head, nxt, lowp=lowp, **head_args)
            del x_low
            _, got_low, _ = read_head(x, gain, head, choice, lowp=False, **head_args)
            found.append((best - np.asarray(got_low))[span])

    if not gaps:
        return {"widest_gap": 0.0, "tokens_compared": 0}
    every = np.concatenate(gaps)
    out = {
        "widest_gap": float(every.max()),
        "mean_gap": float(every.mean()),
        "tokens_compared": int(every.shape[0]),
        "tokens_not_reference_choice": int((every > 0).sum()),
    }
    for name, found in control_gaps.items():
        out[f"control_{name}_widest_gap"] = float(np.concatenate(found).max())
        out[f"control_{name}_not_reference_choice"] = int(
            (np.concatenate(found) > 0).sum()
        )
    if control_gaps:
        out["control_widest_gap"] = min(
            out[f"control_{name}_widest_gap"] for name in control_gaps
        )
    return out
