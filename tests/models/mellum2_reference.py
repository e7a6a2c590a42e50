"""A copy, for the package's CPU tests, of the plain reference the
benchmark keeps in ``benchmarks/reference/mellum2.py`` (the forward pass
alone; the benchmark's file says what each step is and where it comes
from): float32 ``jax.numpy`` at the highest matmul precision, no cache, no
kernels, no program code. The package's tests may not import from
``benchmarks/``, hence the copy; keep the two alike.
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
