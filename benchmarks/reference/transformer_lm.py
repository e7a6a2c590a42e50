"""Plain reference of the served decoder-only transformer: one full forward
pass over a prompt with its served tokens, float32 at the highest matmul
precision, no cache, no kernels, no batching, no program code.

The architecture is GPT-2's (Radford et al. 2019; ``openai-community/
gpt2-xl`` ``config.json``) as the repo's ``TransformerLM`` builds it:
learned token and position tables, pre-norm blocks of causal multi-head
attention (heads of ``d_model / num_heads``, scores scaled by
``head_dim ** -0.5``) and a 4x GELU (tanh approximation) MLP, a final norm
and a head tied to the token table. Departures of the repo's block from
GPT-2, taken over since the program is what is measured: RMSNorm (eps 1e-6,
a gain, no bias) in the place of LayerNorm, and no biases on any
projection.

``lowp`` is the control (see ``PERF.md``): what the configuration keeps in
bfloat16 is rounded to float8 e4m3 (per-tensor scaled) instead, the
precision step below: both operands of every matrix product (projections,
scores, values, head) and the residual stream between blocks.

Weights arrive as the nested dict the benchmark made
(``zkbench/weights.py``), under flax's names for the repo's module:
``embed``, ``pos``, ``block{i}/{RMSNorm_0,qkv,proj,RMSNorm_1,up,down}``,
``RMSNorm_0``.
"""

from typing import Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, lowp):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * gain


def stack_layers(params: Dict, num_layers: int) -> Dict:
    """The per-layer leaves stacked along a new leading axis, so that the
    layers run as one ``lax.scan`` (one compiled body, not ``num_layers``)."""
    def stacked(*path):
        leaves = []
        for i in range(num_layers):
            node = params[f"block{i}"]
            for key in path:
                node = node[key]
            leaves.append(node)
        return jnp.stack(leaves)

    return {
        "ln1": stacked("RMSNorm_0", "scale"),
        "qkv": stacked("qkv", "kernel"),
        "proj": stacked("proj", "kernel"),
        "ln2": stacked("RMSNorm_1", "scale"),
        "up": stacked("up", "kernel"),
        "down": stacked("down", "kernel"),
    }


def make_forward(num_heads: int, lowp: bool = False):
    """``forward(embed, pos, final_gain, layers, tokens [s]) -> logits
    [s, vocab]`` (jitted)."""

    @jax.jit
    def forward(embed, pos, final_gain, layers, tokens):
        s = tokens.shape[0]
        d = embed.shape[1]
        hd = d // num_heads
        x = embed[tokens] + pos[:s]
        mask = jnp.tril(jnp.ones((s, s), bool))

        def block(x, w):
            h = _rms(x, w["ln1"])
            qkv = _mm("sd,de->se", h, w["qkv"], lowp)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q, k, v = (t.reshape(s, num_heads, hd) for t in (q, k, v))
            scores = _mm("qhd,khd->hqk", q, k, lowp) * (hd ** -0.5)
            scores = jnp.where(mask[None], scores, -jnp.inf)
            p = jax.nn.softmax(scores, axis=-1)
            o = _mm("hqk,khd->qhd", p, v, lowp).reshape(s, d)
            x = x + _mm("sd,de->se", o, w["proj"], lowp)
            h = _rms(x, w["ln2"])
            h = jax.nn.gelu(_mm("sd,de->se", h, w["up"], lowp), approximate=True)
            x = x + _mm("se,ed->sd", h, w["down"], lowp)
            return (_fp8(x) if lowp else x), None

        x, _ = jax.lax.scan(block, x, layers)
        x = _rms(x, final_gain)
        return _mm("sd,vd->sv", x, embed, lowp)

    return forward


def make_reader(num_heads: int, lowp: bool = False):
    """``read(embed, pos, final_gain, layers, tokens [s], nxt [s]) ->
    (best [s], got [s], choice [s])``: at each position the reference's
    best logit, the logit of the token that came next, and the token this
    pass puts first. One jitted program of one shape whatever the lengths
    (slicing by a request's own lengths on the device would compile a
    handful of small programs anew for every request of every seed)."""
    forward = make_forward(num_heads, lowp)

    @jax.jit
    def read(embed, pos, final_gain, layers, tokens, nxt):
        logits = forward(embed, pos, final_gain, layers, tokens)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        return best, got, jnp.argmax(logits, axis=-1)

    return read


def served_token_gaps(
    params: Dict,
    model: Dict,
    sequences: List[Dict],
    pad_to: int,
    lowp_control: bool = False,
) -> Dict[str, float]:
    """For each sequence (``prompt`` and ``served`` token arrays), one
    reference pass over the prompt followed by its served tokens. Returns
    the widest gap by which a served token's reference logit lies below the
    reference's best at its position (0 where the served token is the
    reference's own choice), and how many tokens were compared. With
    ``lowp_control`` also the same number for the token the float8 pass
    puts first at each of those positions."""
    import numpy as np

    layers = stack_layers(params, int(model["n_layer"]))
    weights = (params["embed"], params["pos"], params["RMSNorm_0"]["scale"], layers)
    read = make_reader(int(model["n_head"]))
    control = make_reader(int(model["n_head"]), lowp=True) if lowp_control else None
    widest = widest_control = 0.0
    tokens_compared = flips = 0
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
        best, got, _ = (np.asarray(x) for x in read(*weights, tokens, nxt))
        gap = (best - got)[span]
        widest = max(widest, float(gap.max()))
        flips += int((gap > 0).sum())
        tokens_compared += int(gap.shape[0])
        if control is not None:
            # the reference's own logit of the token float8 puts first
            _, _, low_choice = control(*weights, tokens, nxt)
            _, got_low, _ = read(*weights, tokens, low_choice)
            low_gap = (best - np.asarray(got_low))[span]
            widest_control = max(widest_control, float(low_gap.max()))
    out = {
        "widest_gap": widest,
        "tokens_compared": tokens_compared,
        "tokens_not_reference_choice": flips,
    }
    if control is not None:
        out["control_widest_gap"] = widest_control
    return out
