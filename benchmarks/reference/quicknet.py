"""Plain reference of QuickNet training: forward, loss, backward, Adam.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision;
no kernels, no mixed precision, no program code. It follows the published
description (Bannink et al. 2021, "Larq Compute Engine", arXiv:2011.09398,
section 4 and larq-zoo ``sota.QuickNet``) as the repo's model builds it:

- stem: 3x3/2 conv to 8 channels, BN, ReLU, grouped (4) 3x3/2 conv to the
  first section's width, BN;
- a section is ``n`` residual blocks ``x + BN(binconv3x3(sign(x)))`` where
  both the activations and the latent kernels go through ``sign`` with a
  straight-through gradient (activations: passed where ``|x| <= 1``;
  kernels: passed everywhere, the latent weights being clipped to
  ``[-1, 1]`` on reading);
- between sections: ReLU, 3x3/2 binomial blur-pool (depthwise), 1x1 conv
  to the next width, BN;
- head: ReLU, global average pool, dense to the classes.

Departures of the repo's model from larq-zoo (taken over, since the program
is what is measured): the stem and transitions are the repo's
reconstruction; SAME zero padding on the binary convolutions.

Training: label-smoothed softmax cross-entropy (mean over the batch),
batch-statistics BatchNorm (eps 1e-5), Adam (b1 0.9, b2 0.999, eps 1e-8,
bias-corrected) under a linear warm-up of one step from 0 followed by a
cosine decay to 0 over ``total_steps`` (so the first step's learning rate
is 0 and its update nil, as in the program's ``WarmupCosine`` defaults).

Parameter names are flax's automatic ones for the repo's module, in order
of creation: ``Conv_i``, ``BatchNorm_i``, ``QuantConv_i``, ``Dense_0``.

``lowp`` is the control (see ``PERF.md``): everything the configuration
keeps in bfloat16 is rounded to float8 (e4m3, per-tensor scaled) in the
forward pass instead, the precision step below: the operands of every
real-valued convolution and of the dense head, and every activation
between layers (convolution outputs, BatchNorm outputs, the residual
stream). The binary convolutions' +-1 operands stay exact: +-1 is exact in
every integer type.
"""

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _ste_act(x):
    """sign(x) forward; gradient passed where |x| <= 1."""
    passed = jnp.clip(x, -1.0, 1.0)
    return passed + jax.lax.stop_gradient(_sign(x) - passed)


def _ste_kernel(k):
    """sign(k) forward; gradient passed everywhere (clipped latent)."""
    return k + jax.lax.stop_gradient(_sign(k) - k)


def _fp8(x):
    """Round through float8 e4m3 with a per-tensor scale (forward only;
    the gradient passes straight through)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _conv(x, k, stride, groups=1, lowp=False):
    if lowp:
        x, k = _fp8(x), _fp8(k)
    return jax.lax.conv_general_dilated(
        x, k, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST,
    )


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _blur_pool(x):
    c = x.shape[-1]
    f = jnp.array([1.0, 2.0, 1.0], jnp.float32)
    k2d = jnp.outer(f, f)
    k2d = k2d / k2d.sum()
    kernel = jnp.tile(k2d[:, :, None, None], (1, 1, 1, c))
    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(2, 2), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c, precision=HIGHEST,
    )


def _act(x, lowp):
    """An activation as the configuration stores it between layers."""
    return _fp8(x) if lowp else x


def _block(x, kernel, bn, lowp=False):
    y = jax.lax.conv_general_dilated(
        _ste_act(x), _ste_kernel(kernel), window_strides=(1, 1),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST,
    )
    return _act(x + _act(_bn(_act(y, lowp), bn), lowp), lowp)


BN_MOMENTUM = 0.9


def logits_fn(params: Dict, x, arch: Dict, lowp: bool = False, stats=None):
    """``arch``: ``blocks_per_section``, ``section_features`` (the
    configuration file's ``model`` group). ``stats`` (a dict, optional) is
    filled with the batch mean and variance the two stem BatchNorms saw:
    the layers ahead of the first binarization."""
    conv = bn = qconv = 0

    def next_bn():
        nonlocal bn
        p = params[f"BatchNorm_{bn}"]
        bn += 1
        return p

    def next_conv():
        nonlocal conv
        k = params[f"Conv_{conv}"]["kernel"]
        conv += 1
        return k

    act = lambda t: _act(t, lowp)  # noqa: E731
    x = x.astype(jnp.float32)
    x = act(_conv(x, next_conv(), 2, lowp=lowp))
    _note_stats(stats, "BatchNorm_0", x)
    x = act(jax.nn.relu(_bn(x, next_bn())))
    x = act(_conv(x, next_conv(), 2, groups=4, lowp=lowp))
    _note_stats(stats, "BatchNorm_1", x)
    x = act(_bn(x, next_bn()))
    block = jax.checkpoint(_block, static_argnums=(3,))
    for s, n in enumerate(arch["blocks_per_section"]):
        if s > 0:
            x = act(_blur_pool(jax.nn.relu(x)))
            x = act(_conv(x, next_conv(), 1, lowp=lowp))
            x = act(_bn(x, next_bn()))
        for _ in range(n):
            x = block(x, params[f"QuantConv_{qconv}"]["kernel"], next_bn(), lowp)
            qconv += 1
    x = jnp.mean(jax.nn.relu(x), axis=(1, 2))
    dense = params["Dense_0"]
    w = dense["kernel"]
    if lowp:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision=HIGHEST) + dense["bias"]


def _note_stats(stats, name, x):
    """The running statistics a BatchNorm that starts from mean 0 and
    variance 1 holds after its first training step (momentum 0.9, the
    batch's biased variance)."""
    if stats is None:
        return
    x = jax.lax.stop_gradient(x)
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    stats[f"batch_stats/{name}/mean"] = (1 - BN_MOMENTUM) * mean
    stats[f"batch_stats/{name}/var"] = BN_MOMENTUM + (1 - BN_MOMENTUM) * var


def loss_fn(params, x, labels, arch, smoothing, lowp=False):
    """``(loss, stem statistics)``."""
    stats = {}
    logits = logits_fn(params, x, arch, lowp, stats)
    n = logits.shape[-1]
    targets = jax.nn.one_hot(labels, n) * (1.0 - smoothing) + smoothing / n
    loss = -jnp.mean(jnp.sum(targets * jax.nn.log_softmax(logits), axis=-1))
    return loss, stats


def learning_rate(step: int, opt: Dict) -> float:
    """The rate applied by the update that follows ``step`` earlier ones:
    linear warm-up from 0 over ``warmup_steps`` (at least 1), then cosine
    to ``alpha * base_lr`` at ``total_steps``."""
    base, total = float(opt["base_lr"]), int(opt["total_steps"])
    warm = max(1, int(opt.get("warmup_steps", 0)))
    if step < warm:
        return base * step / warm
    end = base * float(opt.get("alpha", 0.0))
    span = max(1, max(2, total) - warm)
    frac = min(1.0, (step - warm) / span)
    return end + (base - end) * 0.5 * (1.0 + math.cos(math.pi * frac))


def _leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        "/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in leaves
    }


def train(
    params: Dict,
    batches: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    arch: Dict,
    opt: Dict,
    smoothing: float,
    lowp: bool = False,
    rows: Optional[slice] = None,
) -> Dict[str, object]:
    """Follow ``len(batches)`` training steps from ``params``. Returns the
    loss of each step, the per-leaf norm of the first step's gradient and
    the per-leaf norm of the parameters' change after the last step, and the
    running statistics of the stem's BatchNorms after the first step.
    ``rows`` restricts every batch to a slice of its rows (the planted
    fault "half of the batch left out")."""
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])

    @jax.jit
    def step(params, mu, nu, x, y, lr, t):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, y, arch, smoothing, lowp
        )
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            params, mu, nu,
        )
        return new, mu, nu, loss, _leaf_norms(grads), stats

    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    first_grad = first_stats = None
    for i, (x, y) in enumerate(batches):
        if rows is not None:
            x, y = x[rows], y[rows]
        params, mu, nu, loss, gnorm, stats = step(
            params, mu, nu, x, y,
            jnp.float32(learning_rate(i, opt)), jnp.float32(i + 1),
        )
        losses.append(float(loss))
        if i == 0:
            first_grad = {k: float(v) for k, v in gnorm.items()}
            first_stats = {k: jax.device_get(v) for k, v in stats.items()}
    change = _leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))
    return {
        "losses": losses,
        "grad_norm": first_grad,
        "change_norm": {k: float(v) for k, v in change.items()},
        "stats": first_stats,
    }
