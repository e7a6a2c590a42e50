"""Jittable train / eval step builders.

This is the boundary the rebuild moves (SURVEY.md §3.3): the reference's
hot loop lives inside Keras ``fit``; here it is an explicit pure function
``(state, batch) -> (state, metrics)`` that ``jax.jit`` (single device) or
``pjit`` over a mesh (via the Partitioner) compiles end-to-end, with the
input state donated so parameter updates happen in place in HBM.
"""

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from zookeeper_tpu.training.state import TrainState

Batch = Dict[str, jax.Array]
Metrics = Dict[str, jax.Array]


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy with integer labels (float32 for the
    reduction regardless of compute dtype)."""
    logits = logits.astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels
    ).mean()


def smoothed_softmax_cross_entropy(smoothing: float):
    """Label-smoothed cross-entropy loss factory (the standard ImageNet
    recipe regularizer): targets become ``(1 - smoothing)`` on the true
    class and ``smoothing / num_classes`` elsewhere. ``smoothing=0``
    returns the plain integer-label loss (identical compiled graph)."""
    if not 0.0 <= smoothing < 1.0:
        raise ValueError(
            f"label smoothing {smoothing} outside [0, 1): 0 disables; "
            "1.0 would erase the labels entirely."
        )
    if smoothing == 0.0:
        return softmax_cross_entropy

    def loss_fn(logits: jax.Array, labels: jax.Array) -> jax.Array:
        logits = logits.astype(jnp.float32)
        num_classes = logits.shape[-1]
        targets = optax.smooth_labels(
            jax.nn.one_hot(labels, num_classes), smoothing
        )
        return optax.softmax_cross_entropy(logits, targets).mean()

    return loss_fn


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return (jnp.argmax(logits, axis=-1) == labels).mean()


def top_k_accuracy(logits: jax.Array, labels: jax.Array, k: int) -> jax.Array:
    """Fraction of examples whose true label is in the top-k logits (the
    ImageNet top-5 companion metric). Rank-general like the other
    metrics: ``[..., num_classes]`` logits against ``[...]`` integer
    labels, so per-position LM scoring works too (``labels[:, None]``
    broke rank-3 broadcasting)."""
    _, top = jax.lax.top_k(logits.astype(jnp.float32), k)
    return (top == labels[..., None]).any(axis=-1).mean()


def kd_divergence(
    student_logits: jax.Array, teacher_logits: jax.Array, temperature: float
) -> jax.Array:
    """Hinton knowledge-distillation loss: T^2-scaled KL(teacher || student)
    over temperature-softened distributions (fp32 reduction)."""
    sl = student_logits.astype(jnp.float32) / temperature
    tl = teacher_logits.astype(jnp.float32) / temperature
    p_t = jax.nn.softmax(tl)
    return (temperature**2) * jnp.mean(
        jnp.sum(p_t * (jax.nn.log_softmax(tl) - jax.nn.log_softmax(sl)), -1)
    )


def make_train_step(
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array] = softmax_cross_entropy,
    *,
    rng_seed: int = 0,
    has_aux_state: bool = True,
    flip_ratio_pattern: str = None,
    distill: Tuple[Callable[[jax.Array], jax.Array], float, float] = None,
    ema_decay: float = None,
    remat: str = "none",
    nan_policy: str = "ignore",
) -> Callable[[TrainState, Batch], Tuple[TrainState, Metrics]]:
    """Build the pure train step. Works unjitted (debugging), under
    ``jax.jit``, or under ``pjit``/``shard_map`` — no collectives are
    hand-written here; with a sharded batch XLA inserts the gradient
    all-reduce automatically from the sharding annotations.

    ``flip_ratio_pattern``: when set (a regex over flat param paths, e.g.
    ``training.optimizer.BINARY_KERNEL_PATTERN``), the step also reports
    ``flip_ratio`` — the fraction of matched weights whose SIGN changed
    this step (larq ``FlipRatio`` capability). Binary nets only learn
    through sign flips, so a collapsed-to-zero or exploding flip ratio is
    the primary training-health signal. Computed fully on device from
    params already in HBM (two sign compares; no extra host syncs).

    ``distill``: optional ``(teacher_fn, alpha, temperature)`` —
    knowledge distillation (the Real-to-Binary recipe's essential
    ingredient). ``teacher_fn(batch_input) -> logits`` runs under
    stop_gradient; total loss becomes ``alpha * hard_loss +
    (1 - alpha) * kd_divergence``; metrics gain ``kd_loss``. The teacher
    runs INSIDE the jitted step, so under pjit its (closed-over) params
    replicate and its forward shards with the batch like the student's.

    ``remat``: rematerialization policy trading recompute FLOPs for HBM
    (the standard lever when activations, not params, bound the batch
    size — e.g. 224^2 activations on big batches):

    - ``"none"``: store all activations (default; fastest when it fits).
    - ``"dots"``: ``jax.checkpoint`` saving only non-batch matmul
      contractions (the transformer-style sweet spot; note XLA lowers
      convs separately, so for conv nets this saves little more than
      "full" — dense/attention-heavy models are where it shines).
    - ``"full"``: save nothing from the forward; backward replays it
      (max memory savings, ~1 extra forward of compute).
    - ``"quant"``: save ONLY the binarized activations the Quant* layers
      tag (``ops.layers.QUANT_ACT_CHECKPOINT_NAME``); BN/ReLU/shortcut
      intermediates recompute. NOTE (measured, BASELINE.md round 4): at
      the north-star QuickNet-Large shapes XLA's own scheduling already
      rematerializes conv nets so well that every policy's temp memory
      is within ~1% of "none" — and "quant" lands ~25% HIGHER (the
      pinned saves constrain fusion). Policies are exactness-preserving
      (pinned by test); measure before relying on one.

    ``nan_policy``: what a non-finite loss or gradient does to the step
    (the resilience posture — one bad step inside a fused ``lax.scan``
    slab would otherwise silently poison every subsequent step):

    - ``"ignore"``: today's behavior, zero extra ops (default).
    - ``"skip"``: when loss or global grad norm is non-finite, the
      params / optimizer state / model_state / EMA keep their PRE-STEP
      values via ``jnp.where`` selects — fully on device, no host sync,
      no ``lax.cond`` dispatch stall — while the STEP COUNTER still
      advances (the counter drives checkpoint naming and the
      ``(seed, epoch)`` pipeline replay; freezing it would break the
      exact-resume contract). Metrics gain a per-step ``skipped_steps``
      0/1 flag (the experiment sums it per epoch).
    - ``"halt"``: on-device identical to ``"skip"`` (the bad update is
      still suppressed so the checkpointed state stays clean), but the
      EXPERIMENT raises ``NonFiniteLossError`` at its next metrics
      readback boundary so a supervisor restores from checkpoint —
      detection latency is the deferred-readback cadence, by design.

    Chaos hook: when an active ``FaultPlan`` sets ``nan_at_step``, the
    loss is scaled by a ``step == N`` selected NaN at trace time —
    poisoning loss AND grads on-device exactly like a real numeric
    blow-up, deterministically.
    """
    flip_paths = None
    if flip_ratio_pattern is not None:
        import re

        flip_paths = re.compile(flip_ratio_pattern)
    if remat not in ("none", "dots", "full", "quant"):
        raise ValueError(
            f"Unknown remat policy {remat!r}; choose none/dots/full/quant."
        )
    if nan_policy not in ("ignore", "skip", "halt"):
        raise ValueError(
            f"Unknown nan_policy {nan_policy!r}; choose ignore/skip/halt."
        )
    # Deterministic chaos: the active FaultPlan's NaN step is read ONCE,
    # at build time, and traced into the compiled step (a plan installed
    # after compilation does not retroactively poison a cached program).
    from zookeeper_tpu.resilience import faults as _faults

    _plan = _faults.active()
    nan_at_step = _plan.nan_at_step if _plan is not None else None

    def train_step(state: TrainState, batch: Batch) -> Tuple[TrainState, Metrics]:
        # Per-step RNG derived from the step counter: deterministic,
        # resume-stable, and identical across data-parallel replicas.
        rng = jax.random.fold_in(jax.random.PRNGKey(rng_seed), state.step)

        # Static across the step: which collections (batch_stats) mutate.
        mutable = (
            tuple(state.model_state.keys())
            if has_aux_state and state.model_state
            else False
        )

        def apply_model(variables, x):
            return state.apply_fn(
                variables,
                x,
                training=True,
                mutable=mutable,
                rngs={"dropout": rng},
            )

        if remat == "dots":
            apply_model = jax.checkpoint(
                apply_model,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif remat == "full":
            apply_model = jax.checkpoint(apply_model)
        elif remat == "quant":
            from zookeeper_tpu.ops.layers import QUANT_ACT_CHECKPOINT_NAME

            apply_model = jax.checkpoint(
                apply_model,
                policy=jax.checkpoint_policies.save_only_these_names(
                    QUANT_ACT_CHECKPOINT_NAME
                ),
            )

        def compute_loss(params):
            variables = {"params": params, **state.model_state}
            out = apply_model(variables, batch["input"])
            if mutable:
                logits, new_model_state = out
            else:
                logits, new_model_state = out, state.model_state
            loss = loss_fn(logits, batch["target"])
            if nan_at_step is not None:
                # Multiplicative NaN: poisons the loss AND (through the
                # chain rule) every gradient — the real blow-up shape.
                loss = loss * jnp.where(
                    state.step == nan_at_step,
                    jnp.float32(jnp.nan),
                    jnp.float32(1.0),
                )
            kd = None
            if distill is not None:
                teacher_fn, alpha, temperature = distill
                t_logits = jax.lax.stop_gradient(teacher_fn(batch["input"]))
                kd = kd_divergence(logits, t_logits, temperature)
                loss = alpha * loss + (1.0 - alpha) * kd
            return loss, (logits, new_model_state, kd)

        (loss, (logits, new_model_state, kd)), grads = jax.value_and_grad(
            compute_loss, has_aux=True
        )(state.params)
        grad_norm = optax.global_norm(grads)
        new_state = state.apply_gradients(grads).replace(
            model_state=dict(new_model_state)
        )
        if ema_decay is not None:
            if state.ema_params is None:
                raise ValueError(
                    "ema_decay is set but the TrainState has no ema_params; "
                    "build it with TrainState.create(..., ema=True)."
                )
            new_state = new_state.replace(
                ema_params=jax.tree.map(
                    lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                    state.ema_params,
                    new_state.params,
                )
            )
        if nan_policy != "ignore":
            # Keep the PRE-step values for every stateful leaf when the
            # step blew up; the step counter still advances (see
            # docstring — it is the resume/replay clock, not model
            # state). Pure where-selects: no host sync, scan-safe.
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)

            def keep_old(new, old):
                return jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new, old
                )

            new_state = new_state.replace(
                params=keep_old(new_state.params, state.params),
                opt_state=keep_old(new_state.opt_state, state.opt_state),
                model_state=keep_old(
                    new_state.model_state, state.model_state
                ),
                ema_params=(
                    keep_old(new_state.ema_params, state.ema_params)
                    if new_state.ema_params is not None
                    else None
                ),
            )
        metrics = {
            "loss": loss,
            "accuracy": accuracy(logits, batch["target"]),
            "grad_norm": grad_norm,
        }
        if nan_policy != "ignore":
            metrics["skipped_steps"] = (~ok).astype(jnp.float32)
        if kd is not None:
            metrics["kd_loss"] = kd
        if flip_paths is not None:
            from flax import traverse_util

            old_flat = traverse_util.flatten_dict(state.params, sep="/")
            new_flat = traverse_util.flatten_dict(new_state.params, sep="/")
            flips = jnp.zeros((), jnp.float32)
            total = 0
            for path, old in old_flat.items():
                if flip_paths.search(path):
                    flips = flips + jnp.sum(
                        (jnp.sign(old) != jnp.sign(new_flat[path])).astype(
                            jnp.float32
                        )
                    )
                    total += old.size
            if total == 0:
                # Raises at TRACE time (paths are static): a pattern that
                # matches nothing would otherwise report a permanent 0.0 —
                # indistinguishable from collapsed binary training, the
                # exact failure the metric exists to catch.
                raise ValueError(
                    f"flip_ratio_pattern {flip_paths.pattern!r} matched no "
                    "parameter path. Is the model actually binarized "
                    "(Quant* layers), or is the pattern misspelled? "
                    f"Available paths: {sorted(old_flat)[:8]}..."
                )
            metrics["flip_ratio"] = flips / total
        return new_state, metrics

    return train_step


def build_multi_step(
    step_fn: Callable[[TrainState, Batch], Tuple[TrainState, Metrics]],
) -> Callable[[TrainState, Batch], Tuple[TrainState, Metrics]]:
    """Fuse a ``(state, batch) -> (state, metrics)`` step into a
    ``(state, slab) -> (state, stacked_metrics)`` multi-step via
    ``jax.lax.scan`` over the slab's leading axis.

    A *slab* is ``unroll`` consecutive batches stacked on the leading
    axis (``{"input": [unroll, batch, ...], "target": [unroll, batch]}``
    — see ``data.pipeline.slab_iterator``); the scan threads the train
    state through all ``unroll`` steps inside ONE compiled program, so
    the Python loop pays dispatch + host bookkeeping once per slab
    instead of once per step, and the per-step metrics come back as
    device-resident ``[unroll]``-stacked arrays the caller can read
    whenever it likes (deferred readback — the host never blocks
    between steps).

    The scan length is the slab's leading dim, resolved at trace time:
    one builder serves every slab size, and ``jax.jit`` caches one
    executable per distinct size (a full epoch needs at most two — the
    steady-state ``unroll`` and one partial final slab). Step counters,
    per-step RNG folding, EMA, and flip-ratio all ride unchanged:
    ``state.step`` advances inside the scan exactly as it does in the
    eager loop — same steps, same batches, same math.

    Exactness (measured, CPU): the dense stack is BIT-identical to the
    eager loop over full training (params, opt state, per-step metrics
    — pinned by tests/training/test_multi_step.py), and the forward is
    bit-identical for every model (step-0 loss/metrics agree exactly).
    Conv BACKWARDS are the one caveat: XLA orders the wgrad reductions
    differently inside a scan body than in a flat jit, so conv
    gradients can differ at the fp32 ULP level between the two
    programs — statistically neutral, but Adam's per-param scaling
    amplifies it over steps (measured ~4e-3 max param drift after 4
    SimpleCnn steps). The same class of drift already separates any
    two differently-compiled programs (remat policies, jax upgrades);
    it is a property of XLA reduction ordering, not of the loop.
    """

    def multi_step(
        state: TrainState, slab: Batch
    ) -> Tuple[TrainState, Metrics]:
        return jax.lax.scan(step_fn, state, slab)

    return multi_step


def host_snapshot(tree):
    """Donation-safe device→host snapshot of a pytree: every leaf comes
    back as an independent host ``np.ndarray``, so the snapshot stays
    valid after the originating device buffers are donated into the
    next step/slab dispatch (the async checkpointer's slab-boundary
    hook — ``training.async_checkpoint``).

    The device→host copies for ALL leaves are issued asynchronously
    first (``copy_to_host_async`` — a leaf that is already host-side
    has nothing to issue), then materialized:
    the transfers overlap each other and any still-running device work
    queued BEHIND the state's producing computation, so the training
    thread pays one drained-copy wait, not a serialized per-leaf walk.
    """
    import numpy as np

    leaves, treedef = jax.tree.flatten(tree)
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()
    # np.asarray on a jax Array materializes the (already in-flight)
    # host copy; 0-d leaves become 0-d ndarrays (orbax rejects bare
    # numpy scalars, so the asarray wrapper is load-bearing).
    return jax.tree.unflatten(
        treedef, [np.asarray(jax.device_get(leaf)) for leaf in leaves]
    )


def make_eval_step(
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array] = softmax_cross_entropy,
    *,
    use_ema: bool = False,
    top5: bool = False,
) -> Callable[[TrainState, Batch], Metrics]:
    """``use_ema``: evaluate the EMA weights instead of the raw params
    (the averaged weights are what ships — standard for the long binary
    recipes, where raw weights oscillate from late sign flips).
    ``top5``: also report top-5 accuracy (the ImageNet companion metric
    larq-zoo publishes alongside top-1)."""

    def eval_step(state: TrainState, batch: Batch) -> Metrics:
        params = state.params
        if use_ema:
            if state.ema_params is None:
                raise ValueError(
                    "use_ema=True but the TrainState has no ema_params; "
                    "build it with TrainState.create(..., ema=True)."
                )
            params = state.ema_params
        variables = {"params": params, **state.model_state}
        logits = state.apply_fn(variables, batch["input"], training=False)
        metrics = {
            "loss": loss_fn(logits, batch["target"]),
            "accuracy": accuracy(logits, batch["target"]),
        }
        if top5:
            metrics["top5_accuracy"] = top_k_accuracy(
                logits, batch["target"], k=5
            )
        return metrics

    return eval_step
