"""On-device latency measurement utilities.

Per-dispatch Python-loop timing of a small kernel measures the
dispatch jitter, not the kernel, so chains of data-dependent applies
run INSIDE one compiled ``lax.scan`` — one dispatch per chain — and the
marginal time over two chain lengths cancels the fixed dispatch + sync
overhead. ``block_until_ready`` is the completion barrier.
"""

import time
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def scan_chain_latency(
    apply_fn: Callable[[Any], Any],
    x: Any,
    *,
    length: int = 50,
    rounds: int = 4,
    escalate: bool = True,
) -> float:
    """Marginal seconds per ``apply_fn(x)`` call.

    ``apply_fn`` must be a pure function of its input returning an array
    (e.g. ``lambda x: module.apply(variables, x, training=False)``). The
    chain feeds a data-dependent scalar of each output back into the
    next input, so XLA can neither hoist the apply out of the loop nor
    dead-code-eliminate it; timing is min-over-``rounds`` per chain
    length (min over additive non-negative noise is sound), marginal
    over lengths ``length`` and ``2 * length``.

    ``escalate``: a non-positive marginal means host jitter exceeded
    the whole chain's work (jitter varies by session) — retry once at
    4x the chain length and 2x the rounds,
    where real work dwarfs the noise, before clamping.
    """

    def chain(k: int):
        @jax.jit
        def run(xx):
            def body(carry, _):
                y = apply_fn(carry)
                s = (jnp.sum(y) * 1e-12).astype(xx.dtype)
                return xx + s, jnp.ravel(y)[0]

            _, ys = jax.lax.scan(body, xx, None, length=k)
            return ys[-1]

        return run

    run_n, run_2n = chain(length), chain(2 * length)
    # Compile + warm both lengths before timing.
    jax.block_until_ready(run_n(x))
    jax.block_until_ready(run_2n(x))
    best_n = best_2n = np.inf
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(run_n(x))
        best_n = min(best_n, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(run_2n(x))
        best_2n = min(best_2n, time.perf_counter() - t0)
    marginal = (best_2n - best_n) / length
    if marginal <= 0 and escalate:
        return scan_chain_latency(
            apply_fn, x, length=4 * length, rounds=2 * rounds,
            escalate=False,
        )
    # Floor, not a negative time: if even the escalated chains can't
    # resolve the apply above the noise, ~0 says "unmeasurably fast at
    # these lengths — raise `length`".
    return max(marginal, 1e-9)


def time_marginal(run_chain, n1: int, n2: int, rounds: int) -> float:
    """Per-step marginal time via two-chain-length differencing — the
    one timing protocol the whole bench uses (BASELINE.md methodology;
    lives here so bench.py and the library share ONE copy).

    ``run_chain(n)`` runs ``n`` chained steps ended by a host readback
    and returns wall seconds. Each chain length takes its min over
    ``rounds`` INDEPENDENTLY (min over additive non-negative noise is
    sound), then the marginal is taken once — min over per-round
    *differences* would be biased fast whenever a jitter spike landed
    on a short chain. May return <= 0 under pathological jitter;
    callers decide how to handle.
    """
    t1_min = t2_min = None
    for _ in range(rounds):
        t1 = run_chain(n1)
        t2 = run_chain(n2)
        t1_min = t1 if t1_min is None else min(t1_min, t1)
        t2_min = t2 if t2_min is None else min(t2_min, t2)
    return (t2_min - t1_min) / (n2 - n1)


def measure_fused_loop_time(
    multi_step: Callable[[Any, Any], Tuple[Any, Any]],
    state: Any,
    slab: Any,
    *,
    rounds: int = 4,
    n1: int = 8,
    n2: int = 24,
) -> Tuple[float, Any]:
    """Steady-state wall seconds PER STEP of the fused multi-step loop
    — the END-TO-END number (Python dispatch + host bookkeeping +
    compute), where the bench's ``step_time_ms`` is the HBM-resident
    compute-only anchor. The gap between them is exactly the per-step
    overhead the multi-step engine amortizes.

    ``multi_step`` is a compiled ``(state, slab) -> (state,
    stacked_metrics)`` (``build_multi_step`` through
    ``Partitioner.compile_multi_step(..., donate_slab=False)`` — the
    slab is re-driven every call, so it must NOT be donated; the state
    should be). Chains of ``n`` back-to-back slab dispatches end in one
    ``block_until_ready``, timed with the repo's standard protocol:
    min-over-``rounds`` per chain length independently, marginal over
    the two lengths so the fixed dispatch + sync overhead of the chain
    ENDS cancels while the per-slab dispatch cost — the thing being
    measured — stays in. May return a non-positive time under
    pathological jitter; callers decide whether to escalate chain
    lengths (pass larger ``n1``/``n2``) or discard.

    Returns ``(seconds_per_step, final_state)`` — the state is
    threaded through every timed step (donation consumed the input),
    so callers can keep using it.
    """
    unroll = int(
        next(iter(slab.values())).shape[0]
        if isinstance(slab, dict)
        else jax.tree.leaves(slab)[0].shape[0]
    )
    holder = {"state": state}

    def run_chain(n: int) -> float:
        st = holder["state"]
        t0 = time.perf_counter()
        for _ in range(n):
            st, metrics = multi_step(st, slab)
        holder["state"] = st
        jax.block_until_ready(metrics["loss"])
        return time.perf_counter() - t0

    run_chain(1)  # Warm the compile before timing.
    per_slab = time_marginal(run_chain, n1, n2, rounds)
    return per_slab / unroll, holder["state"]


def measure_serving_latency(
    engine: Any,
    x: Any,
    *,
    n1: int = 8,
    n2: int = 24,
    rounds: int = 6,
    percentile_samples: int = 24,
    chain_len: int = 4,
) -> Tuple[float, float, float]:
    """Steady-state latency of the SERVING path — one
    ``InferenceEngine.infer`` dispatch (engine Python + host input
    staging + padded compiled forward), measured with the repo's shared
    protocols:

    - the MEAN per-dispatch time comes from :func:`time_marginal` over
      chains of back-to-back dispatches (the fixed chain-end sync
      cancels; the per-dispatch cost stays in) — this anchors
      ``serve_qps_per_chip``;
    - the p50/p99 come from ``percentile_samples`` independent SHORT
      chains of ``chain_len`` dispatches each (per-dispatch =
      chain/len): chaining amortizes the fixed readback the same way
      while preserving dispatch-to-dispatch spread, which a single
      marginal would average away.

    The engine must be warmed (``warmup()``) — a compile inside the
    timed window would dominate everything. Returns
    ``(mean_s, p50_s, p99_s)`` per dispatch; the mean may be
    non-positive under pathological jitter (callers decide, like every
    ``time_marginal`` consumer).
    """
    def run_chain(k: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = engine.infer(x)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    run_chain(2)  # warm the dispatch path (not the compile — warmup())
    mean_s = time_marginal(run_chain, n1, n2, rounds)
    samples = np.asarray(
        sorted(run_chain(chain_len) / chain_len
               for _ in range(percentile_samples))
    )
    return (
        mean_s,
        float(np.percentile(samples, 50)),
        float(np.percentile(samples, 99)),
    )


def measure_inference_latency(
    module: Any,
    variables: Any,
    input_shape: Tuple[int, ...],
    *,
    batch_size: int = 1,
    dtype: Any = jnp.float32,
    length: int = 50,
    rounds: int = 4,
    seed: int = 0,
) -> float:
    """Seconds per forward pass of ``module.apply`` at ``batch_size``."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch_size, *input_shape)), dtype)
    return scan_chain_latency(
        lambda xx: module.apply(variables, xx, training=False),
        x,
        length=length,
        rounds=rounds,
    )
