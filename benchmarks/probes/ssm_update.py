"""A probe of the machine, not part of a run: the state-space mixer's two
paths alone at the ``falcon_h1_34b_4l`` cell's own shapes (32 heads of
128 with a state of 256 in 2 groups; 128 slots a decode step, prompts of
128 and 1,024 tokens in chunks of 128).

    python3 benchmarks/probes/ssm_update.py

1. **The one-token update** (``ops/ssm.py:ssm_decode_update``): 128 slots'
   float32 state ``[128, 32, 128, 256]`` (537 MB) read and written once,
   plain ``jax.numpy`` that XLA fuses into one pass. Milliseconds a call
   beside the least time the chip could take: twice the state's bytes
   over HBM bandwidth. A call's time is 24 calls of the one jitted update
   (the state donated and handed on) dispatched back to back and waited
   for once, over 24, the median of 5 such trains after a warm-up: the
   host dispatches the next call (0.9 ms; PR 31) while the device runs
   this one. (Chained inside one program XLA would fuse the updates into
   fewer passes over the state.)
2. **The chunked scan** (``ssm_chunk_scan``, a Pallas kernel): one prompt
   of 128 and of 1,024 tokens from a zero state, bfloat16 operands; a
   call's time is a program of 12 chained calls less one of 4, over 8
   (median of 20 after a warm-up, each program ended by
   ``block_until_ready``): a lone call's wall time on this host is mostly
   the dispatch. The least time is the larger of the chunked form's operations over the
   bf16 peak and its bytes over HBM (``shapes/falcon_h1.py``, one layer).
3. **Is it right.** Both against the plain reference's scan over tokens
   (``reference/falcon_h1.py:recurrence``, float32, highest precision):
   the scan's outputs and the state it hands on; then 4 one-token updates
   from that state against the scan continued. Exit code 1 if the error's
   rms passes 1e-2 of the outputs' own rms (bfloat16 operands: 2**-9 a
   rounding, a few of them; a wrong chunk boundary or group reads 1). The
   largest single error is printed beside it: outputs of 5 rms carry a
   rounding of 0.02 rms each.

Needs a TPU.
"""

import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

SLOTS, HEADS, P, N, GROUPS, CHUNK = 128, 32, 128, 256, 2, 128
PEAK_OPS, PEAK_BYTES = 197e12, 819e9


def _inputs(batch, s, seed, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, s, HEADS, P)).astype(dtype)
    B = (0.3 * jax.random.normal(ks[1], (batch, s, GROUPS, N))).astype(dtype)
    C = (0.3 * jax.random.normal(ks[2], (batch, s, GROUPS, N))).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (batch, s, HEADS)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[4], (HEADS,)))
    return x, dt, A, B, C


def _median_ms(fn, state, calls=20):
    """Median wall milliseconds of ``fn(state) -> state`` (the state
    donated and handed on), after a warm-up call."""
    samples = []
    for _ in range(calls + 1):
        t0 = time.perf_counter()
        state = fn(state)
        for leaf in state:
            leaf.block_until_ready()
        samples.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(samples[1:])


def _per_call_ms(step, state):
    """A chained call's time: a program of 12 calls less one of 4, over
    8. ``step(state) -> state``, each call reading the one before."""
    import jax

    def chained(n):
        def run(state):
            for _ in range(n):
                state = step(state)
            return state

        return jax.jit(run, donate_argnums=0)

    long, short = chained(12), chained(4)
    return (_median_ms(long, state()) - _median_ms(short, state())) / 8


def update_timings() -> None:
    import jax
    import jax.numpy as jnp

    from zookeeper_tpu.ops import ssm

    x, dt, A, B, C = _inputs(SLOTS, 1, 0, jnp.bfloat16)
    x, dt, B, C = x[:, 0], dt[:, 0], B[:, 0], C[:, 0]
    least = 1e3 * 2 * SLOTS * HEADS * P * N * 4 / PEAK_BYTES
    update = jax.jit(
        lambda S: ssm.ssm_decode_update(S, x, dt, A, B, C)[1],
        donate_argnums=0,
    )
    state, calls, trains = jnp.zeros((SLOTS, HEADS, P, N), jnp.float32), 24, []
    for _ in range(6):
        t0 = time.perf_counter()
        for _ in range(calls):
            state = update(state)
        state.block_until_ready()
        trains.append(1e3 * (time.perf_counter() - t0) / calls)
    took = statistics.median(trains[1:])
    print(
        f"ssm probe: one-token update, {SLOTS} slots: least {least:.3f} ms; "
        f"XLA's fusion {took:.3f} ms a call ({100 * least / took:.1f}% of "
        f"the roofline)",
        flush=True,
    )


def scan_timings() -> None:
    import jax.numpy as jnp

    from zookeeper_tpu.ops import ssm
    from zkbench import cells

    cell = cells.Cell("falcon_h1.chat_decode_closed")
    shapes = cell.shapes_module("falcon_h1")
    one_layer = dict(cell.config["model"], num_hidden_layers=1)
    peaks = {"bf16_flops_per_s": PEAK_OPS, "hbm_bytes_per_s": PEAK_BYTES}
    for s in (128, 1024):
        x, dt, A, B, C = _inputs(1, s, s, jnp.bfloat16)

        def step(state):
            (y,) = state
            y, _ = ssm.ssm_chunk_scan(
                (x + 1e-3 * y).astype(x.dtype), dt, A, B, C, chunk=CHUNK,
                interpret=False,
            )
            return (y,)

        ms = _per_call_ms(step, lambda: (jnp.zeros(x.shape, jnp.float32),))
        least = 1e3 * shapes.least_ssm_scan_seconds(one_layer, s, peaks)
        print(
            f"ssm probe: chunked scan, {s} tokens: least {least:.4f} ms; "
            f"Pallas kernel {ms:.3f} ms ({100 * least / ms:.1f}% of the roofline)",
            flush=True,
        )


def check() -> float:
    """The error's rms over the outputs' rms, the larger of the outputs'
    and the states' (point 3)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from zookeeper_tpu.ops import ssm
    from zkbench import cells

    reference = cells.Cell("falcon_h1.chat_decode_closed").reference_module()
    per = HEADS // GROUPS
    batch, s, extra = 2, 200, 4
    x, dt, A, B, C = _inputs(batch, s + extra, 7, jnp.bfloat16)
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = [
            reference.recurrence(
                f32(x[i]), jnp.repeat(f32(B[i]), per, axis=1),
                jnp.repeat(f32(C[i]), per, axis=1), dt[i], A,
            )
            for i in range(batch)
        ]
        prefix = [
            reference.recurrence(
                f32(x[i, :s]), jnp.repeat(f32(B[i, :s]), per, axis=1),
                jnp.repeat(f32(C[i, :s]), per, axis=1), dt[i, :s], A,
            )[1]
            for i in range(batch)
        ]
    want_y = np.stack([np.asarray(y) for y, _ in want])
    want_last = np.stack([np.asarray(last) for _, last in want])
    rms = lambda a: float(np.sqrt((np.asarray(a, np.float64) ** 2).mean()))
    scale, state_scale = rms(want_y), rms(np.stack(prefix))
    y, state = ssm.ssm_chunk_scan(
        x[:, :s], dt[:, :s], A, B[:, :s], C[:, :s], chunk=CHUNK, interpret=False
    )
    err = np.asarray(y) - want_y[:, :s]
    state_err = np.asarray(state) - np.stack(prefix)
    worst = max(rms(err) / scale, rms(state_err) / state_scale)
    print(
        f"ssm probe: chunked scan of {s} tokens against the scan over tokens: "
        f"output error rms {rms(err) / scale:.5f} of the outputs' rms {scale:.3f} "
        f"(largest {np.abs(err).max() / scale:.4f}), state error rms "
        f"{rms(state_err) / state_scale:.5f} of the state's rms {state_scale:.3f} "
        f"(largest {np.abs(state_err).max() / state_scale:.4f})",
        flush=True,
    )
    for t in range(s, s + extra):
        y_t, state = ssm.ssm_decode_update(
            state, x[:, t], dt[:, t], A, B[:, t], C[:, t]
        )
        worst = max(worst, rms(np.asarray(y_t) - want_y[:, t]) / scale)
    last_err = rms(np.asarray(state) - want_last) / state_scale
    print(
        f"ssm probe: {extra} one-token updates from that state: largest error "
        f"rms so far {worst:.5f}, the last state's {last_err:.5f}",
        flush=True,
    )
    return max(worst, last_err)


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print("ssm probe: needs a TPU", file=sys.stderr)
        return 3
    update_timings()
    scan_timings()
    worst = check()
    print(f"ssm probe: {'ok' if worst <= 1e-2 else 'WRONG'}", flush=True)
    return 0 if worst <= 1e-2 else 1


if __name__ == "__main__":
    sys.exit(main())
