"""A probe of the machine, not part of a run: the expert layer at the
``mellum2_8l`` cell's own widths (64 experts of 2304 -> 896 -> 2304, the 8
largest a token), at the two shapes the cell runs it at (a decode step's 64
tokens, a prefill bucket's 2,048).

    python3 benchmarks/probes/moe_grouped_matmul.py

1. **Which grouped matmul.** Milliseconds a call (median of 20 after a
   warm-up, each ended by ``block_until_ready``) for the three matmuls of
   one layer on rows already sorted by expert, three ways: ``jax.lax.
   ragged_dot`` and the Pallas grouped kernel that ships with jax
   (``megablox.gmm``, a row tile against an expert's whole matrix), both on
   experts stacked ``[experts, k, n]``; and the program's own
   ``zookeeper_tpu.ops.moe.grouped_matmul`` on the experts side by side
   ``[k, experts * n]``, as the program holds them. Beside them the least
   time the chip could take: the larger of the operations over the bf16
   peak and the expert bytes over HBM bandwidth.
2. **Is it right.** ``sparse_moe`` against the plain sum over experts in
   float32 at the highest precision, with the routing computed here in
   numpy (float64), not by the program: a dropped row, a wrong expert's
   block or a wrong unsort shows as an error of the output's own size.
   Tokens whose 8th and 9th router weights lie within 1e-4 of each other
   are left out (float32 and float64 may order them differently). Exit
   code 1 if the largest error passes 4e-2 on outputs of unit scale
   (bfloat16 rows and a bfloat16 hidden state: 2**-7 a rounding, two of
   them).

Needs a TPU.
"""

import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

D, F, EXPERTS, TOP_K = 2304, 896, 64, 8


def _stack(w, experts):
    return w.reshape(w.shape[0], experts, -1).swapaxes(0, 1)


def _weights(d, f, experts):
    import jax
    import jax.numpy as jnp

    kg, ku, kd = jax.random.split(jax.random.PRNGKey(0), 3)
    gate = jax.random.normal(kg, (d, experts * f), jnp.bfloat16) * d ** -0.5
    up = jax.random.normal(ku, (d, experts * f), jnp.bfloat16) * d ** -0.5
    down = jax.random.normal(kd, (f, experts * d), jnp.bfloat16) * f ** -0.5
    return gate, up, down


def timings(d, f, experts, k) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from zookeeper_tpu.ops.moe import grouped_matmul

    gate, up, down = _weights(d, f, experts)
    stacked = tuple(_stack(w, experts) for w in (gate, up, down))
    kx, kr = jax.random.split(jax.random.PRNGKey(1))

    def layer(matmul, weights):
        def run(rows, sizes):
            g = matmul(rows, weights[0], sizes, jnp.float32)
            u = matmul(rows, weights[1], sizes, jnp.float32)
            h = (jax.nn.silu(g) * u).astype(rows.dtype)
            return matmul(h, weights[2], sizes, rows.dtype)

        return jax.jit(run)

    def ragged(lhs, rhs, sizes, out):
        return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=out)

    def shipped(lhs, rhs, sizes, out):
        tm = 256 if lhs.shape[0] >= 4096 else 128
        return gmm(
            lhs, rhs, sizes, preferred_element_type=out,
            tiling=(tm, rhs.shape[1], rhs.shape[2]),
        )

    def timed(fn, *args):
        fn(*args).block_until_ready()
        samples = []
        for _ in range(20):
            t0 = time.perf_counter()
            fn(*args).block_until_ready()
            samples.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(samples)

    for tokens in (64, 2048):
        m = tokens * k
        rows = jax.random.normal(kx, (m, d), jnp.bfloat16)
        picks = jax.random.randint(kr, (m,), 0, experts)
        sizes = jnp.bincount(picks, length=experts).astype(jnp.int32)
        ops = 6.0 * m * d * f
        nbytes = 3.0 * min(experts, m) * d * f * 2
        least = 1e3 * max(ops / 197e12, nbytes / 819e9)
        print(
            f"moe probe: tokens {tokens} rows {m}: least {least:.3f} ms; "
            f"ragged_dot {timed(layer(ragged, stacked), rows, sizes):.3f} ms; "
            f"megablox gmm, stacked {timed(layer(shipped, stacked), rows, sizes):.3f} ms; "
            f"ops/moe.py, side by side "
            f"{timed(layer(grouped_matmul, (gate, up, down)), rows, sizes):.3f} ms",
            flush=True,
        )


def check(d, f, experts, k, token_counts=(64, 1024)) -> float:
    """The largest error of ``sparse_moe`` against the plain sum (point 2
    of the docstring); infinite if a row was lost."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from zookeeper_tpu.ops.moe import sparse_moe

    gate, up, down = _weights(d, f, experts)
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    router = jax.random.normal(kw, (d, experts), jnp.float32) * d ** -0.5
    g64, u64, d64 = (
        np.asarray(_stack(w, experts).astype(jnp.float32))
        for w in (gate, up, down)
    )
    worst = 0.0
    for tokens in token_counts:
        x = jax.random.normal(jax.random.fold_in(kx, tokens), (tokens, d), jnp.bfloat16)
        got, load = jax.jit(lambda *a: sparse_moe(*a, k=k))(x, router, gate, up, down)
        x64 = np.asarray(x.astype(jnp.float32), np.float64)
        logits = x64 @ np.asarray(router, np.float64)
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        order = np.argsort(-probs, axis=-1)
        ranked = np.take_along_axis(probs, order, -1)
        sure = ranked[:, k - 1] - ranked[:, k] > 1e-4
        chosen, w = order[:, :k], ranked[:, :k]
        w = w / w.sum(-1, keepdims=True)
        want = np.zeros((tokens, d))
        for e in range(experts):
            t, slot = np.nonzero(chosen == e)
            h = x64[t] @ g64[e].astype(np.float64)
            h = h / (1.0 + np.exp(-h)) * (x64[t] @ u64[e].astype(np.float64))
            np.add.at(want, t, w[t, slot][:, None] * (h @ d64[e].astype(np.float64)))
        err = np.abs(np.asarray(got.astype(jnp.float32)) - want)[sure].max()
        worst = max(worst, float(err))
        print(
            f"moe probe: sparse_moe at {tokens} tokens against the plain sum, "
            f"routing by numpy: largest error {err:.4f} over {int(sure.sum())} "
            f"tokens (output rms {np.sqrt((want ** 2).mean()):.3f}), rows "
            f"counted {int(load.sum())} of {tokens * k}",
            flush=True,
        )
        if int(load.sum()) != tokens * k:
            worst = float("inf")
    return worst


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print("moe probe: needs a TPU", file=sys.stderr)
        return 3
    timings(D, F, EXPERTS, TOP_K)
    worst = check(D, F, EXPERTS, TOP_K)
    print(f"moe probe: {'ok' if worst <= 4e-2 else 'WRONG'}", flush=True)
    return 0 if worst <= 4e-2 else 1


if __name__ == "__main__":
    sys.exit(main())
