"""Entry kind ``serve_gain``: the ``serve`` entry, every line of it, with
the benchmark's weights drawn at a gain that the configuration's file
gives to named leaves (``weights_gain``: ``{"<leaf path's end>": gain}``,
e.g. ``{"ssm_in/kernel": 16.0}``).

Why. ``zkbench/weights.py`` draws every kernel so that it preserves its
input's variance. A configuration whose published multipliers presume
trained weights of another scale then computes with branches that are all
but switched off, and the comparison that decides ``correct`` cannot see
them (``falcon_h1_34b_4l``: 0.25 x 0.18-0.5 in front of its state-space
mixer leave the mixer at 1% of the residual stream and a lost recurrent
state under bfloat16's rounding; ``PERF.md`` section 2). A gain on the
leaf in front of the branch is the same draw at another scale: ``N(0, 1)
* gain / sqrt(fan_in)``. The program and the plain reference are handed
the same arrays, as in ``serve``; the program's code, its multipliers and
the reference's equations are the published ones.

How. ``serve.run`` looks ``make_weights`` up in its own module when the
program asks for its weights; this entry puts a wrapper there for the
length of the run and takes it away again. Gains are powers of two, so
that scaling a bfloat16 leaf is exact and the leaf is what drawing it at
that scale and rounding would have given. A leaf no gain names is what
``serve`` gives it, bit for bit. (A ``benchmark`` PR can move the rule
into ``zkbench/weights.py`` and delete this file: ``PERF.md`` section 7.)
"""

import math
from typing import Dict

from zkbench import weights
from zkbench.cells import load_module


def gained(make_weights, gains: Dict[str, float]):
    """``make_weights`` with each leaf whose path is, or ends in ``/`` and,
    a key of ``gains`` multiplied by that gain in its own type."""
    for suffix, gain in gains.items():
        if gain <= 0 or math.frexp(gain)[0] != 0.5:
            raise ValueError(
                f"weights_gain[{suffix!r}] = {gain}: a gain is a power of "
                "two (exact in bfloat16)"
            )

    def named_by(path: str):
        for suffix in gains:
            if path == suffix or path.endswith("/" + suffix):
                return suffix
        return None

    def make(like, seed, shardings=None):
        import jax

        tree = make_weights(like, seed, shardings)
        flat = weights.flat_paths(tree)
        named = {path: named_by(path) for path in flat}
        unused = set(gains) - set(named.values())
        if unused:
            raise ValueError(
                f"weights_gain names no leaf: {sorted(unused)} (leaves: "
                f"{sorted(flat)[:8]} ...)"
            )
        leaves = [
            leaf if named[path] is None
            else (leaf * float(gains[named[path]])).astype(leaf.dtype)
            for path, leaf in flat.items()
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tree), leaves
        )

    return make


def run(ctx):
    import os

    serve = load_module(
        os.path.join(ctx.cell.bench_dir, "entries", "serve.py"), "serve"
    )
    plain = serve.make_weights
    serve.make_weights = gained(plain, ctx.cell.config["weights_gain"])
    try:
        return serve.run(ctx)
    finally:
        serve.make_weights = plain
