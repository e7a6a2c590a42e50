"""Entry kind ``serve_shift``: the ``serve`` entry, every line of it, with
a constant added to the leaves of the benchmark's weights that the
configuration's file names (``weights_shift``: ``{"<leaf path's end>":
constant}``, e.g. ``{"kda_dt_bias": -4.0}``), and then ``serve_gain``'s
gains where the file also gives ``weights_gain``.

Why. ``zkbench/weights.py`` draws every vector about 0 (``0.02 N``). A
leaf that a model's family initialises about another value cannot be
reached by a gain: ``solar_open2_ep8_4l``'s decay is ``-exp(A_log)
softplus(. + dt_bias)``, the family draws ``dt_bias`` so that the
``softplus`` is 0.001-0.1 (a state that remembers tens to hundreds of
tokens), and about 0 it is 0.7: a state that forgets in two tokens, which
would hide a lost state from the comparison that decides ``correct`` as
PR 31's first scales hid its mixer. The shift is the same draw about
another mean: ``0.02 N + constant``, rounded to the leaf's type. The
program and the plain reference are handed the same arrays, as in
``serve``; the program's code and the reference's equations are the
published ones.

How. As ``serve_gain``: ``serve.run`` looks ``make_weights`` up in its own
module when the program asks for its weights; this entry puts a wrapper
there for the length of the run and takes it away again. A leaf no shift
names is what ``serve`` gives it, bit for bit.
"""

import os
from typing import Dict

from zkbench import weights
from zkbench.cells import load_module


def shifted(make_weights, shifts: Dict[str, float]):
    """``make_weights`` with the constant of ``shifts`` added, in float32
    and rounded back to the leaf's own type, to each leaf whose path is,
    or ends in ``/`` and, a key of it."""

    def named_by(path: str):
        for suffix in shifts:
            if path == suffix or path.endswith("/" + suffix):
                return suffix
        return None

    def make(like, seed, shardings=None):
        import jax
        import jax.numpy as jnp

        tree = make_weights(like, seed, shardings)
        flat = weights.flat_paths(tree)
        named = {path: named_by(path) for path in flat}
        unused = set(shifts) - set(named.values())
        if unused:
            raise ValueError(
                f"weights_shift names no leaf: {sorted(unused)} (leaves: "
                f"{sorted(flat)[:8]} ...)"
            )
        leaves = [
            leaf if named[path] is None
            else (
                leaf.astype(jnp.float32) + float(shifts[named[path]])
            ).astype(leaf.dtype)
            for path, leaf in flat.items()
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tree), leaves
        )

    return make


def run(ctx):
    entries = os.path.join(ctx.cell.bench_dir, "entries")
    serve = load_module(os.path.join(entries, "serve.py"), "serve")
    config = ctx.cell.config
    inner = serve
    if config.get("weights_gain"):
        inner = load_module(os.path.join(entries, "serve_gain.py"), "serve_gain")
    plain = serve.make_weights
    serve.make_weights = shifted(plain, config["weights_shift"])
    try:
        return inner.run(ctx)
    finally:
        serve.make_weights = plain
