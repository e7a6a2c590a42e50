"""The benchmark's own weights: made from ``--seed`` on the device, in one
jitted call, in the type the program holds them in.

The program is handed these weights in the place of its own initializer's
(training: through its checkpoint-restore hook; serving: through its
model's ``initialize``), and the plain reference is handed the same
arrays. Neither side's initializer is the other's yardstick.

A leaf's distribution follows from its name and rank alone:

- ``scale`` (a norm's gain): ``1 + 0.1 * N(0, 1)``; ``bias``: ``0.1 * N``;
- ``embed`` and ``pos`` (token and position tables): ``0.02 * N``;
- any other leaf of rank >= 2 (a kernel): ``N / sqrt(fan_in)`` with
  ``fan_in`` the product of all but the last dimension;
- anything else: ``0.02 * N``.

Each leaf's key is ``fold_in(key(seed), crc32(path))``, so a leaf keeps
its values when leaves are added around it.
"""

import zlib
from typing import Any, Dict, Tuple

import numpy as np


def seed32(seed: int) -> int:
    """Fold an arbitrary whole-number seed (the driver's pass 2**31) into
    what ``numpy`` and ``jax.random`` both take."""
    return int(seed) % (2**31 - 1)


def _path_str(path) -> str:
    parts = []
    for entry in path:
        for attr in ("key", "name", "idx"):
            if hasattr(entry, attr):
                parts.append(str(getattr(entry, attr)))
                break
        else:
            parts.append(str(entry))
    return "/".join(parts)


def flat_paths(tree: Any) -> Dict[str, Any]:
    """``{"a/b/c": leaf}`` of a params tree (nested dicts of any kind)."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_path_str(path): leaf for path, leaf in leaves}


def _scale_and_shift(path: str, shape: Tuple[int, ...]) -> Tuple[float, float]:
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return 0.1, 1.0
    if leaf == "bias":
        return 0.1, 0.0
    if leaf in ("embed", "pos"):
        return 0.02, 0.0
    if len(shape) >= 2:
        fan_in = int(np.prod(shape[:-1]))
        return float(1.0 / np.sqrt(max(1, fan_in))), 0.0
    return 0.02, 0.0


def make_weights(like: Any, seed: int, shardings: Any = None) -> Any:
    """A tree shaped like ``like`` (arrays or ``ShapeDtypeStruct``s; a
    nested dict) filled from ``seed`` by the rule above, built on the
    device by one jitted call. ``shardings`` (a matching tree, optional)
    places the leaves."""
    import jax
    import jax.numpy as jnp

    paths = flat_paths(like)
    spec = {
        p: (tuple(v.shape), jnp.dtype(v.dtype)) for p, v in paths.items()
    }

    def build(key):
        flat = {}
        for path, (shape, dtype) in spec.items():
            scale, shift = _scale_and_shift(path, shape)
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            x = jax.random.normal(k, shape, jnp.float32) * scale + shift
            flat[path] = x.astype(dtype)
        return flat

    kwargs = {}
    if shardings is not None:
        kwargs["out_shardings"] = flat_paths(shardings)
    flat = jax.jit(build, **kwargs)(jax.random.PRNGKey(seed32(seed)))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[_path_str(path)] for path, _ in leaves]
    )
