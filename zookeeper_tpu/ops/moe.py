"""Sparse mixture-of-experts feed-forward: router, top-k, grouped matmul.

One function, :func:`sparse_moe`, serves a decode step's few dozen tokens
and a prefill's thousands alike:

1. route: ``softmax(x @ router)`` in float32 (a bfloat16 router flips
   near-ties between experts, and the served token with them), the ``k``
   largest per token, their weights renormalised to sum 1;
2. sort the ``tokens * k`` (token, expert) pairs by expert, so each
   expert's rows are contiguous;
3. three grouped matmuls over the sorted rows (row block ``e`` meets
   expert ``e``'s matrix; :func:`grouped_matmul`): gate and up
   ``[d, f]``, SwiGLU, down ``[f, d]``;
4. unsort, weight, and sum each token's ``k`` rows.

No token is dropped: there is no capacity limit, an expert takes however
many rows the router sends it (``group_sizes`` carries the counts, and
is returned so a caller can watch the load).

The experts' matrices lie side by side in one plain kernel a
projection: gate and up ``[d, experts * f]``, down ``[f, experts * d]``,
expert ``e`` the column block ``e``. Each leaf is then a ``[fan_in,
out]`` matrix like every other kernel of the model (an initializer or a
weight generator that scales by fan-in scales it per expert), and the
grouped kernel finds an expert by a block index, with no leading
dimension to step over.

A chip's share of the experts (``held=(first, count)``; docs/DESIGN.md
§28): the router keeps every expert's column and the top-k is over all of
them, the three expert leaves hold the ``count`` column blocks of experts
``first .. first + count``, and only the (token, choice) pairs whose
expert is held are grouped and multiplied. The result is the part of the
layer's output that those experts give: the parts of every share add up
to the uncut layer. What the other chips hold, and the exchange that
would sum the parts, are not here. A shared expert beside the routed
ones is the block's (``models/transformer.py``), not this function's.
"""

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from zookeeper_tpu.ops.blocks import vmem_limit_bytes


def grouped_matmul(lhs, rhs, group_sizes, out_dtype):
    """``lhs [m, k]`` (rows sorted by group) times ``rhs [k, groups * n]``
    (group ``g`` the columns ``g * n ..``) -> ``[m, n]``: rows
    ``sum(group_sizes[:g]) ..`` meet group ``g``'s block.

    On a TPU a Pallas kernel (:func:`_gmm`): a row tile against the
    whole of one expert's block a grid step, over the (row tile, group)
    pairs that hold rows only; a block past ``_BLOCK_BYTES`` (4096 x
    1280: a row tile against it overflows VMEM) is taken in equal column
    tiles, the pairs run once for each. Elsewhere ``jax.lax.ragged_dot``, which
    is also its oracle in the tests. Measured on the v5e at the
    ``mellum2_8l`` cell's two shapes, a layer's three matmuls
    (``benchmarks/probes/moe_grouped_matmul.py``; PERF.md, PR 26)."""
    k = lhs.shape[1]
    groups = group_sizes.shape[0]
    n = rhs.shape[1] // groups
    if jax.default_backend() != "tpu":
        stacked = rhs.reshape(k, groups, n).swapaxes(0, 1)
        return jax.lax.ragged_dot(
            lhs, stacked, group_sizes, preferred_element_type=out_dtype
        )
    m = lhs.shape[0]
    # 256 rows a tile once an expert sees hundreds (a prefill), 128
    # below; 512 rows against a whole block overflow VMEM.
    tm = 256 if m >= 4096 and m % 256 == 0 else 128
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(
        lhs, rhs, group_sizes, out_dtype=out_dtype, tm=tm,
        tn=_column_tile(k, n, rhs.dtype.itemsize),
    )
    return out[:m] if pad else out


#: The largest block of one expert's matrix a grid step takes whole.
_BLOCK_BYTES = 6 * 2**20


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of an expert's ``[k, n]`` block a grid step takes: all of
    them where the block is within ``_BLOCK_BYTES``, else the widest equal
    tile of whole 128-lane registers that is."""
    fits = [
        n // parts for parts in range(1, n // 128 + 1)
        if n % (parts * 128) == 0 and k * (n // parts) * itemsize <= _BLOCK_BYTES
    ]
    return fits[0] if fits else n


@partial(jax.jit, static_argnames=("out_dtype", "tm", "tn", "interpret"))
def _gmm(lhs, rhs, group_sizes, *, out_dtype, tm, tn=None, interpret=False):
    """The kernel behind :func:`grouped_matmul` on a TPU (``m`` a
    multiple of ``tm``). The grid runs over the (row tile, group) pairs
    that hold rows, in row order (the bookkeeping is the one jax ships
    with its own grouped kernel): a tile that two groups share is
    visited once for each, stays in VMEM between the visits, and each
    visit stores its own rows only. Rows past the last group's are left
    as they were allocated. ``tn`` (None: all): the columns of a group's
    block a step takes; the pairs then run once a column tile, the tiles
    outermost, so that a shared row tile still stays between its visits.
    Jitted, so that the device trace names the op after this function."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    m, k = lhs.shape
    groups = group_sizes.shape[0]
    n = rhs.shape[1] // groups
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=False,
    )

    # One grid axis over the visits where a block is taken whole (the
    # call as it always was); a leading axis over the column tiles else.
    tiles = 1 if tn is None else n // tn
    tiled = tiles > 1
    tn = n // tiles

    def kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref):
        i = pl.program_id(int(tiled))
        acc = jnp.dot(
            lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32
        )
        g = group_ids[i]
        row = tile_ids[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0
        )
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    if tiled:
        grid = (tiles, visits)
        in_specs = [
            pl.BlockSpec((tm, k), lambda j, i, o, g, t: (t[i], 0)),
            pl.BlockSpec((k, tn), lambda j, i, o, g, t: (0, g[i] * tiles + j)),
        ]
        out_specs = pl.BlockSpec((tm, tn), lambda j, i, o, g, t: (t[i], j))
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(
                2 * (tm * k + k * tn) * lhs.dtype.itemsize + 8 * tm * tn
            ),
        )
    else:
        grid = (visits,)
        in_specs = [
            pl.BlockSpec((tm, k), lambda i, o, g, t: (t[i], 0)),
            pl.BlockSpec((k, n), lambda i, o, g, t: (0, g[i])),
        ]
        out_specs = pl.BlockSpec((tm, n), lambda i, o, g, t: (t[i], 0))
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, in_specs=in_specs, out_specs=out_specs,
            grid=grid,
        ),
        compiler_params=compiler_params,
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)


def route_top_k(
    x: jax.Array, router: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """``(weights [t, k] float32, experts [t, k] int32)`` for tokens
    ``x [t, d]`` under ``router [d, experts]``: softmax over every
    expert in float32 at the highest matmul precision, the ``k``
    largest, renormalised to sum 1."""
    logits = jnp.dot(
        x.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def sparse_moe(
    x: jax.Array,
    router: jax.Array,
    gate: jax.Array,
    up: jax.Array,
    down: jax.Array,
    *,
    k: int,
    held: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``y [t, d]`` and ``tokens_per_expert [experts] int32`` for
    ``x [t, d]``: ``y = sum_e w_e down_e(silu(gate_e x) * up_e x)`` over
    each token's ``k`` routed experts (see the module docstring). The
    matmuls run in ``x``'s dtype with float32 accumulation.

    ``held = (first, count)``: ``gate``, ``up`` and ``down`` hold the
    column blocks of experts ``first .. first + count`` only, the sum
    runs over a token's routed experts among those, and the counts are
    theirs (``[count]``). The pairs whose expert is not held sort behind
    the last group, where the grouped matmul computes nothing."""
    t, d = x.shape
    num_experts = router.shape[1]
    weights, experts = route_top_k(x, router, k)
    mine = None
    if held is not None:
        first, num_experts = held
        mine = (experts >= first) & (experts < first + num_experts)
        experts = jnp.where(mine, experts - first, num_experts)
        weights = jnp.where(mine, weights, 0.0)

    flat_expert = experts.reshape(t * k)
    order = jnp.argsort(flat_expert, stable=True)
    rows = x[order // k]
    # (a share's pairs of absent experts count in one bin past the held)
    bins = num_experts if mine is None else num_experts + 1
    group_sizes = jnp.bincount(flat_expert, length=bins)[:num_experts].astype(
        jnp.int32
    )

    def grouped(lhs, rhs, out_dtype):
        return grouped_matmul(
            lhs, rhs.astype(lhs.dtype), group_sizes, out_dtype
        )

    hidden = jax.nn.silu(grouped(rows, gate, jnp.float32)) * grouped(
        rows, up, jnp.float32
    )
    # the down projection's rows leave in x's dtype (the accumulation
    # inside the matmul is float32 either way): at a prefill's 57,344
    # rows a float32 copy of them and of their unsorted twin is 1 GB
    out = grouped(hidden.astype(x.dtype), down, x.dtype)

    # back to (token, choice) order by the inverse permutation: a gather,
    # where a scatter-add over tokens would serialise on the TPU
    inverse = jnp.argsort(order)
    out = out[inverse].reshape(t, k, d)
    if mine is not None:
        # rows behind the last group are whatever the buffer held
        out = jnp.where(mine[..., None], out, 0)
    y = jnp.sum(out.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), group_sizes
