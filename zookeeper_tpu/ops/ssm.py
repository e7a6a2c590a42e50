"""A Mamba-2 state-space mixer's mathematics, once: the causal depthwise
convolution with its carry, the chunked scan a prefill runs, and the
one-token update a decode step runs.

The recurrence, a head (``x_t [p]`` the head's input, ``B_t``, ``C_t
[n]`` its group's, ``dt_t > 0`` and ``A < 0`` scalars, ``S [p, n]`` the
state), in float32:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

(the skip ``D x_t`` is the caller's: an elementwise add). Two forms of
it live here:

- :func:`ssm_chunk_scan`: a whole sequence in chunks of ``chunk``
  tokens. With ``a_i = sum_{t <= i} dt_t A`` inside a chunk, a chunk's
  outputs are ``((C B^T) * L * dt) x`` with ``L_ij = exp(a_i - a_j)`` for
  ``i >= j`` (matrix products, bfloat16 operands where the model computes
  in bfloat16, float32 accumulation) plus ``exp(a_i) C_i S_prev``, and the
  state it hands on is ``exp(a_end) S_prev + (x * dt exp(a_end - a))^T
  B``. ``dt = 0`` neither decays nor adds: a caller masks ``dt`` past a
  sequence's own length and the state that comes back is the state after
  its last real token, whatever the padding. A Pallas kernel on a TPU
  and plain ``jax.numpy`` elsewhere (which is also the kernel's oracle
  in the tests; ``interpret=True`` runs the kernel off the TPU).
- :func:`ssm_decode_update`: one token for every slot, elementwise in
  float32 over the whole state, which is read and written once, in place
  where the caller donates it. Plain ``jax.numpy`` everywhere.

The state, ``dt``, ``A`` and every decay are float32 in both.
"""

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from zookeeper_tpu.ops.blocks import vmem_limit_bytes

__all__ = ["causal_conv", "ssm_chunk_scan", "ssm_decode_update"]


def causal_conv(x, kernel, bias, carry=None, lengths=None):
    """Causal depthwise convolution over the sequence: ``x [b, s, c]``,
    ``kernel [k, c]`` (tap ``k - 1`` meets the current row), ``bias
    [c]`` (None: none); ``y_t = bias + sum_j kernel[j] x_{t - (k - 1) + j}``, the rows
    before position 0 taken from ``carry [b, k - 1, c]`` (zeros where
    None: a sequence's start). Returns ``(y [b, s, c]`` in ``x``'s type,
    float32 sums, ``rows [b, k - 1, c])``: the last ``k - 1`` inputs of
    each sequence at its own length (``lengths [b]``; None: ``s``), which
    is the next call's ``carry``. Rows of ``x`` at or past ``lengths`` do
    not reach ``rows``."""
    b, s, c = x.shape
    taps = kernel.shape[0]
    if carry is None:
        carry = jnp.zeros((b, taps - 1, c), x.dtype)
    padded = jnp.concatenate([carry.astype(x.dtype), x], axis=1)
    y = 0.0 if bias is None else bias.astype(jnp.float32)
    for j in range(taps):
        y = y + kernel[j].astype(jnp.float32) * padded[:, j : j + s].astype(
            jnp.float32
        )
    if lengths is None:
        rows = padded[:, s:]
    else:
        # position p sits at padded row p + taps - 1: the rows of
        # positions lengths - (taps - 1) .. lengths - 1
        at = lengths[:, None] + jnp.arange(taps - 1)[None, :]
        rows = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return y.astype(x.dtype), rows


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -- the chunked scan ---------------------------------------------------------


def ssm_chunk_scan(
    x, dt, A, B, C, *, chunk: int, interpret: Optional[bool] = None
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over whole sequences from a zero state: ``x [b, s,
    h, p]`` (its type is the matrix products' operand type), ``dt [b, s,
    h]`` float32 (0 where a row is padding), ``A [h]`` float32, ``B``,
    ``C [b, s, g, n]`` (group ``j`` serves heads ``j h / g ..``).
    Returns ``(y [b, s, h, p] float32, state [b, h, p, n] float32)``, the
    state after the last row. ``s`` is padded to whole chunks here (with
    ``dt = 0`` rows). ``interpret`` None: the Pallas kernel on a TPU,
    ``jax.numpy`` elsewhere; True/False force the kernel."""
    b, s, h, p = x.shape
    pad = -s % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    dt = dt.astype(jnp.float32)
    a = dt * A.astype(jnp.float32)  # [b, s, h], <= 0
    if interpret is None and not _on_tpu():
        y, state = _chunk_scan_jnp(x, dt, a, B, C, chunk)
    else:
        y, state = _ssm_chunk_scan(
            x, dt, a, B, C, chunk=chunk, interpret=bool(interpret)
        )
    return (y[:, :s] if pad else y), state


def _chunk_scan_jnp(x, dt, a, B, C, chunk):
    """:func:`ssm_chunk_scan` in ``jax.numpy`` (``s`` whole chunks):
    within a chunk by matrix products, across chunks by a scan over the
    carried state."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    hg, q, c = h // g, chunk, s // chunk
    f32 = jnp.float32
    xs = x.reshape(b, c, q, g, hg, p)
    Bs, Cs = B.reshape(b, c, q, g, n), C.reshape(b, c, q, g, n)
    dts = dt.reshape(b, c, q, g, hg)
    acum = jnp.cumsum(a.reshape(b, c, q, g, hg), axis=2)
    # [b, c, g, hg, i, j]: exp(a_i - a_j) where i >= j
    by_head = jnp.moveaxis(acum, 2, -1)
    seg = by_head[..., :, None] - by_head[..., None, :]
    L = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg, -jnp.inf))
    CB = jnp.einsum("bcign,bcjgn->bcgij", Cs, Bs, preferred_element_type=f32)
    M = CB[:, :, :, None] * L * jnp.moveaxis(dts, 2, -1)[..., None, :]
    y = jnp.einsum(
        "bcghij,bcjghp->bcighp", M.astype(x.dtype), xs,
        preferred_element_type=f32,
    )
    end = acum[:, :, -1:]  # [b, c, 1, g, hg]
    xw = (xs.astype(f32) * (dts * jnp.exp(end - acum))[..., None]).astype(x.dtype)
    states = jnp.einsum(
        "bcjghp,bcjgn->bcghpn", xw, Bs, preferred_element_type=f32
    )

    def carry(S, step):
        decay, add = step
        return decay[..., None, None] * S + add, S

    last, before = jax.lax.scan(
        carry,
        jnp.zeros((b, g, hg, p, n), f32),
        (jnp.moveaxis(jnp.exp(end[:, :, 0]), 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # [b, c, g, hg, p, n]
    y = y + jnp.exp(acum)[..., None] * jnp.einsum(
        "bcign,bcghpn->bcighp", Cs, before.astype(x.dtype),
        preferred_element_type=f32,
    )
    return y.reshape(b, s, h, p), last.reshape(b, h, p, n)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssm_chunk_scan(x, dt, a, B, C, *, chunk, interpret=False):
    """The kernel behind :func:`ssm_chunk_scan` on a TPU: one (sequence,
    head, chunk) a grid step, the chunks in order with the state carried
    in VMEM. A step's vectors (``dt``, the running sum of ``a``) arrive
    as rows; the one that has to scale rows of a matrix is turned by a
    masked lane sum against the identity. Jitted, so that the device
    trace names the op after this function."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, p = x.shape
    g, n = B.shape[2:]
    hg, q, c = h // g, chunk, s // chunk
    f32 = jnp.float32
    acum = jnp.cumsum(a.reshape(b, c, q, h), axis=2)
    # [b, h, c, 2, q]: a chunk's dt and running sum, as rows
    rows = jnp.stack([dt.reshape(b, c, q, h), acum], axis=-1)
    rows = rows.transpose(0, 3, 1, 4, 2)
    xt = x.transpose(0, 2, 1, 3)  # [b, h, s, p]
    Bt, Ct = B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3)

    def kernel(rows_ref, x_ref, b_ref, c_ref, y_ref, state_ref, s_ref):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _start():
            s_ref[...] = jnp.zeros_like(s_ref)

        dt_row = rows_ref[0, 0, 0, 0:1, :]  # [1, q]
        a_row = rows_ref[0, 0, 0, 1:2, :]
        xs, Bm, Cm = x_ref[0, 0], b_ref[0, 0], c_ref[0, 0]
        ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)

        def column(row):
            return jnp.sum(jnp.where(ii == jj, row, 0.0), axis=-1, keepdims=True)

        a_col = column(a_row)
        L = jnp.exp(jnp.where(ii >= jj, a_col - a_row, -jnp.inf))
        CB = jax.lax.dot_general(
            Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=f32
        )
        y = jnp.dot(
            (CB * L * dt_row).astype(xs.dtype), xs, preferred_element_type=f32
        )
        S = s_ref[...]
        y = y + jnp.exp(a_col) * jax.lax.dot_general(
            Cm, S.astype(xs.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )
        y_ref[0, 0] = y.astype(y_ref.dtype)
        # the chunk's last running sum, as a [1, 1] every lane can read
        # (a slice at lane q - 1 cannot be broadcast both ways)
        a_end = jnp.sum(
            jnp.where(jj[:1] == q - 1, a_row, 0.0), axis=-1, keepdims=True
        )
        xw = (xs.astype(f32) * column(dt_row * jnp.exp(a_end - a_row))).astype(
            xs.dtype
        )
        S = jnp.exp(a_end) * S + jax.lax.dot_general(
            xw, Bm, (((0,), (0,)), ((), ())), preferred_element_type=f32
        )
        s_ref[...] = S

        @pl.when(ci == c - 1)
        def _end():
            state_ref[0, 0] = S

    step = 4 * (q * (2 * p + 2 * n) + 2 * p * n + 4 * q * q)
    y, state = pl.pallas_call(
        kernel,
        grid=(b, h, c),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 2, q), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, q, p), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j, k: (i, j // hg, k, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j, k: (i, j // hg, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), f32),
            jax.ShapeDtypeStruct((b, h, p, n), f32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(2 * step),
        ),
        interpret=interpret,
    )(rows, xt, Bt, Ct)
    return y.transpose(0, 2, 1, 3), state


# -- the one-token update -----------------------------------------------------


def ssm_decode_update(state, x, dt, A, B, C) -> Tuple[jax.Array, jax.Array]:
    """One token for every sequence: ``state [b, h, p, n]`` float32, ``x
    [b, h, p]``, ``dt [b, h]`` float32, ``A [h]``, ``B``, ``C [b, g,
    n]``. Returns ``(y [b, h, p] float32, new state)``. Float32
    throughout, elementwise over the state in its own shape, so that XLA
    fuses it into one pass that reads and writes the state once (in
    place where the caller donates it: 80% of HBM's peak on a v5e, which
    a Pallas kernel of the same arithmetic did not beat)."""
    f32 = jnp.float32
    heads_a_group = state.shape[1] // B.shape[1]
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))  # [b, h]
    dtx = dt[..., None] * x.astype(f32)  # [b, h, p]
    B = jnp.repeat(B.astype(f32), heads_a_group, axis=1)  # [b, h, n]
    C = jnp.repeat(C.astype(f32), heads_a_group, axis=1)
    state = decay[..., None, None] * state + dtx[..., None] * B[:, :, None, :]
    return jnp.sum(state * C[:, :, None, :], axis=-1), state
