"""Gated delta-rule linear attention with a decay a channel (Kimi Delta
Attention, arXiv:2510.26692), once: the chunked form a prefill runs and
the one-token update a decode step runs.

The recurrence, a head (``q_t``, ``k_t [dk]``, ``v_t [dv]``, the log
decay ``g_t [dk] <= 0`` a channel of the key, ``beta_t`` a scalar, ``S
[dk, dv]`` the state, from zeros), in float32:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The transition is not diagonal, so this is not ``ops/ssm.py``'s scan.
Two forms of it live here:

- :func:`kda_chunk_scan`: whole sequences in chunks of ``chunk`` tokens
  (the WY form). With ``w_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T
  k_t)`` the recurrence is ``S_t = Diag(exp g_t) S_{t-1} + k_t w_t^T``.
  Inside a chunk that starts from ``S_0``, with ``G_t = sum_{i <= t}
  g_i``, ``kf = k exp(G)``, ``kb = k exp(-G)``, ``qf = q exp(G)`` and ``L
  = Diag(beta) strict_tril(kf kb^T)``, the ``w`` of the chunk solve the
  unit lower-triangular system ``(I + L) W = Diag(beta) (V - kf S_0)``.
  ``T = (I + L)^-1`` does not depend on the state, so it is found for
  every chunk at once, exactly (forward substitution inside diagonal
  blocks of 16, the block inverse above them; float32 at the highest
  matmul precision, nothing truncated), and with ``Kbar = T Diag(beta)
  kf``, ``Ubar = T Diag(beta) V`` and ``P = tril(qf kb^T)`` a chunk is
  four matrix products against the carried state:

      W = Ubar - Kbar S_0        O = qf S_0 + P W
      S_C = Diag(exp G_C) S_0 + (k exp(G_C - G))^T W

  These four take operands in the type of ``q`` (bfloat16 where the
  model computes in it) with float32 accumulation; the decays, ``T`` and
  the state are float32. A row with ``g = 0`` and ``beta = 0`` leaves the
  state alone: rows at or past ``lengths`` are masked so, and the state
  that comes back is the state after each sequence's last real token. A
  chunk's ``exp(-G)`` must stay inside float32: ``chunk * min g > -80``,
  a decay of 0.29 a token or slower at chunks of 64. A Pallas kernel on
  a TPU (the head's state stays in VMEM across a sequence's chunks) and
  ``jax.numpy`` elsewhere, which is also the kernel's oracle in the
  tests (``interpret=True`` runs the kernel off the TPU).
- :func:`kda_decode_update`: one token for every slot in float32: both
  reads of the old state (``k^T Diag(alpha) S`` and ``q^T Diag(alpha)
  S``) in one pass, the rows scaled by ``alpha`` and one rank-1
  correction in a second, in place where the caller donates the state.
"""

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from zookeeper_tpu.ops.blocks import vmem_limit_bytes

__all__ = ["kda_chunk_scan", "kda_decode_update"]

_HIGHEST = jax.lax.Precision.HIGHEST
#: Forward substitution runs inside diagonal blocks of at most this.
_SOLVE_BLOCK = 16


def _unit_lower_inverse(L):
    """``(I + L)^-1`` for strictly lower-triangular ``L [..., n, n]``
    float32, exactly: row ``t`` of the inverse is ``e_t - sum_{i < t} L_ti
    row_i`` (forward substitution) inside blocks of ``_SOLVE_BLOCK``, and
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]`` above."""
    n = L.shape[-1]
    if n <= _SOLVE_BLOCK or n % 2:
        eye = jnp.eye(n, dtype=L.dtype)
        rows = []
        for t in range(n):
            row = jnp.broadcast_to(eye[t], L.shape[:-2] + (n,))
            if t:
                row = row - jnp.einsum(
                    "...i,...ij->...j", L[..., t, :t],
                    jnp.stack(rows, axis=-2), precision=_HIGHEST,
                )
            rows.append(row)
        return jnp.stack(rows, axis=-2)
    m = n // 2
    A = _unit_lower_inverse(L[..., :m, :m])
    B = _unit_lower_inverse(L[..., m:, m:])
    CA = jnp.einsum("...ij,...jk->...ik", L[..., m:, :m], A, precision=_HIGHEST)
    X = -jnp.einsum("...ij,...jk->...ik", B, CA, precision=_HIGHEST)
    top = jnp.concatenate([A, jnp.zeros_like(CA).swapaxes(-1, -2)], axis=-1)
    return jnp.concatenate(
        [top, jnp.concatenate([X, B], axis=-1)], axis=-2
    )


def _chunk_operands(q, k, v, g, beta, chunk):
    """What a chunk's four products read, for every chunk at once: ``q``,
    ``k [b, s, h, dk]``, ``v [b, s, h, dv]``, ``g [b, s, h, dk]`` and
    ``beta [b, s, h]`` float32 (``s`` whole chunks) -> ``(qf, Kbar, Ubar,
    P, kend, gend)``, each ``[b, h, chunks, chunk, .]`` (``gend [b, h,
    chunks, 1, dk]``); ``qf``, ``Kbar``, ``P`` and ``kend`` in ``q``'s
    type, ``Ubar`` and ``gend`` float32."""
    b, s, h, dk = q.shape
    f32, dtype = jnp.float32, q.dtype

    def chunks(x):  # [b, s, h, d] -> [b, h, c, chunk, d]
        x = x.reshape(b, s // chunk, chunk, h, x.shape[-1])
        return x.transpose(0, 3, 1, 2, 4).astype(f32)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])
    G = jnp.cumsum(g, axis=3)
    grow, shrink = jnp.exp(-G), jnp.exp(G)
    kf, kb, qf = k * shrink, k * grow, (q * shrink).astype(dtype)
    A = jnp.einsum("...id,...jd->...ij", kf, kb, precision=_HIGHEST)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    T = _unit_lower_inverse(beta * jnp.where(lower & ~lower.T, A, 0.0))
    Kbar = jnp.einsum("...ij,...jd->...id", T, beta * kf, precision=_HIGHEST)
    Ubar = jnp.einsum("...ij,...jd->...id", T, beta * v, precision=_HIGHEST)
    P = jnp.einsum(
        "...id,...jd->...ij", qf, kb.astype(dtype), preferred_element_type=f32
    )
    P = jnp.where(lower, P, 0.0).astype(dtype)
    gend = shrink[:, :, :, -1:]
    kend = (kb * gend).astype(dtype)
    return qf, Kbar.astype(dtype), Ubar, P, kend, gend


def kda_chunk_scan(
    q, k, v, g, beta, *, chunk: int, lengths=None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over whole sequences from a zero state: ``q``, ``k
    [b, s, h, dk]`` (their type is the four products' operand type), ``v
    [b, s, h, dv]``, ``g [b, s, h, dk]`` float32 log decays, ``beta [b,
    s, h]`` float32. Rows at or past ``lengths [b]`` (None: none) leave
    the state alone. Returns ``(o [b, s, h, dv] float32, state [b, h, dk,
    dv] float32)``, the state after each sequence's last real row. ``s``
    is padded to whole chunks here. ``interpret`` None: the Pallas kernel
    on a TPU, ``jax.numpy`` elsewhere; True/False force the kernel."""
    b, s, h, _ = q.shape
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if lengths is not None:
        real = jnp.arange(s)[None, :] < lengths[:, None]
        g = jnp.where(real[:, :, None, None], g, 0.0)
        beta = jnp.where(real[:, :, None], beta, 0.0)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    operands = _chunk_operands(q, k, v, g, beta, chunk)
    if interpret is None and jax.default_backend() != "tpu":
        o, state = _chunk_scan_jnp(*operands)
    else:
        o, state = _kda_chunk_scan(*operands, interpret=bool(interpret))
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, s + pad, h, -1)
    return (o[:, :s] if pad else o), state


def _chunk_scan_jnp(qf, Kbar, Ubar, P, kend, gend):
    """The chunks in order in ``jax.numpy``: a scan over the carried
    state, the same four products as the kernel."""
    b, h, _, _, dk = qf.shape
    f32, dtype = jnp.float32, qf.dtype

    def mm(eq, x, y):
        return jnp.einsum(
            eq, x.astype(dtype), y.astype(dtype), preferred_element_type=f32
        )

    def step(S, operands):
        qf_c, Kbar_c, Ubar_c, P_c, kend_c, gend_c = operands
        W = Ubar_c - mm("bhid,bhdv->bhiv", Kbar_c, S)
        O = mm("bhid,bhdv->bhiv", qf_c, S) + mm("bhij,bhjv->bhiv", P_c, W)
        S = gend_c[:, :, 0, :, None] * S + mm("bhid,bhiv->bhdv", kend_c, W)
        return S, O

    state, o = jax.lax.scan(
        step,
        jnp.zeros((b, h, dk, Ubar.shape[-1]), f32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (qf, Kbar, Ubar, P, kend, gend)),
    )
    return jnp.moveaxis(o, 0, 2), state


@partial(jax.jit, static_argnames=("interpret",))
def _kda_chunk_scan(qf, Kbar, Ubar, P, kend, gend, *, interpret=False):
    """The kernel behind :func:`kda_chunk_scan` on a TPU: one (sequence,
    head, chunk) a grid step, the chunks in order with the head's state
    carried in VMEM. The chunk's last decay arrives as a row and is
    turned, to scale the state's rows, by a masked lane sum against the
    identity. Jitted, so that the device trace names the op after this
    function."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, c, q, dk = qf.shape
    dv = Ubar.shape[-1]
    f32 = jnp.float32

    def kernel(
        qf_ref, kbar_ref, ubar_ref, p_ref, kend_ref, gend_ref,
        o_ref, state_ref, s_ref,
    ):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _start():
            s_ref[...] = jnp.zeros_like(s_ref)

        S = s_ref[...]
        dtype = qf_ref.dtype
        Sd = S.astype(dtype)
        W = ubar_ref[0, 0, 0] - jnp.dot(
            kbar_ref[0, 0, 0], Sd, preferred_element_type=f32
        )
        Wd = W.astype(dtype)
        o_ref[0, 0, 0] = jnp.dot(
            qf_ref[0, 0, 0], Sd, preferred_element_type=f32
        ) + jnp.dot(p_ref[0, 0, 0], Wd, preferred_element_type=f32)
        ii = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
        decay = jnp.sum(
            jnp.where(ii == jj, gend_ref[0, 0, 0], 0.0), axis=-1, keepdims=True
        )
        S = decay * S + jax.lax.dot_general(
            kend_ref[0, 0, 0], Wd, (((0,), (0,)), ((), ())),
            preferred_element_type=f32,
        )
        s_ref[...] = S

        @pl.when(ci == c - 1)
        def _end():
            state_ref[0, 0] = S

    def chunk_block(width):
        return pl.BlockSpec(
            (1, 1, 1, q, width), lambda i, j, n: (i, j, n, 0, 0)
        )

    step = 4 * (q * (3 * dk + 2 * dv + q) + dk + 2 * dk * dv + dk * dk)
    return pl.pallas_call(
        kernel,
        grid=(b, h, c),
        in_specs=[
            chunk_block(dk), chunk_block(dk), chunk_block(dv),
            chunk_block(q), chunk_block(dk),
            pl.BlockSpec((1, 1, 1, 1, dk), lambda i, j, n: (i, j, n, 0, 0)),
        ],
        out_specs=[
            chunk_block(dv),
            pl.BlockSpec((1, 1, dk, dv), lambda i, j, n: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, c, q, dv), f32),
            jax.ShapeDtypeStruct((b, h, dk, dv), f32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(2 * step),
        ),
        interpret=interpret,
    )(qf, Kbar, Ubar, P, kend, gend)


def kda_decode_update(state, q, k, v, g, beta) -> Tuple[jax.Array, jax.Array]:
    """One token for every sequence: ``state [b, h, dk, dv]`` float32,
    ``q``, ``k [b, h, dk]``, ``v [b, h, dv]``, ``g [b, h, dk]`` float32
    log decays, ``beta [b, h]`` float32. Returns ``(o [b, h, dv]
    float32, new state)``. Float32 throughout, elementwise over the state
    in its own shape: one pass reads what the old state gives the key and
    the query (``u = (alpha k)^T S``, ``(alpha q)^T S``), a second scales
    its rows by ``alpha`` and adds ``k (beta (v - u))^T``; the output
    follows from the two reads, ``o = (alpha q)^T S + (q . k) beta (v -
    u)``, without a third."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha = jnp.exp(g.astype(f32))
    u = jnp.sum(state * (alpha * k)[..., None], axis=2)
    seen = jnp.sum(state * (alpha * q)[..., None], axis=2)
    delta = beta.astype(f32)[..., None] * (v - u)  # [b, h, dv]
    state = alpha[..., None] * state + k[..., None] * delta[:, :, None, :]
    o = seen + jnp.sum(q * k, axis=-1, keepdims=True) * delta
    return o, state
