"""Gated delta-rule linear attention (``ops/kda.py``): the chunked form in
both flavours (``jax.numpy``; the TPU's kernel in the interpreter) and the
one-token update against the recurrence token by token in float64, ragged
lengths, and the exact triangular solve."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu.ops import kda
from zookeeper_tpu.ops.ssm import causal_conv

B, H, DK, DV = 2, 3, 16, 8


def _inputs(s, seed=0, decay=0.1):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, s, H, DK))
    k = rng.standard_normal((B, s, H, DK))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((B, s, H, DV))
    g = -np.abs(rng.standard_normal((B, s, H, DK))) * decay
    beta = 2.0 / (1.0 + np.exp(-rng.standard_normal((B, s, H))))
    return q, k, v, g, beta


def _token_scan(q, k, v, g, beta, lengths=None, state=None):
    """The recurrence as it is written, a token at a time, float64."""
    b, s, h, dk = q.shape
    S = np.zeros((b, h, dk, v.shape[-1])) if state is None else state.copy()
    out = np.zeros((b, s, h, v.shape[-1]))
    for i in range(b):
        for t in range(s if lengths is None else int(lengths[i])):
            S[i] = np.exp(g[i, t])[..., None] * S[i]
            seen = np.einsum("hkv,hk->hv", S[i], k[i, t])
            S[i] += beta[i, t][:, None, None] * k[i, t][..., None] * (v[i, t] - seen)[:, None, :]
            out[i, t] = np.einsum("hkv,hk->hv", S[i], q[i, t])
    return out, S


def _f32(*arrays):
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays)


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize(
    "chunk,s", [(8, 37), (16, 16), (32, 70), (64, 64)],
    ids=["8x37", "16x16", "32x70", "64x64"],
)
def test_chunked_form_is_the_token_scan(chunk, s, interpret):
    q, k, v, g, beta = _inputs(s, seed=chunk)
    want_o, want_s = _token_scan(q, k, v, g, beta)
    o, state = kda.kda_chunk_scan(
        *_f32(q, k, v, g, beta), chunk=chunk, interpret=interpret
    )
    assert o.dtype == state.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(state, want_s, atol=2e-5)


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "pallas"])
def test_rows_past_a_sequences_length_leave_its_state_alone(interpret):
    """Ragged lengths in one call: each sequence's state is the state after
    its own last real token, and its real rows are what they would be
    alone."""
    s, lengths = 40, np.asarray([40, 13])
    q, k, v, g, beta = _inputs(s, seed=3)
    want_o, want_s = _token_scan(q, k, v, g, beta, lengths)
    o, state = kda.kda_chunk_scan(
        *_f32(q, k, v, g, beta), chunk=16, lengths=jnp.asarray(lengths),
        interpret=interpret,
    )
    np.testing.assert_allclose(state, want_s, atol=2e-5)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(o[i, :n], want_o[i, :n], atol=2e-5)


@pytest.mark.parametrize("split", [21, 1], ids=["after-21", "after-1"])
def test_one_token_updates_continue_the_scan(split):
    s = split + 6
    q, k, v, g, beta = _inputs(s, seed=split)
    want_o, want_s = _token_scan(q, k, v, g, beta)
    args = _f32(q, k, v, g, beta)
    _, state = kda.kda_chunk_scan(*(a[:, :split] for a in args), chunk=8)
    for t in range(split, s):
        o, state = kda.kda_decode_update(state, *(a[:, t] for a in args))
        np.testing.assert_allclose(o, want_o[:, t], atol=2e-5)
    np.testing.assert_allclose(state, want_s, atol=2e-5)


def test_strong_decay_and_equal_keys_solve_exactly():
    """The case a truncated solve gets wrong: every key of a chunk the
    same (the triangular system is dense, its entries as large as beta)
    and a decay of 0.37 a token."""
    s = 32
    q, k, v, g, beta = _inputs(s, seed=8)
    k[:] = k[:, :1]
    g[:] = -1.0
    beta[:] = 1.9
    want_o, want_s = _token_scan(q, k, v, g, beta)
    o, state = kda.kda_chunk_scan(*_f32(q, k, v, g, beta), chunk=32)
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(state, want_s, atol=5e-5)


@pytest.mark.parametrize("n", [1, 7, 16, 24, 64])
def test_unit_lower_inverse_is_the_inverse(n):
    rng = np.random.default_rng(n)
    L = np.tril(rng.standard_normal((3, n, n)), -1)
    got = kda._unit_lower_inverse(jnp.asarray(L, jnp.float32))
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(n) + L), atol=1e-4, rtol=1e-4
    )


def test_a_carried_convolution_feeds_the_same_keys():
    """The mixer's causal convolution (``ops/ssm.py:causal_conv``, no
    bias) split at a token: the carry of the first call makes the second
    call's rows what one call over both gives, ragged lengths included."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 20, 12)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((4, 12)), jnp.float32)
    whole, rows = causal_conv(x, kernel, None)
    first, carry = causal_conv(x[:, :9], kernel, None)
    second, rows2 = causal_conv(x[:, 9:], kernel, None, carry=carry)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole, atol=1e-6)
    np.testing.assert_array_equal(rows, rows2)
    # at its own length a padded sequence hands on its own last rows
    _, ragged = causal_conv(x, kernel, None, lengths=jnp.asarray([20, 9]))
    np.testing.assert_array_equal(ragged[1], carry[1])
    np.testing.assert_array_equal(ragged[0], rows[0])
