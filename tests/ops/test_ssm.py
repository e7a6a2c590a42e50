"""``ops/ssm.py`` against the token-by-token scan of the plain reference
(``benchmarks/reference/falcon_h1.py:recurrence``): the chunked form in
both flavours (``jax.numpy`` and the Pallas kernel, interpreted), the
one-token update, and the causal convolution with its carry."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "models")
)
import falcon_h1_tiny as tiny  # noqa: E402

from zookeeper_tpu.ops import ssm  # noqa: E402

reference = tiny.load_reference()

HEADS, P, GROUPS, N = 4, 8, 2, 16


def _inputs(s, seed=0, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, s, HEADS, P))
    B = jax.random.normal(ks[1], (batch, s, GROUPS, N))
    C = jax.random.normal(ks[2], (batch, s, GROUPS, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (batch, s, HEADS)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[4], (HEADS,)))
    return x, dt, A, B, C


def _scan(x, dt, A, B, C):
    """The reference's scan, a sequence at a time."""
    per = HEADS // GROUPS
    ys, states = [], []
    for i in range(x.shape[0]):
        y, last = reference.recurrence(
            x[i], jnp.repeat(B[i], per, axis=1), jnp.repeat(C[i], per, axis=1),
            dt[i], A,
        )
        ys.append(y)
        states.append(last)
    return jnp.stack(ys), jnp.stack(states)


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize(
    "chunk,s", [(4, 16), (4, 13), (4, 3), (128, 256), (128, 200), (128, 40)]
)
def test_chunked_form_is_the_token_scan(chunk, s, interpret):
    """Lengths that are and are not whole chunks, shorter than one chunk
    too: outputs and the state handed on."""
    x, dt, A, B, C = _inputs(s, seed=s)
    want_y, want_state = _scan(x, dt, A, B, C)
    y, state = ssm.ssm_chunk_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    assert y.shape == want_y.shape and state.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "pallas"])
def test_zero_dt_rows_do_not_advance_the_state(interpret):
    """What a caller masks (``dt = 0`` past a sequence's length) neither
    decays the state nor adds to it."""
    x, dt, A, B, C = _inputs(24, seed=1)
    lengths = jnp.asarray([9, 24])
    real = jnp.arange(24)[None, :] < lengths[:, None]
    _, state = ssm.ssm_chunk_scan(
        x, jnp.where(real[..., None], dt, 0.0), A, B, C, chunk=8,
        interpret=interpret,
    )
    _, short = _scan(x[:1, :9], dt[:1, :9], A, B[:1, :9], C[:1, :9])
    _, whole = _scan(x[1:], dt[1:], A, B[1:], C[1:])
    np.testing.assert_allclose(state[0], short[0], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(state[1], whole[0], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("split", [11, 1], ids=["after-11", "after-1"])
def test_one_token_updates_continue_the_scan(split):
    """A prefix by the chunked form, then token by token: the same
    outputs and state as the scan over the whole sequence."""
    s = 20
    x, dt, A, B, C = _inputs(s, seed=2)
    want_y, want_state = _scan(x, dt, A, B, C)
    _, state = ssm.ssm_chunk_scan(
        x[:, :split], dt[:, :split], A, B[:, :split], C[:, :split], chunk=4
    )
    for t in range(split, s):
        y, state = ssm.ssm_decode_update(
            state, x[:, t], dt[:, t], A, B[:, t], C[:, t]
        )
        np.testing.assert_allclose(y, want_y[:, t], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-4, rtol=2e-4)


def test_conv_carry_joins_two_calls_and_reads_rows_at_each_length():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (2, 12, 6))
    kernel, bias = jax.random.normal(ks[1], (4, 6)), jax.random.normal(ks[2], (6,))
    whole, rows = ssm.causal_conv(x, kernel, bias)
    # by hand: tap 3 meets the current row, zeros before the start
    padded = np.concatenate([np.zeros((2, 3, 6)), np.asarray(x)], axis=1)
    want = sum(np.asarray(kernel)[j] * padded[:, j : j + 12] for j in range(4)) + np.asarray(bias)
    np.testing.assert_allclose(whole, want, atol=1e-5)
    np.testing.assert_array_equal(rows, x[:, 9:])
    first, carry = ssm.causal_conv(x[:, :7], kernel, bias)
    second, rows2 = ssm.causal_conv(x[:, 7:], kernel, bias, carry=carry)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole, atol=1e-5)
    np.testing.assert_array_equal(rows2, rows)
    # rows at each sequence's own length; a length under 3 keeps zeros
    _, at = ssm.causal_conv(x, kernel, bias, lengths=jnp.asarray([2, 7]))
    np.testing.assert_array_equal(at[0], jnp.concatenate([jnp.zeros((1, 6)), x[0, :2]]))
    np.testing.assert_array_equal(at[1], x[1, 4:7])
