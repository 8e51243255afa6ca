"""ops/linear_attention.py: the chunked gated delta rule and its one-token step
against the recurrence they restate, token by token in float32.

Tolerances: everything is float32 at ``highest`` matmul precision, so the two
forms differ by rounding alone; the chunked form solves a 64 x 64 triangular
system a sub-chunk and sums over it, which leaves ~1e-5 of values of order one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.linear_attention import (
    SUB_CHUNK,
    _unit_lower_inverse,
    gated_delta_chunk,
    gated_delta_step,
)

H, DK, DV = 3, 24, 40
TOL = dict(rtol=2e-4, atol=2e-5)


def _inputs(seed, B, T, beta_range=(0.0, 2.0), g_scale=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, DK))
    k = jax.random.normal(ks[1], (B, T, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, DV))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    lo, hi = beta_range
    beta = lo + (hi - lo) * jax.random.uniform(ks[4], (B, T, H))
    S0 = jax.random.normal(ks[5], (B, H, DK, DV))
    return q, k, v, g, beta, S0


def recurrence(q, k, v, g, beta, S, valid_len=None):
    """The rule as written, a token at a time (NumPy, float64)."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64) for a in (q, k, v, g, beta, S))
    B, T = q.shape[:2]
    out = np.zeros((B, T, H, DV))
    S = S.copy()
    for b in range(B):
        for t in range(T if valid_len is None else int(valid_len[b])):
            for h in range(H):
                a = np.exp(g[b, t, h])
                kt, qt = k[b, t, h], q[b, t, h]
                write = v[b, t, h] - a * (S[b, h].T @ kt)
                S[b, h] = a * S[b, h] + beta[b, t, h] * np.outer(kt, write)
                out[b, t, h] = S[b, h].T @ qt
    return out, S


@pytest.mark.parametrize("T", [1, 5, 16, SUB_CHUNK, 96, 2 * SUB_CHUNK, 200])
def test_chunked_form_is_the_recurrence(T):
    """Shorter than a sub-chunk, one, a ragged tail, several: from a non-zero state."""
    args = _inputs(T, 2, T)
    o, S = gated_delta_chunk(*args)
    want_o, want_S = recurrence(*args)
    np.testing.assert_allclose(o, want_o, **TOL)
    np.testing.assert_allclose(S, want_S, **TOL)


@pytest.mark.parametrize("beta_range", [(0.0, 0.1), (0.9, 1.1), (1.9, 2.0), (0.0, 2.0)])
def test_beta_across_its_range(beta_range):
    """beta near 2 reflects the state along a key (the negative eigenvalue): the
    triangular system is then at its worst conditioned."""
    args = _inputs(7, 1, 2 * SUB_CHUNK, beta_range=beta_range)
    o, S = gated_delta_chunk(*args)
    want_o, want_S = recurrence(*args)
    np.testing.assert_allclose(o, want_o, **TOL)
    np.testing.assert_allclose(S, want_S, **TOL)


@pytest.mark.parametrize("g_scale", [0.01, 3.0, 40.0])
def test_slow_and_fast_decay_neither_overflow_nor_drift(g_scale):
    """A head that forgets within a token (exp(-40 * 64) underflows to zero) and one that barely forgets."""
    args = _inputs(11, 1, 2 * SUB_CHUNK, g_scale=g_scale)
    o, S = gated_delta_chunk(*args)
    want_o, want_S = recurrence(*args)
    assert np.isfinite(o).all() and np.isfinite(S).all()
    np.testing.assert_allclose(o, want_o, **TOL)
    np.testing.assert_allclose(S, want_S, **TOL)


@pytest.mark.parametrize("valid", [[0, 3], [SUB_CHUNK, SUB_CHUNK + 1], [100, 17]])
def test_tokens_beyond_valid_len_leave_the_state(valid):
    """A padded last chunk: the state is the one after the last real token, a row with none keeps S0."""
    T = 100
    args = _inputs(3, 2, T)
    valid_len = jnp.asarray(valid, jnp.int32)
    o, S = gated_delta_chunk(*args, valid_len=valid_len)
    want_o, want_S = recurrence(*args, valid_len=valid)
    np.testing.assert_allclose(S, want_S, **TOL)
    for b, n in enumerate(valid):
        np.testing.assert_allclose(o[b, :n], want_o[b, :n], **TOL)
    assert np.isfinite(o).all()


def test_two_chunks_carry_one_state():
    """A prompt in two chunks through the carried state is the prompt in one."""
    args = _inputs(5, 1, 160)
    q, k, v, g, beta, S0 = args
    whole_o, whole_S = gated_delta_chunk(*args)
    first = [a[:, :96] for a in (q, k, v, g, beta)]
    second = [a[:, 96:] for a in (q, k, v, g, beta)]
    o1, S1 = gated_delta_chunk(*first, S0)
    o2, S2 = gated_delta_chunk(*second, S1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), whole_o, **TOL)
    np.testing.assert_allclose(S2, whole_S, **TOL)


def test_step_is_a_chunk_of_one():
    q, k, v, g, beta, S0 = _inputs(9, 3, 1)
    o, S = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S0)
    chunk_o, chunk_S = gated_delta_chunk(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o, chunk_o[:, 0], **TOL)
    np.testing.assert_allclose(S, chunk_S, **TOL)
    want_o, want_S = recurrence(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o, want_o[:, 0], **TOL)
    np.testing.assert_allclose(S, want_S, **TOL)


def test_step_leaves_the_state_of_a_row_that_is_not_live():
    q, k, v, g, beta, S0 = _inputs(13, 3, 1)
    live = jnp.asarray([True, False, True])
    _, S = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S0, live=live)
    _, moved = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S0)
    np.testing.assert_array_equal(S[1], S0[1])
    np.testing.assert_array_equal(S[0], moved[0])
    np.testing.assert_array_equal(S[2], moved[2])


@pytest.mark.parametrize("C", [8, 16, 32, 64])
def test_unit_lower_inverse(C):
    """Blocks of 16 solved row by row and joined by matmuls: (I + A) X = I."""
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(C), (2, 3, C, C)) * 0.3, -1)
    X = _unit_lower_inverse(A)
    eye = np.eye(C)
    np.testing.assert_allclose(np.asarray(X, np.float64) @ (eye + np.asarray(A, np.float64)), np.broadcast_to(eye, X.shape), atol=5e-5)
    assert np.allclose(np.triu(np.asarray(X), 1), 0.0)
