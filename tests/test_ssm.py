"""ops/ssm.py: the chunked (SSD) form and the one-token step of the Mamba-2
recurrence against the recurrence itself, a token at a time in NumPy float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.ssm import SUB_CHUNK, mamba2_chunk, mamba2_step

H, P, G, N = 8, 16, 2, 32


def _inputs(T, B=2, seed=0, dt_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0) * dt_scale
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    D = jax.random.normal(ks[5], (H,))
    S0 = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, S0


def _recurrence(x, dt, A, Bm, Cm, D, S0, valid_len=None):
    """A token at a time, as the definition reads; the states after every token too."""
    B, T = x.shape[:2]
    R = H // G
    S = np.asarray(S0, np.float64)
    x, dt, A, Bm, Cm, D = (np.asarray(a, np.float64) for a in (x, dt, A, Bm, Cm, D))
    ys, states = np.zeros((B, T, H, P)), []
    for t in range(T):
        for b in range(B):
            if valid_len is not None and t >= int(valid_len[b]):
                continue
            for h in range(H):
                g = h // R
                S[b, h] = np.exp(dt[b, t, h] * A[h]) * S[b, h] + dt[b, t, h] * np.outer(x[b, t, h], Bm[b, t, g])
                ys[b, t, h] = S[b, h] @ Cm[b, t, g] + D[h] * x[b, t, h]
        states.append(S.copy())
    return ys, S, states


@pytest.mark.parametrize("T", [1, 7, SUB_CHUNK, 200, 2 * SUB_CHUNK + 44])
def test_the_chunked_form_is_the_recurrence_from_a_carried_state(T):
    args = _inputs(T)
    y, S = jax.jit(mamba2_chunk)(*args)
    y_ref, S_ref, _ = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S), S_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("valid", [(0, 5), (130, 200), (128, 129)])
def test_tokens_at_or_beyond_valid_len_move_nothing(valid):
    T = 200
    args = _inputs(T, seed=1)
    valid_len = jnp.asarray(valid, jnp.int32)
    y, S = jax.jit(mamba2_chunk)(*args, valid_len)
    y_ref, S_ref, _ = _recurrence(*args, valid_len=valid)
    np.testing.assert_allclose(np.asarray(S), S_ref, rtol=2e-4, atol=2e-4)
    for b, n in enumerate(valid):
        np.testing.assert_allclose(np.asarray(y)[b, :n], y_ref[b, :n], rtol=2e-4, atol=2e-4)
    if valid[0] == 0:  # a row with no real token keeps the state it came with, bit for bit
        np.testing.assert_array_equal(np.asarray(S)[0], np.asarray(args[-1])[0])


def test_two_chunks_carry_one_state():
    T = 300
    x, dt, A, Bm, Cm, D, S0 = _inputs(T, seed=2)
    whole_y, whole_S = mamba2_chunk(x, dt, A, Bm, Cm, D, S0)
    cut = 172
    y1, S1 = mamba2_chunk(x[:, :cut], dt[:, :cut], A, Bm[:, :cut], Cm[:, :cut], D, S0)
    y2, S2 = mamba2_chunk(x[:, cut:], dt[:, cut:], A, Bm[:, cut:], Cm[:, cut:], D, S1)
    np.testing.assert_allclose(np.concatenate([y1, y2], axis=1), np.asarray(whole_y), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(whole_S), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dt_scale", [1e-3, 1.0, 40.0])
def test_slow_and_fast_decay_neither_overflow_nor_drift(dt_scale):
    args = _inputs(260, seed=3, dt_scale=dt_scale)
    y, S = mamba2_chunk(*args)
    y_ref, S_ref, _ = _recurrence(*args)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(S)).all()
    scale = max(1.0, float(np.abs(y_ref).max()))
    np.testing.assert_allclose(np.asarray(y) / scale, y_ref / scale, atol=3e-4)
    np.testing.assert_allclose(np.asarray(S), S_ref, rtol=3e-4, atol=3e-4 * max(1.0, float(np.abs(S_ref).max())))


def test_n_steps_are_the_recurrence():
    T = 9
    x, dt, A, Bm, Cm, D, S0 = _inputs(T, seed=4)
    y_ref, S_ref, _ = _recurrence(x, dt, A, Bm, Cm, D, S0)
    step = jax.jit(mamba2_step)
    S = S0
    for t in range(T):
        y, S = step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, S)
        np.testing.assert_allclose(np.asarray(y), y_ref[:, t], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S), S_ref, rtol=2e-4, atol=2e-4)


def test_a_step_leaves_the_state_of_a_row_that_is_not_live():
    x, dt, A, Bm, Cm, D, S0 = _inputs(1, seed=5)
    _, S = mamba2_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, S0, live=jnp.asarray([True, False]))
    assert not np.array_equal(np.asarray(S)[0], np.asarray(S0)[0])
    np.testing.assert_array_equal(np.asarray(S)[1], np.asarray(S0)[1])
