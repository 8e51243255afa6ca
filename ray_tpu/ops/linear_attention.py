"""The gated delta rule (Gated DeltaNet; Yang, Kautz, Hatamizadeh 2024), the
recurrence of a linear-attention layer, in the two forms a server needs.

A head keeps a state ``S`` in R^{dk x dv}. A token brings a query and a key
(dk, the key of unit length), a value (dv), a log decay ``g <= 0`` and a write
strength ``beta`` in (0, 2):

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``gated_delta_step`` is that, for one token a row: a decode step. The state is
read twice and written once (``S^T [k, q]`` in one pass, then the update; ``o``
comes from the first pass: ``S_t^T q = exp(g) S^T q + beta (k . q) u``).

``gated_delta_chunk`` is the same recurrence over a prefill chunk carried from
a state and to one, in the chunked (WY / UT transform) form: the chunk is cut
into sub-chunks of ``SUB_CHUNK`` tokens; inside a sub-chunk the tokens' writes
are solved for together, one unit-lower-triangular system a head (``_unit_lower_
inverse``: forward substitution in blocks of ``_SOLVE_BLOCK``, the blocks joined
by matmuls), and only the sub-chunks follow one another, ``T / 64`` steps where
the recurrence takes ``T``. Every decay that is exponentiated is a difference
of cumulative sums inside one sub-chunk taken later minus earlier: never above
one, so nothing overflows however fast a head forgets.

Both keep the state and every sum in float32 (matmuls at ``highest`` precision:
on a TPU a float32 matmul is otherwise one bfloat16 pass). Plain ``jax.numpy``;
a Pallas kernel would read the state once a step where this reads it twice
(PERF.md, ``linear_state_roofline`` / ``linear_scan_roofline``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SUB_CHUNK = 64
_SOLVE_BLOCK = 16
_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST


def gated_delta_step(q, k, v, g, beta, S, live=None):
    """One token a row. q, k [B, H, dk], v [B, H, dv], g, beta [B, H], S [B, H,
    dk, dv] float32 -> (o [B, H, dv] float32, S). ``live`` [B] bool (optional):
    a row that is not leaves its state as it was."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    kq = jnp.stack([k, q], axis=2)  # [B, H, 2, dk]
    Sk, Sq = jnp.moveaxis(jnp.sum(S[:, :, None] * kq[..., None], axis=3), 2, 0)  # S^T k, S^T q: [B, H, dv]
    alpha = jnp.exp(g)[..., None]
    u = beta[..., None] * (v - alpha * Sk)
    o = alpha * Sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    S_new = alpha[..., None] * S + k[..., None] * u[:, :, None]
    if live is not None:
        S_new = jnp.where(live[:, None, None, None], S_new, S)
    return o, S_new


def _forward_substitution(A):
    """(I + A)^-1 - I for strictly lower triangular A [..., c, c], row by row:
    row i of the result is ``-A[i] - sum_{j < i} A[i, j] N[j]``."""
    c = A.shape[-1]

    def row(i, N):
        mine = lax.dynamic_index_in_dim(N, i, axis=-2, keepdims=False)  # still -A[i]: zero from column i on
        new = mine + jnp.einsum("...j,...jk->...k", mine, N, precision=_EXACT)
        return lax.dynamic_update_index_in_dim(N, new, i, axis=-2)

    return lax.fori_loop(1, c, row, -A)


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower triangular A [..., C, C], float32: halved
    down to blocks of ``_SOLVE_BLOCK`` (the two halves side by side, so each
    level is one call), those solved row by row, and two solved halves joined
    as ``[[X1, 0], [-X2 A21 X1, X2]]``."""
    C = A.shape[-1]
    if C <= _SOLVE_BLOCK or C % 2:
        return _forward_substitution(A) + jnp.eye(C, dtype=A.dtype)
    h = C // 2
    X = _unit_lower_inverse(jnp.stack([A[..., :h, :h], A[..., h:, h:]]))
    X1, X2 = X[0], X[1]
    X21 = -jnp.matmul(jnp.matmul(X2, A[..., h:, :h], precision=_EXACT), X1, precision=_EXACT)
    top = jnp.concatenate([X1, jnp.zeros_like(X1)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([X21, X2], axis=-1)], axis=-2)


def gated_delta_chunk(q, k, v, g, beta, S0, valid_len=None):
    """A chunk of T tokens a row, carried from ``S0`` to the state after the
    row's last valid token. q, k [B, T, H, dk], v [B, T, H, dv], g, beta [B, T,
    H], S0 [B, H, dk, dv] float32, ``valid_len`` [B] int32 (optional: all T) ->
    (o [B, T, H, dv] float32, S_T). A token at or beyond ``valid_len`` neither
    decays the state nor writes to it (its own output is not meaningful)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(SUB_CHUNK, T)
    pad = -T % C
    g, beta = g.astype(_F32), beta.astype(_F32)
    if valid_len is not None:
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None])[..., None]
        g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    n = (T + pad) // C

    def heads_first(a):  # [B, T, H, ...] -> [B, H, n, C, ...], the tail beyond T as tokens that do nothing
        a = jnp.pad(a.astype(_F32), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(B, H, n, C, *a.shape[3:])

    q, k, v, g, beta = (heads_first(a) for a in (q, k, v, g, beta))
    decay = jnp.cumsum(g, axis=-1)  # [B, H, n, C], within the sub-chunk
    # since[i, j] = exp(decay_i - decay_j) for j <= i: what row j's write has decayed to by row i.
    lower = jnp.tril(jnp.ones((C, C), bool))
    since = jnp.where(lower, jnp.exp(jnp.where(lower, decay[..., :, None] - decay[..., None, :], 0.0)), 0.0)
    k_beta = k * beta[..., None]
    # Row i's write is beta_i k_i (v_i - [what the state and the rows before i hold along k_i])^T: the
    # rows of one sub-chunk solve (I + tril(beta k k^T since, -1)) W = [beta v, beta k exp(decay)].
    A = jnp.einsum("bhnid,bhnjd->bhnij", k_beta, k, precision=_EXACT) * since
    X = _unit_lower_inverse(jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), A, 0.0))
    w_v = jnp.matmul(X, v * beta[..., None], precision=_EXACT)  # [B, H, n, C, dv]
    w_k = jnp.matmul(X, k_beta * jnp.exp(decay)[..., None], precision=_EXACT)  # [B, H, n, C, dk]
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, precision=_EXACT) * since
    q_in = q * jnp.exp(decay)[..., None]  # against the state the sub-chunk starts from
    to_end = jnp.exp(decay[..., -1:] - decay)  # what each row's write has decayed to by the sub-chunk's end
    k_out = k * to_end[..., None]
    whole = jnp.exp(decay[..., -1])  # [B, H, n]

    def sub_chunk(S, xs):
        w_v, w_k, qk, q_in, k_out, whole = xs
        writes = w_v - jnp.matmul(w_k, S, precision=_EXACT)  # [B, H, C, dv]
        o = jnp.matmul(q_in, S, precision=_EXACT) + jnp.matmul(qk, writes, precision=_EXACT)
        S = whole[..., None, None] * S + jnp.einsum("bhck,bhcv->bhkv", k_out, writes, precision=_EXACT)
        return S, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (w_v, w_k, qk, q_in, k_out, whole))
    S, o = lax.scan(sub_chunk, S0.astype(_F32), xs)  # o [n, B, H, C, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, n * C, dv)[:, :, :T]
    return jnp.moveaxis(o, 1, 2), S
