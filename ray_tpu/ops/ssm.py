"""The Mamba-2 recurrence (state-space duality; Dao and Gu 2024), the mixer of
a state-space block, in the two forms a server needs.

A head keeps a state ``S`` in R^{P x N} (P channels, each a state N wide). A
token brings the head's input ``x`` (P), a time step ``dt > 0``, and the
group's ``B`` and ``C`` (N each; the heads of a group share them); the head has
a decay rate ``A < 0`` and a skip ``D``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

``mamba2_step`` is that, for one token a row: a decode step. The state is read
once and written once.

``mamba2_chunk`` is the same recurrence over a prefill chunk carried from a
state and to one, in the chunked (SSD) form: the chunk is cut into sub-chunks
of ``SUB_CHUNK`` tokens; inside a sub-chunk every token reads every earlier
one through one masked ``[SUB_CHUNK, SUB_CHUNK]`` product a head, ``(C_i . B_j)
exp(sum of dt A over j+1..i) dt_j``, and only the sub-chunks follow one
another through the carried state, ``T / 128`` steps where the recurrence
takes ``T``. Every decay that is exponentiated is a difference of cumulative
sums inside one sub-chunk taken later minus earlier: never above one, so
nothing overflows however fast a head forgets.

Both keep the state and every sum in float32 (matmuls at ``highest``
precision: on a TPU a float32 matmul is otherwise one bfloat16 pass). Plain
``jax.numpy``; PERF.md (``linear_state_roofline`` / ``linear_scan_roofline`` of
the cell that serves such blocks) says what a Pallas kernel would be worth.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

SUB_CHUNK = 128
_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST


def mamba2_step(x, dt, A, Bm, Cm, D, S, live=None):
    """One token a row. x [B, H, P], dt [B, H], A, D [H], Bm, Cm [B, G, N]
    (head h reads group ``h // (H / G)``), S [B, H, P, N] float32 -> (y [B, H,
    P] float32, S). ``live`` [B] bool (optional): a row that is not leaves its
    state as it was."""
    B, H, P = x.shape
    G = Bm.shape[1]
    x, dt, Bm, Cm = (a.astype(_F32) for a in (x, dt, Bm, Cm))
    by_head = lambda a: jnp.repeat(a, H // G, axis=1)  # noqa: E731  [B, G, N] -> [B, H, N]
    decay = jnp.exp(dt * A.astype(_F32))[..., None, None]
    S_new = decay * S + (dt[..., None] * x)[..., None] * by_head(Bm)[:, :, None, :]
    y = jnp.sum(S_new * by_head(Cm)[:, :, None, :], axis=-1) + D.astype(_F32)[:, None] * x
    if live is not None:
        S_new = jnp.where(live[:, None, None, None], S_new, S)
    return y, S_new


def mamba2_chunk(x, dt, A, Bm, Cm, D, S0, valid_len=None):
    """A chunk of T tokens a row, carried from ``S0`` to the state after the
    row's last valid token. x [B, T, H, P], dt [B, T, H], A, D [H], Bm, Cm [B,
    T, G, N], S0 [B, H, P, N] float32, ``valid_len`` [B] int32 (optional: all T)
    -> (y [B, T, H, P] float32, S_T). A token at or beyond ``valid_len`` neither
    decays the state nor writes to it (its own output is not meaningful)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G  # heads a group
    C = min(SUB_CHUNK, T)
    pad = -T % C
    n = (T + pad) // C
    dt = dt.astype(_F32)
    if valid_len is not None:
        dt = jnp.where(jnp.arange(T, dtype=jnp.int32)[None, :, None] < valid_len[:, None, None], dt, 0.0)

    def sub_chunks(a, *tail):  # [B, T, ...] -> [B, n, C, *tail], the tokens beyond T doing nothing (dt = 0)
        a = jnp.pad(a.astype(_F32), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return a.reshape(B, n, C, *tail)

    x, dt, Bm, Cm = sub_chunks(x, G, R, P), sub_chunks(dt, G, R), sub_chunks(Bm, G, N), sub_chunks(Cm, G, N)
    decay = jnp.cumsum(dt * A.astype(_F32).reshape(G, R), axis=2)  # [B, n, C, G, R], within the sub-chunk, <= 0
    # since[i, j] = exp(decay_i - decay_j) for j <= i: what token j's write has decayed to by token i.
    lower = jnp.tril(jnp.ones((C, C), bool))[:, :, None, None]
    since = decay[:, :, :, None] - decay[:, :, None, :]  # [B, n, C, C, G, R]
    since = jnp.where(lower, jnp.exp(jnp.where(lower, since, 0.0)), 0.0)
    cb = jnp.einsum("bnigs,bnjgs->bnijg", Cm, Bm, precision=_EXACT)  # a group's, shared by its heads
    reads = cb[..., None] * since * dt[:, :, None]  # [B, n, C, C, G, R]: token i's weight on token j's input
    y = jnp.einsum("bnijgr,bnjgrp->bnigrp", reads, x, precision=_EXACT)
    to_end = jnp.exp(decay[:, :, -1:] - decay)  # what each token's write has decayed to by the sub-chunk's end
    writes = x * (dt * to_end)[..., None]  # [B, n, C, G, R, P]
    from_start = jnp.exp(decay)  # against the state the sub-chunk starts from
    whole = jnp.exp(decay[:, :, -1])  # [B, n, G, R]

    def sub_chunk(S, xs):  # S [B, G, R, P, N]
        writes, Bm, Cm, from_start, whole = xs
        carried = jnp.einsum("bcgs,bgrps->bcgrp", Cm, S, precision=_EXACT) * from_start[..., None]
        S = whole[..., None, None] * S + jnp.einsum("bcgrp,bcgs->bgrps", writes, Bm, precision=_EXACT)
        return S, carried

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (writes, Bm, Cm, from_start, whole))
    S, carried = lax.scan(sub_chunk, S0.astype(_F32).reshape(B, G, R, P, N), xs)  # carried [n, B, C, G, R, P]
    y = y + jnp.moveaxis(carried, 0, 1) + D.astype(_F32).reshape(G, R)[:, :, None] * x
    return y.reshape(B, n * C, H, P)[:, :T], S.reshape(B, H, P, N)
