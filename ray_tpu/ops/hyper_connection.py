"""Manifold-constrained hyper-connections: a residual path of ``n`` streams.

A token's state is ``X`` [n, D] (arXiv:2512.24880 over arXiv:2409.19606). A
sub-layer ``F`` does not read ``x`` and add to it; it reads a learned,
input-dependent mixture of the n rows and writes back through a doubly
stochastic n x n matrix:

    x~ = vec(X) in float32;  m = (x~ phi) * rsqrt(mean(x~^2) + norm_eps)
    H_pre  = sigmoid(alpha_0 m[0:n] + b[0:n])
    H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])
    M_0    = exp(clip(alpha_2 mat(m[2n:]) + mat(b[2n:]), clamp))        (n x n, row-major)
    iters times: M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps);   H_res = M
    u = sum_i H_pre[i] X[i];  y = F(norm(u));  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

``mix`` computes ``u`` and the write-back coefficients, ``join`` the last line.
The 2n + n^2 coefficients of a token are float32 from ``x~`` to ``H_res`` (the
product with ``phi`` at ``highest`` precision: a TPU's default rounds a float32
operand to bfloat16); ``u`` and ``X'`` accumulate in float32 and are stored in
the stream's dtype.

Plain ``jax.numpy``, written for the TPU's tiles:

- the stream is ``[..., n * D]``, row i its columns ``i * D : (i + 1) * D``
  (``x~`` as it lies). An axis of n = 4 in front of D would sit on a tile's
  sublanes and pad to 16 in bfloat16: four times the bytes of every read and
  write of the stream;
- ``phi`` is stored ``[2n + n^2, n * D]``: 24 sublanes, the stream's width on
  the lanes. The other way round its 24 columns pad to 128 lanes;
- the coefficients are computed TOKENS-MINOR, ``[2n + n^2, tokens]``, each of
  the n x n entries a vector over the tokens: the Sinkhorn iterations are
  elementwise arithmetic on 16 such vectors (a row sum is three additions),
  which the compiler fuses into one operation, where 40 reductions over a
  ``[tokens, 4, 4]`` array (each 4 x 4 a padded tile of its own) need not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def widen(x, n: int):
    """Embeddings [..., D] -> the stream they start [..., n * D]: each of the n rows the embedding."""
    return jnp.tile(x, (1,) * (x.ndim - 1) + (n,))


def collapse(stream, n: int):
    """The stream [..., n * D] -> what the final norm reads [..., D]: the sum of its rows."""
    return sum(row.astype(F32) for row in _rows(stream, n)).astype(stream.dtype)


def _rows(stream, n: int) -> list:
    D = stream.shape[-1] // n
    return [stream[..., i * D : (i + 1) * D] for i in range(n)]


def sinkhorn(M: list, iters: int, eps: float) -> list:
    """M: n rows of n arrays (entry [i][j] of every token's matrix), positive.
    ``iters`` times rows then columns divided by their sums + eps."""
    n = len(M)
    for _ in range(iters):
        M = [[m / (sum(row) + eps) for m in row] for row in M]
        cols = [sum(M[i][j] for i in range(n)) + eps for j in range(n)]
        M = [[M[i][j] / cols[j] for j in range(n)] for i in range(n)]
    return M


def coefficients(stream, phi, b, alpha, cfg):
    """(H_pre [n], H_post [n], H_res [n][n]) as lists of float32 arrays over
    the stream's tokens (its leading axes flattened: [T])."""
    n = cfg.hc_mult
    x = stream.reshape(-1, stream.shape[-1]).astype(F32)
    m = jnp.einsum("td,kd->kt", x, phi.astype(F32), precision=jax.lax.Precision.HIGHEST)
    m = m * jax.lax.rsqrt(jnp.mean(x * x, axis=-1) + cfg.norm_eps)[None, :]
    alpha, b = alpha.astype(F32), b.astype(F32)
    pre = [jax.nn.sigmoid(alpha[0] * m[i] + b[i]) for i in range(n)]
    post = [2.0 * jax.nn.sigmoid(alpha[1] * m[n + i] + b[n + i]) for i in range(n)]
    lo, hi = cfg.hc_res_clamp
    M = [[jnp.exp(jnp.clip(alpha[2] * m[2 * n + i * n + j] + b[2 * n + i * n + j], lo, hi)) for j in range(n)] for i in range(n)]
    return pre, post, sinkhorn(M, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def mix(stream, phi, b, alpha, cfg):
    """stream [..., n * D] -> (u [..., D], the branch's input before its norm;
    H_post and H_res, what ``join`` writes back through: n and n x n float32
    arrays over the stream's tokens [T], as ``coefficients`` gives them. Stacked
    into one [.., n, n] array between the two they would be sliced apart again.)"""
    pre, post, res = coefficients(stream, phi, b, alpha, cfg)
    u = sum(_beside(h, stream) * row.astype(F32) for h, row in zip(pre, _rows(stream, cfg.hc_mult)))
    return u.astype(stream.dtype), post, res


def _beside(c, stream):
    """A coefficient a token [T] beside the token's values: [..., 1]."""
    return c.reshape(*stream.shape[:-1], 1)


def join(stream, y, post, res):
    """X'[i] = sum_j H_res[i][j] X[j] + H_post[i] y: stream [..., n * D], the
    branch's output y [..., D], ``mix``'s post and res."""
    n = len(post)
    rows, y = [row.astype(F32) for row in _rows(stream, n)], y.astype(F32)
    out = [
        sum(_beside(res[i][j], stream) * rows[j] for j in range(n)) + _beside(post[i], stream) * y
        for i in range(n)
    ]
    return jnp.concatenate(out, axis=-1).astype(stream.dtype)
