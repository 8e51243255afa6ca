"""``ops/rows_to_tokens.py`` (PR 58): the kernel that sums a trained experts'
block's sorted rows to their tokens, interpreted here, against the plain
``.at[token].add`` it replaces on a TPU; the two functions ``moe._held_rows``
calls (``gather_rows``, ``sum_rows``) differentiated against the plain form;
and ``routed_experts(rows=)`` with the kernel against itself without it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as attention_ops
from ray_tpu.ops import rows_to_tokens
from ray_tpu.parallel import moe

D = 128


def _sorted_rows(chosen, held: int, *, rows: int, first: int = 0, dtype=jnp.bfloat16, poison: bool = True, seed: int = 0):
    """What ``moe._held_rows`` holds of one piece: ``chosen`` [N, k] (a token's
    experts, distinct; the first ``held`` are held here) -> the piece's rows
    [rows, D] from sorted row ``first`` on (the dead ones NaN and Inf where
    ``poison``), the token of each, where each run ends in the piece, N and the
    rows' float32 weights."""
    rng = np.random.default_rng(seed)
    N, k = chosen.shape
    expert = np.where(chosen.reshape(-1) < held, chosen.reshape(-1), held)
    order = np.pad(np.argsort(expert, kind="stable"), (0, first + rows))
    ends = np.cumsum(np.bincount(expert, minlength=held + 1)[:held])
    at = order[first : first + rows]
    run_ends = np.clip(ends, first, first + rows) - first
    values = rng.standard_normal((rows, D)).astype(np.float32)
    dead = np.arange(rows) >= run_ends[-1]
    if poison:
        values[dead] = np.where(rng.random((int(dead.sum()), D)) < 0.5, np.nan, np.inf)
    token = np.where(dead, 0, at // k)
    return jnp.asarray(values, dtype), jnp.asarray(token, jnp.int32), jnp.asarray(run_ends, jnp.int32), N, jnp.asarray(rng.random(rows), jnp.float32)


def _uniform(N, E, k, seed=0):
    return np.argsort(np.random.default_rng(seed).random((N, E)), axis=1)[:, :k]


def _one_nobody_chose_and_one_everybody_chose():
    """Expert 0: every token's; expert 1: nobody's; two more a token of experts 2 .. 7."""
    return np.concatenate([np.zeros((512, 1), np.int64), _uniform(512, 6, 2, seed=1) + 2], axis=1)


def _a_tile_nobodys_row_lands_on():
    """Tokens 128 .. 383 (a tile of 256 and more) choose among experts 4 .. 15, none of the four held."""
    chosen = _uniform(512, 16, 4, seed=2)
    chosen[128:384] = _uniform(256, 12, 4, seed=2) + 4
    return chosen


CASES = {
    # (i) the cell's structure scaled down: 64 experts, 8 a token, 16 held (a quarter live), the bound's rows
    "the benchmark's structure scaled down": lambda: _sorted_rows(_uniform(512, 64, 8), 16, rows=moe.held_rows(512 * 8, (0, 4))),
    # (ii) a tile's range of one run longer than a window, and an empty run
    "an expert nobody chose and one every token chose": lambda: _sorted_rows(_one_nobody_chose_and_one_everybody_chose(), 4, rows=1280),
    # (iii) is every case's: the dead rows hold NaN and Inf; here nearly every row is dead
    "nearly every row dead": lambda: _sorted_rows(_uniform(256, 64, 2, seed=3), 2, rows=512),
    # (iv) the pieces behind the bound: the piece starts in the middle of a run and ends in the middle of another
    "a piece that starts in the middle of a run": lambda: _sorted_rows(_uniform(512, 16, 4, seed=4), 8, rows=384, first=300),
    "a piece behind the last live row": lambda: _sorted_rows(_uniform(256, 16, 4, seed=5), 4, rows=256, first=512),
    # (v) is every case's but this one's: bfloat16 rows, float32 weights
    "float32 rows": lambda: _sorted_rows(_uniform(256, 16, 4, seed=6), 8, rows=640, dtype=jnp.float32),
    "a tile nobody's row lands on": lambda: _sorted_rows(_a_tile_nobodys_row_lands_on(), 4, rows=512),
}


@pytest.mark.parametrize("weighted", [True, False], ids=["the combine", "the dispatch's gradient"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_scatter_add(case, weighted):
    """The kernel's sum equals ``plain_sum``'s up to the order of a token's few
    float32 additions (exactly, where the result is rounded to bfloat16 from
    the same float32 sum), is finite though the dead rows
    are not, and a token no live row names gets zeros."""
    rows, token, ends, N, weights = CASES[case]()
    weights, dtype = (weights, jnp.float32) if weighted else (None, rows.dtype)
    want = rows_to_tokens.plain_sum(rows, token, ends, N, weights, dtype)
    got = jax.jit(lambda *a: rows_to_tokens.kernel_sum(*a, N, weights, dtype, interpret=True))(rows, token, ends)
    assert got.dtype == want.dtype and got.shape == (N, D) and bool(jnp.isfinite(got).all())
    live = np.asarray(token)[: int(ends[-1])]
    assert np.array_equal(np.asarray(got.astype(jnp.float32))[np.setdiff1d(np.arange(N), live)], np.zeros((N - len(set(live.tolist())), D)))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0, atol=2e-6 if dtype == jnp.float32 else 2e-2)


def test_the_plan_walks_every_live_row_once_and_fits_its_grid():
    """``_plan``'s ranges, window by window, cover each live row exactly once,
    under the tile its token lies in; and its items fit the static grid."""
    rows, token, ends, N, _ = CASES["the benchmark's structure scaled down"]()
    tile, window = rows_to_tokens.token_tile(N), rows_to_tokens._WINDOW
    total, pair, lo, hi, first_window, first_item = (np.asarray(a) for a in rows_to_tokens._plan(token, ends, N, tile))
    assert total[0] <= len(pair) == rows.shape[0] // window + len(ends) * (N // tile)
    seen = np.zeros(rows.shape[0], np.int64)
    for i in range(int(total[0])):
        p = pair[i]
        w = first_window[p] + i - first_item[p]
        at = np.arange(max(lo[p], w * window), min(hi[p], (w + 1) * window))
        assert (np.asarray(token)[at] // tile == p // len(ends)).all()
        seen[at] += 1
    assert (seen[: int(ends[-1])] == 1).all() and (seen[int(ends[-1]) :] == 0).all()
    assert (np.diff(pair[: int(total[0])] // len(ends)) >= 0).all()  # a tile's items one after the other


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_two_functions_gradients_are_the_plain_forms(dtype, monkeypatch):
    """``gather_rows`` and ``sum_rows`` (``ops.attention._on_tpu`` answering yes:
    ``kernel_sums`` takes the kernel, which runs interpreted here) against
    ``jax.grad`` of ``where(live, x[token], 0)`` and of ``plain_sum``: values and
    the gradients in x, in the rows and in the weights; with NaN in the dead
    rows, which must reach no gradient."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    rows, token, ends, N, weights = _sorted_rows(_uniform(256, 16, 4, seed=7), 8, rows=640, dtype=dtype)
    assert rows_to_tokens.kernel_sums(*rows.shape, N)
    key = jax.random.split(jax.random.PRNGKey(0), 2)
    x, mix = jax.random.normal(key[0], (N, D)).astype(dtype), jax.random.normal(key[1], (N, D))
    live = (jnp.arange(rows.shape[0]) < ends[-1])[:, None]
    tol = dict(rtol=0, atol=1e-5) if dtype == jnp.float32 else dict(rtol=0, atol=6e-2)

    def close(got, want):
        assert got.dtype == want.dtype and bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)

    # the dispatch: the rows' cotangent is NaN where the rows are dead, as a grouped matmul's gradient leaves it
    cotangent = jnp.where(live, jax.random.normal(key[1], rows.shape), jnp.nan).astype(dtype)
    got, got_grad = jax.vjp(lambda x: rows_to_tokens.gather_rows(x, token, ends), x)
    want, want_grad = jax.vjp(lambda x: jnp.where(live, x[token], 0), x)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    close(got_grad(cotangent)[0], want_grad(jnp.where(live, cotangent, 0))[0])
    # the combine
    loss = lambda f: lambda rows, weights: jnp.sum(f(rows, weights) * mix)  # noqa: E731
    got = jax.value_and_grad(loss(lambda r, w: rows_to_tokens.sum_rows(r, w, token, ends, N)), argnums=(0, 1))(rows, weights)
    want = jax.value_and_grad(loss(lambda r, w: rows_to_tokens.plain_sum(r, token, ends, N, w)), argnums=(0, 1))(rows, weights)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        close(g, w)


@pytest.mark.parametrize("rows", [384, 128], ids=["the share fits its bound", "pieces behind the bound run"])
def test_a_bounded_block_with_the_kernel_is_the_block_without_it(rows, monkeypatch):
    """``routed_experts(rows=)`` at a width of whole lanes with ``_on_tpu``
    answering yes (the grouped matmuls and both sums are then the kernels,
    interpreted) against the same call on the plain path: the result and the
    gradient in x, the router and the three expert stacks."""
    N, F, E, K, share = 256, 32, 8, 2, (1, 2)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    params = dict(
        gate=jax.random.normal(ks[0], (D, E)) * D**-0.5, wg_e=jax.random.normal(ks[1], (E // 2, D, F)) * D**-0.5,
        wi_e=jax.random.normal(ks[2], (E // 2, D, F)) * D**-0.5, wo_e=jax.random.normal(ks[3], (E // 2, F, D)) * F**-0.5,
    )
    x = jax.random.normal(ks[4], (N, D))

    def run(params, x):
        out, sizes, _, _ = moe.routed_experts(params, x, k=K, share=share, score="softmax", rows=rows)
        return jnp.sum(out * jnp.cos(jnp.arange(D, dtype=jnp.float32))), sizes

    (want, sizes), want_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    assert (int(sizes.sum()) > rows) == (rows == 128)
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    assert moe.experts_run(N * K, E, D, F) == "kernel" and rows_to_tokens.kernel_sums(rows, D, N)
    (got, _), got_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=2e-5, err_msg=jax.tree_util.keystr(path))
