"""The sum of sorted rows to their tokens, in one pass over the rows.

A trained experts' block (``parallel/moe._held_rows``) holds its assignments
sorted by expert: ``rows [R, D]``, the token of each ``[R]``, and where each
expert's run of rows ends. It sums them to their tokens twice a layer: forward
as the combine (each row times its routing weight), backward as the gradient
of the dispatch's gather. As ``zeros.at[token].add(rows)`` that is an XLA
scatter-add with duplicate indices, which the v5e runs a row at a time: 2.9 ms
for 40,960 rows of 2304 into 16,384 tokens, and as long again for the float32
temporaries round it, where the bytes take 0.4 (my chip run, PR 50).

What the scatter-add cannot use: the sort is stable over assignment ids
``token * k + slot`` and a token chooses an expert at most once, so inside a
run the tokens are STRICTLY ASCENDING. The rows of a tile of tokens are then,
for each run, one contiguous range of it, and where the ranges lie is a count
of a few hundred scalars (``_plan``). The kernel walks (token tile, window of
rows) pairs, a tile's pairs one after the other:

- a window is ``_WINDOW`` sorted rows, aligned, brought in once a pair by the
  pipeline; the rows outside the pair's range are selected to ZERO in VMEM
  before any product: rows of another run or another tile, and the dead rows
  behind the last run, where a grouped matmul leaves whatever was there (not
  even finite at some widths: a 0/1 matrix times NaN is NaN);
- the window is summed to its tokens by a 0/1 selection matrix ``[tile,
  window]`` on the MXU, accumulated in float32: exact, since at most one row
  of a range lands on a token. The row's weight (float32) is gathered by the
  same selection on the VPU and multiplies the token's product;
- a tile's float32 accumulator stays in VMEM over its pairs and is written
  once, rounded once, in the result's dtype. A tile no row lands on gets one
  pair with an empty range, and zeros.

A token's sum is taken in run order: it may differ from the scatter-add's by
the order of at most ``k`` float32 additions, and by nothing else.

On a v5e at Mellum's share (40,960 sorted rows of 2304 in bfloat16, 32,813 of
them live in 16 runs, 16,384 tokens; my chip runs, PR 58; ms a call, the mean
of 20 back to back with the plan's ~5 us; under the profiler the kernel alone
reads 1.67 / 1.51 at 512 x 128), by tokens a tile x rows a window:

    sum                               scatter-add   512x128  256x128  1024x128  512x256
    weighted -> float32 (the combine)     5.51       1.73     1.81     2.04      2.48
    plain -> bfloat16 (the gradient)      5.86       1.57     1.65     1.88      2.33

765 of the grid's 832 steps are items at 512 x 128 (638 of 672 at 512 x 256,
1,274 of 1,344 at 256 x 128): a step is one ``[512, 128] x [128, 2304]``
selection product, ~1.5 us of the four matrix units, and ~0.4 us of grid
step; the rows' 450 MB and the result's 75-151 MB take 0.65-0.75 ms at the
HBM's rate. Results equal the scatter-add's bit for bit on that input.

``gather_rows`` and ``sum_rows`` are the dispatch and the combine as the block
uses them, each the other's transpose, with hand-written gradients. On a TPU,
where the model's width fills whole lanes, the sums are the kernel; elsewhere
(every CPU run, the toy widths) they are the plain ``.at[token].add``, which
is also the kernel's reference in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as _attention_ops
from ray_tpu.ops.attention import _on_tpu

# Sorted rows a window: one contraction of the matrix unit.
_WINDOW = 128
# Tokens a tile, the largest that divides the tokens: a tile's accumulator is [tile, D] float32 in VMEM.
_TOKEN_TILES = (512, 256, 128)
_LANES = 128


def token_tile(n_tokens: int) -> int | None:
    """Tokens a tile of a call over ``n_tokens`` tokens, None where no tile divides them."""
    return next((tile for tile in _TOKEN_TILES if n_tokens % tile == 0), None)


def kernel_sums(rows: int, width: int, n_tokens: int) -> bool:
    """Whether the sums of ``rows`` sorted rows of ``width`` to ``n_tokens``
    tokens are the kernel's: on a TPU, whole lanes, whole windows and tiles.
    (``ops.attention``'s predicate, as ``moe.experts_run`` asks it: a test that
    patches it there gets the kernel here interpreted.)"""
    return _attention_ops._on_tpu() and width % _LANES == 0 and rows % _WINDOW == 0 and token_tile(n_tokens) is not None


def _live(ends, rows: int):
    """[rows, 1] bool: the rows of some run (the runs lie one behind the other from row 0 on)."""
    return (jnp.arange(rows, dtype=jnp.int32) < ends[-1])[:, None]


def plain_sum(rows, token, ends, n_tokens: int, weights=None, dtype=jnp.float32):
    """The sum as a scatter-add: rows [R, D] of which those before ``ends[-1]``
    are live, token [R] int32, weights [R] float32 or None -> [n_tokens, D] in
    ``dtype``, products and sums in float32. What the kernel replaces, what
    every CPU run keeps, and the kernel's reference."""
    live = _live(ends, rows.shape[0])
    # Selected BEFORE the product: what a grouped matmul leaves in a row of no group may not be finite, and the
    # product's gradient in the weight is the cotangent TIMES that row, where 0 x NaN is NaN.
    live_rows = jnp.where(live, rows, 0).astype(jnp.float32)
    if weights is not None:
        live_rows = live_rows * weights[:, None]
    out = jnp.zeros((n_tokens, rows.shape[1]), jnp.float32)
    return out.at[jnp.where(live[:, 0], token, 0)].add(live_rows).astype(dtype)


def _plan(token, ends, n_tokens: int, tile: int):
    """The kernel's walk, as scalars: for each (token tile, run) PAIR, tile by
    tile, the range of sorted rows ``[lo, hi)`` of the run whose tokens lie in
    the tile (one range: a run's tokens ascend), the first window it touches
    and the first ITEM of the pair (an item is one (pair, window): one grid
    step), and for each item its pair. By comparisons and sums over the rows,
    no gather and no scatter: a gather of scalars costs the v5e ~10 ns each.

    Items at most: a run's items are its window and tile changes + 1, window
    changes in all under R / window, tile changes a run under the tiles."""
    R, G, tiles = token.shape[0], ends.shape[0], n_tokens // tile
    at = jnp.arange(R, dtype=jnp.int32)[:, None]
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    # Rows of run g before tile j's first token, counted on the matrix unit: 0/1 operands, float32 sums, exact.
    of_run = ((at >= starts[None, :]) & (at < ends[None, :])).astype(jnp.bfloat16)  # [R, G]; a dead row is of none
    below = (token[:, None] < jnp.arange(tiles + 1, dtype=jnp.int32)[None, :] * tile).astype(jnp.bfloat16)  # [R, tiles + 1]
    before = starts[:, None] + jnp.einsum("rg,rj->gj", of_run, below, preferred_element_type=jnp.float32).astype(jnp.int32)
    lo, hi = before[:, :-1].T, before[:, 1:].T  # [tiles, G]
    windows = jnp.where(hi > lo, (hi - 1) // _WINDOW - lo // _WINDOW + 1, 0)
    # A tile no row lands on is still written: one item of its first pair, whose range is empty.
    nobody = jnp.sum(windows, axis=1, keepdims=True) == 0
    windows = (windows + (nobody & (jnp.arange(G) == 0)[None, :])).reshape(-1)
    item_end = jnp.cumsum(windows, dtype=jnp.int32)
    items = R // _WINDOW + G * tiles
    pair = jnp.sum(item_end[None, :] <= jnp.arange(items, dtype=jnp.int32)[:, None], axis=1, dtype=jnp.int32)
    first_window = jnp.minimum(lo.reshape(-1) // _WINDOW, R // _WINDOW - 1)
    return item_end[-1:], pair, lo.reshape(-1), hi.reshape(-1), first_window, item_end - windows


def _strip(width: int) -> int:
    """Columns a product takes at a time: the widest of whole lanes up to 512 that divides the width."""
    return max(t for t in range(_LANES, 4 * _LANES + 1, _LANES) if width % t == 0)


def _item(i, total_ref, pair_ref, lo_ref, hi_ref, first_window_ref, first_item_ref, *, windows: int):
    """Grid step ``i``'s (pair, window of rows) by ``_plan``'s scalars. The steps behind the last item stay where it
    was: nothing moves."""
    i = jnp.minimum(i, total_ref[0] - 1)
    pair = pair_ref[i]
    return pair, jnp.minimum(first_window_ref[pair] + i - first_item_ref[pair], windows - 1)


def _walk(*refs, runs: int, windows: int, weighted: bool):
    from jax import lax
    from jax.experimental import pallas as pl

    plan, (token_ref, *refs) = refs[:6], refs[6:]
    total_ref, pair_ref, lo_ref, hi_ref = plan[:4]
    weights_ref, rows_ref, out_ref, acc_ref = refs if weighted else (None, *refs)
    tile, width = acc_ref.shape
    i, total = pl.program_id(0), total_ref[0]

    def tile_of(i):
        return pair_ref[jnp.clip(i, 0, total - 1)] // runs

    @pl.when(i < total)
    def _():
        pair, window = _item(i, *plan, windows=windows)
        lo, hi = lo_ref[pair], hi_ref[pair]

        @pl.when((i == 0) | (tile_of(i - 1) != tile_of(i)))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        row = window * _WINDOW + lax.broadcasted_iota(jnp.int32, (1, _WINDOW), 1)
        token = jnp.where((row >= lo) & (row < hi), token_ref[...], -1)  # [1, window]
        lands = token == tile_of(i) * tile + lax.broadcasted_iota(jnp.int32, (tile, _WINDOW), 0)  # [tile, window]
        selection = jnp.where(lands, 1.0, 0.0).astype(rows_ref.dtype)
        row = window * _WINDOW + lax.broadcasted_iota(jnp.int32, (_WINDOW, 1), 0)
        inside = (row >= lo) & (row < hi)  # [window, 1]
        weight = jnp.sum(jnp.where(lands, weights_ref[...], 0.0), axis=1, keepdims=True) if weighted else None  # [tile, 1]
        exact = {"precision": lax.Precision.HIGHEST} if rows_ref.dtype == jnp.float32 else {}
        strip = _strip(width)
        for c in range(0, width, strip):
            rows = rows_ref[:, c : c + strip]
            landed = jnp.dot(selection, jnp.where(inside, rows, jnp.zeros_like(rows)), preferred_element_type=jnp.float32, **exact)
            acc_ref[:, c : c + strip] += landed * weight if weighted else landed

        @pl.when((i == total - 1) | (tile_of(i + 1) != tile_of(i)))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def kernel_sum(rows, token, ends, n_tokens: int, weights=None, dtype=jnp.float32, interpret: bool | None = None):
    """``plain_sum`` by the kernel (the module's docstring), for runs whose
    tokens ascend. ``interpret`` None: compiled on a TPU, interpreted elsewhere
    (tests)."""
    return _call(rows, token, ends, weights, n_tokens=n_tokens, dtype=jnp.dtype(dtype), interpret=not _on_tpu() if interpret is None else interpret)


# Under ``jit``: a step calls the kernel from every layer, forward and backward, in the cond and out of it, and traces
# and lowers it once a signature (sixteen lowerings made a Mellum step's trace and lowering 8.7 s where the parent's
# takes 6.9, twice a run; so 7.6; my chip runs, PR 58).
@functools.partial(jax.jit, static_argnames=("n_tokens", "dtype", "interpret"))
def _call(rows, token, ends, weights, *, n_tokens: int, dtype, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (R, D), G, tile = rows.shape, ends.shape[0], token_tile(n_tokens)
    windows = R // _WINDOW
    plan = _plan(token, ends, n_tokens, tile)

    item = functools.partial(_item, windows=windows)
    by_lane = pl.BlockSpec((None, 1, _WINDOW), lambda i, *plan: (item(i, *plan)[1], 0, 0))
    by_lanes = [token.reshape(windows, 1, _WINDOW)] + ([] if weights is None else [weights.astype(jnp.float32).reshape(windows, 1, _WINDOW)])
    return pl.pallas_call(
        functools.partial(_walk, runs=G, windows=windows, weighted=weights is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            in_specs=[*[by_lane] * len(by_lanes), pl.BlockSpec((_WINDOW, D), lambda i, *plan: (item(i, *plan)[1], 0))],
            out_specs=pl.BlockSpec((tile, D), lambda i, *plan: (item(i, *plan)[0] // G, 0)),
            grid=(plan[1].shape[0],),
            scratch_shapes=(pltpu.VMEM((tile, D), jnp.float32),),
        ),
        # A tile's pairs in order: its accumulator passes from one to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two windows of rows, two tiles of the result and the accumulator, and room for a strip's products
            vmem_limit_bytes=2 * _WINDOW * D * rows.dtype.itemsize + tile * D * (2 * dtype.itemsize + 4) + (16 << 20),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tokens, D), dtype),
        interpret=interpret,
        name="rows_to_tokens",
    )(*plan, *by_lanes, rows)


def _sum(rows, token, ends, n_tokens, weights, dtype):
    which = kernel_sum if kernel_sums(*rows.shape, n_tokens) else plain_sum
    return which(rows, token, ends, n_tokens, weights, dtype)


@jax.custom_vjp
def gather_rows(x, token, ends):
    """The dispatch: x [N, D], token [R] int32, ends [G] int32 -> the sorted
    rows [R, D], ``x[token]`` where the row is of some run and zero behind the
    last. XLA's gather forward; its gradient is the sum of the rows' cotangents
    to their tokens, in float32, rounded once to x's dtype."""
    return jnp.where(_live(ends, token.shape[0]), x[token], 0)


def _gather_rows_forward(x, token, ends):
    return gather_rows.fun(x, token, ends), (token, ends, jnp.zeros((x.shape[0], 0), x.dtype))


def _gather_rows_backward(residuals, cotangent):
    token, ends, like = residuals  # ``like``: x's tokens and dtype, no bytes
    return _sum(cotangent, token, ends, like.shape[0], None, like.dtype), None, None


gather_rows.defvjp(_gather_rows_forward, _gather_rows_backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def sum_rows(rows, weights, token, ends, n_tokens: int):
    """The combine: the sorted rows [R, D] (those behind the last run hold
    anything), each times its weight [R] float32, summed to their tokens:
    [n_tokens, D] float32. Its gradient is the scatter-add's: the tokens'
    cotangents gathered to the rows, times the weight for the rows, times the
    row summed over its width for the weights."""
    return _sum(rows, token, ends, n_tokens, weights, jnp.float32)


def _sum_rows_forward(rows, weights, token, ends, n_tokens):
    return sum_rows.fun(rows, weights, token, ends, n_tokens), (rows, weights, token, ends)


def _sum_rows_backward(n_tokens, residuals, cotangent):
    rows, weights, token, ends = residuals
    _, transpose = jax.vjp(lambda rows, weights: plain_sum(rows, token, ends, n_tokens, weights), rows, weights)
    return (*transpose(cotangent), None, None)


sum_rows.defvjp(_sum_rows_forward, _sum_rows_backward)
