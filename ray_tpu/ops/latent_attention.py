"""Attention over a paged LATENT pool, read where it lies: a decode row's one
query (``paged_latent_attention``) and a prefill chunk's (``paged_latent_chunk_attention``).

One query a slot against the slot's cached rows (MLA in absorbed form: a row
is the normed latent followed by the rotary key, ``generate._project_latent``;
every head scores and sums over the SAME rows). The XLA path gathers each
slot's whole block table into a ``[B, n_max * Bs, W]`` view, a layer at a
time, and reads that copy twice more; this kernel walks the table instead
(the walk itself, ``ops/paged_attention.py::_walk``, is shared with the kernel
over a pool of keys and values; this module hands it one leaf and the two
products of the absorbed form):

- the pool leaf ``[L, N, Bs, W]`` stays in HBM whole and is never sliced: the
  layer's index is an operand, and a block is one contiguous ``[Bs, W]``
  region that an async copy brings into VMEM;
- the block tables and the lengths are scalar-prefetch operands, so the
  copies' addresses are computed on the scalar core (the tables whole in
  SMEM: 32 slots x 256 blocks are 32 KB; a deployment whose ``B * n_max``
  words do not fit is refused by the compiler as the engine is built);
- grid = (slots,), in order; inside, a loop over compute steps of ``_PAGES``
  blocks, double-buffered: while a step's scores and weighted sum run, the
  next step's blocks (or the next slot's first) are in flight. A slot stops
  at ``ceil(length / Bs)`` blocks: its last, shorter step copies those it
  holds and no more (what the buffer keeps behind them is an earlier step's,
  or the zeros it starts from: finite, and masked), and a slot of length 0
  copies nothing. A whole step's copies are unrolled, a short step's looped
  (``each_block``);
- online softmax with float32 running maximum, sum and accumulator as the
  loop's carries (``ops/attention.py``'s way); the probabilities go to the
  second product in the pool's dtype, as ``generate._latent_attention``'s do.

The result is ``[B, H, 1, W]`` in the queries' dtype, the array
``_latent_attention``'s ``bhqk,bkr->bhqr`` product returns: the latent's
columns are cut from it and carried through W_uv outside, in XLA.

A prefill chunk (PR 48) is the same walk with the two axes it lacked. The
chunk's rows are written to the pool first; its queries ``[B, T, H, W]`` are
laid out by head (``[B, H, T, W]``, the result's layout) and cut into TILES of
``Tq`` consecutive queries x all ``H`` heads: every head scores the same
cached rows, so a compute step is ONE ``[H * Tq, W] x [W, keys]`` product and
one ``[H * Tq, keys] x [keys, W]``, the matrix unit's shape, and the grid is
(row, tile) in order, the buffers and the copies in flight handed from a tile
to the next as from a slot to the next. A tile walks the row's table from
block 0 to the block of ITS OWN last query's position and no further, so the
chunk's triangle is skipped, not masked (``walk_call``'s ``lengths`` are a
tile's, ``starts`` say where its first query stands, and the mask inside a
step is one ``[Tq, keys]`` comparison for all heads); a padded last chunk's
queries past the row's real rows see every real row, a tile of nothing but
padding and an inactive row (a table that starts at the null block) walk
nothing and get zeros. The view this replaces (``_paged_view`` +
``_cache_mask`` + ``_latent_attention``) gathers the row's WHOLE table a layer
and scores every query against all of it in float32, whatever the row holds.

On a v5e (my chip runs, PR 48; ms for one 512-token chunk's attention, the
layers one after another; bfloat16, blocks of 16 rows of 640, the weighted
sum over the latent's 512 columns), by the context the row already holds.
Xing4.0-29B-A4B's 6 layers, 32 heads, a table of 768 blocks:

    rows a tile x blocks a step    0      2048    4990    11500
    the view                     24.96   24.92   25.02   24.92
    512 x 16                      2.15    6.66   12.86   26.62
    512 x 32                      2.32    5.99   11.13   22.61
    1024 x 16                     2.26    6.16   11.86   24.51
    1024 x 32                     2.36    5.76   10.72   21.55
    1024 x 64                     3.12    6.34   10.75   20.89
    2048 x 32                     2.37    5.81   10.70   21.35
    2048 x 64                     3.24    6.37   10.89   20.32
    1024 x 32, sum over 512       2.30    5.52   10.00   20.16
    2048 x 64, sum over 512       3.15    5.89    9.83   18.81

GLM-4.7-Flash's 8 layers, 20 heads, a table of 256 blocks, 384 real tokens at
context 0: the view 8.08; 640 rows x 16 blocks 2.09, x 32 1.97, x 64 2.54;
320 x 32 2.13; 1280 x 32 2.08. 1024 rows (32 queries x 32 heads; 32 x 20 = 640
for GLM) x 32 blocks it is: 62-70 % of the bf16 peak over the pairs a causal
chunk has at the padded width (66-73 % with the second product over the
latent's columns alone, ``value_width``), level from 2k tokens on; a wider
tile buys nothing and doubles the kernel's build, 64 blocks a step are 5 %
faster behind 11k tokens and a third slower at none (a tile's last step is
scored whole). Neither masking the last steps only (``lax.cond`` round the
comparison) nor two half-tiles a step (for the scheduler to lay one's softmax
beside the other's product) moved a reading by 1 %. The largest difference
from the view's result at those shapes: 0.008 at context 0, 0.0005 behind it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _on_tpu
from ray_tpu.ops.paged_attention import walk_call

# Blocks a compute step takes: 32 x 16 rows. A step costs ~0.4 us whatever it
# holds (the chain copy -> scores -> softmax -> weighted sum is not overlapped
# from step to step) and ~1.8 ns a row, and a slot's last step is scored
# whole. On a v5e over GLM-4.7-Flash's pool, 32 slots at 1000-3500 rows, 8
# layers (0.91 ms at the HBM's peak): 4 blocks 6.27 ms, 8 3.25, 16 2.23, 32
# 1.71, 64 1.57; the view it replaces 8.45 (my chip runs, PR 44).
_PAGES = 32
# A prefill chunk's tile: queries x heads, the rows of one product a compute
# step, and the blocks such a step takes (the docstring has the chip's readings).
_TILE_ROWS = 1024
_CHUNK_PAGES = 32
# What the compiler may give the chunk's kernel of a v5e's 128 MiB of VMEM (16 by default): a tile's queries and
# result twice (their pipeline), its float32 scores, exponentials and accumulator, ~17 MB at 1024 rows x 512 keys.
_CHUNK_VMEM_BYTES = 64 << 20
_LANES = 128
_SUBLANES = 16  # rows of a bfloat16 tile: a tile of queries is whole ones, so that [H, Tq, W] is [H * Tq, W] as it lies


def _scores(q, rows, *, sm_scale: float):
    """q [H, W] (a chunk's tile: [H, Tq, W]) against the step's rows [T, W]: [H, T] ([H, Tq, T]).
    Every head scores the same rows: ONE product, of all the tile's H * Tq rows."""
    s = jax.lax.dot_general(q.reshape(-1, q.shape[-1]), rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return (s * sm_scale).reshape(*q.shape[:-1], -1)


def _sums(p, rows, *, width: int | None = None):
    """p [H, T] ([H, Tq, T]) over the step's rows [T, W]: [H, W] ([H, Tq, W]).
    ``width``: over the rows' first ``width`` columns alone, zeros behind them
    (a slice of a buffer in VMEM costs nothing; the caller cuts the rest away)."""
    flat = p.reshape(-1, p.shape[-1]).astype(rows.dtype)
    cut = bool(width) and width < rows.shape[-1]
    o = jax.lax.dot_general(flat, rows[:, :width] if cut else rows, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    if cut:
        o = jnp.concatenate([o, jnp.zeros((o.shape[0], rows.shape[-1] - width), o.dtype)], axis=1)
    return o.reshape(*p.shape[:-1], -1)


def _load(bufs):
    """The step's rows [T, W] of the one leaf's buffer [pages, Bs, W]."""
    (buf,) = bufs
    return buf[...].reshape(-1, buf.shape[-1])


def paged_latent_attention(q, ckv, layer, block_tables, lengths, *, sm_scale: float, interpret: bool | None = None):
    """q [B, 1, H, W] (absorbed queries) over layer ``layer`` (traced) of the
    pool leaf ``ckv`` [L, N, Bs, W] through ``block_tables`` [B, n_max]:
    slot b attends its first ``lengths[b]`` rows, in table order, and a slot of
    length 0 reads nothing and gets zeros. Returns the softmax-weighted sum
    of the WHOLE rows, [B, H, 1, W] in q's dtype. ``interpret`` None: compiled
    on a TPU, interpreted elsewhere (tests)."""
    return _call(
        q, ckv, jnp.asarray(layer, jnp.int32), jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
        sm_scale=sm_scale, pages=min(_PAGES, block_tables.shape[1]),
        interpret=not _on_tpu() if interpret is None else interpret,
    )


# Under ``jit``: a program that calls the kernel from several layer stacks traces and lowers it once.
@functools.partial(jax.jit, static_argnames=("sm_scale", "pages", "interpret"))
def _call(q, ckv, layer, block_tables, lengths, *, sm_scale: float, pages: int, interpret: bool):
    B, _, H, W = q.shape
    # [B, H, 1, W] with the unit axis squeezed from the kernel's blocks (as
    # jax's own paged-attention kernel lays out heads that are no multiple of
    # the sublanes): the kernel sees [H, W] and the program one array of the
    # result's final shape, with no reshape behind it.
    return walk_call(
        q.reshape(B, H, 1, W), (ckv,), layer, block_tables, lengths, q_block=(None, H, None, W),
        name="paged_latent_attention", pages=pages, interpret=interpret, window=0,
        load=_load, scores=functools.partial(_scores, sm_scale=sm_scale), sums=_sums,
    )


def _tile(T: int, H: int) -> int:
    """Queries a tile of a chunk of ``T`` takes: the most whole sublane tiles
    of them that ``_TILE_ROWS`` rows hold at ``H`` heads and that divide the
    chunk; a chunk they do not divide (or shorter than one) is padded to them."""
    most = max(_TILE_ROWS // H // _SUBLANES, 1) * _SUBLANES
    fits = [tq for tq in range(_SUBLANES, most + 1, _SUBLANES) if T % tq == 0]
    return max(fits) if fits else min(most, -(-T // _SUBLANES) * _SUBLANES)


def paged_latent_chunk_attention(
    q, ckv, layer, block_tables, starts, ends, *, sm_scale: float, value_width: int | None = None, interpret: bool | None = None
):
    """A chunk's queries q [B, T, H, W] (absorbed), row b's at positions
    ``starts[b]``.., over layer ``layer`` (traced) of the pool leaf ``ckv`` [L,
    N, Bs, W] through ``block_tables`` [B, n_max], the chunk's own rows
    already written there: a query attends the row's cached rows up to its own
    position and under ``ends[b]`` (past which the chunk's rows are padding; a
    padded query sees every real row, a tile of nothing but padding none; 0:
    an inactive row, which reads nothing and gets zeros). Returns the
    softmax-weighted sum of the rows, [B, H, T, W] in q's dtype: of the WHOLE
    rows, or (``value_width``: the latent's columns, a multiple of 128) of
    their first ``value_width`` columns with zeros behind them, a fifth of the
    second product less at 512 of 640. ``interpret`` None: compiled on a TPU,
    interpreted elsewhere (tests)."""
    _, T, H, _ = q.shape
    return _chunk_call(
        q, ckv, jnp.asarray(layer, jnp.int32), jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32),
        sm_scale=sm_scale, pages=min(_CHUNK_PAGES, block_tables.shape[1]), tile=_tile(T, H),
        width=value_width if value_width and value_width % _LANES == 0 else None,
        interpret=not _on_tpu() if interpret is None else interpret,
    )


@functools.partial(jax.jit, static_argnames=("sm_scale", "pages", "tile", "width", "interpret"))
def _chunk_call(q, ckv, layer, block_tables, starts, ends, *, sm_scale: float, pages: int, tile: int, width: int | None, interpret: bool):
    _, T, H, W = q.shape
    tiles = -(-T // tile)
    # By head, as the result is: a tile is [H, tile, W], H * tile rows of one product.
    qh = q.transpose(0, 2, 1, 3)
    if tiles * tile != T:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, tiles * tile - T), (0, 0)))
    first = starts[:, None] + tile * jnp.arange(tiles, dtype=jnp.int32)[None, :]  # [B, tiles]: each tile's first position
    # A tile walks to its last query's position and no further: the chunk's triangle is skipped, not masked.
    lengths = jnp.where(first < ends[:, None], jnp.minimum(first + tile, ends[:, None]), 0)
    o = walk_call(
        qh, (ckv,), layer, block_tables, lengths.reshape(-1), q_block=(None, H, tile, W), tile_axis=2,
        starts=first.reshape(-1), name="paged_latent_chunk_attention", pages=pages, interpret=interpret, window=0,
        vmem_limit_bytes=_CHUNK_VMEM_BYTES, load=_load, scores=functools.partial(_scores, sm_scale=sm_scale),
        sums=functools.partial(_sums, width=width),
    )
    return o if tiles * tile == T else o[:, :, :T]
