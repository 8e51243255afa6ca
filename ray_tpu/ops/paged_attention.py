"""Decode attention over a paged pool of keys and values, read where it lies.

One query a slot, ``q [B, 1, H, Dh]``, against the slot's cached rows in
layer ``layer`` of the pool leaves ``k``, ``v`` ``[L, N, Bs, KV, Dh]``
(``generate.init_paged_cache``, one group of layers). The XLA path gathers
every slot's whole block table into two ``[B, n_max * Bs, KV, Dh]`` views, a
layer at a time, whatever the rows hold; this kernel walks the table instead,
and a step's cost follows what the rows hold, not the table's width:

- the leaves stay in HBM whole and are never sliced: the layer's index is an
  operand, and a block is one contiguous ``[Bs, KV, Dh]`` region a leaf (32 KB
  for 16 x 8 x 128 bf16) that an async copy brings into VMEM;
- the block tables and the lengths are scalar-prefetch operands, so the
  copies' addresses are computed on the scalar core (the tables whole in
  SMEM: 16 slots x 160 blocks are 10 KB);
- grid = (slots,), in order; inside, a loop over compute steps of ``pages``
  blocks, double-buffered: while a step's scores and weighted sums run, the
  next step's blocks (or the next slot's first) are in flight. A slot stops at
  ``ceil(length / Bs)`` blocks: its last, shorter step copies those it holds
  and no more (what the buffers keep behind them is an earlier step's, or the
  zeros they start from: finite, and masked), and a slot of length 0 copies
  nothing. Under a sliding window shorter than the row the walk STARTS at the
  first block the window reaches, and the mask does the rest inside it;
- a cached row holds its KV heads on the sublanes (``[KV, Dh]`` is one tile of
  8 x 128), and a head's rows are every KV-th row of the step's ``[T * KV,
  Dh]``: a strided load takes them (of bfloat16, whose rows lie two to a
  32-bit word, the words of two heads at once, parted by a shift and a mask:
  the way of jax's ``ragged_paged_attention``), and the heads are stacked
  ``[KV, T, Dh]`` for two batched products. ``H / KV`` query heads score one
  KV head's rows (grouped queries: K and V are read once, at KV width);
- online softmax with float32 running maximum, sum and accumulator as the
  loop's carries (``ops/attention.py``'s way); the probabilities go to the
  second product in the pool's dtype, as ``generate._cache_attention``'s do.

The walk (``_walk``) is ``ops/latent_attention.py``'s too: that kernel hands
it one leaf and its own two products. It names no architecture: KV heads,
group size, head width, block size and window are what the arrays and the
arguments say.

On a v5e over Mistral-7B's pool (16 slots, 16 layers in a scan, 8 KV heads x
128 under 32 query heads, bfloat16 blocks of 16 rows, a table of 160 blocks;
my chip runs, PR 45; ms for the 16 layers' attention of one decode step, least
= the rows' bytes at the HBM's 819 GB/s), by blocks a compute step takes:

    rows a slot            least    4      8      16     32     64
    16 x 100-2000 tokens   1.35   2.62   1.89   1.66   1.68   1.79
    16 x ~400              0.52   1.11   0.84   0.71   0.69   0.91
    16 x ~100              0.13   0.39   0.33   0.33   0.43   0.64
    16 x ~2000             2.60   4.89   3.46   3.00   3.04   3.14
    3 x ~300, 13 empty     0.06   0.25   0.23   0.23   0.24   0.27

against the view (``_paged_view`` + ``_cache_mask`` + ``_cache_attention``),
which costs its rung whatever the rows hold: 1.33 ms at 512 tokens, 2.58 at
1024, 5.01 at 2048, 7.29 at 2560. 16 blocks (256 rows, 0.5 MB a leaf a
buffer) it is: 70-87 % of the HBM's rate from 400 tokens a row on, and a
slot's last step, scored whole, wastes least where rows are short. A call
costs ~14 us (0.23 ms for 16 layers) before it reads a row. The largest
difference from the view's result over those mixes: 0.003 (bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _on_tpu

# Blocks a compute step takes: 16 x 16 rows (the module's docstring has the chip's readings).
_PAGES = 16


def _walk(
    lengths_ref, tables_ref, layer_ref,  # scalar prefetch: [G], [B * n_max], [1] (and, ``causal``, [G] behind them)
    *refs,  # the slot's queries; then the pool leaves [L, N, Bs, ...] in HBM, the slot's result, and the scratch:
    # a buffer [2, pages, Bs, ...] a leaf, DMA semaphores [2], SMEM [2]: (buffer of the next step, 1 if its copies are in flight)
    pages: int, n_max: int, window: int, load, scores, sums, tiles: int = 1, causal: bool = False,
):
    """The kernel's body, for any row a block table names: ``load(buffers)``
    takes a compute step's rows out of the leaves' buffers (``[pages, Bs,
    ...]`` each), ``scores(q, rows) -> [..., T]`` float32 (scaled) scores them
    against the slot's queries, ``sums(p, rows) -> [..., W]`` float32 is the
    second product, and the result ``[..., W]`` has the shape of the slot's
    block of the output. ``window`` 0: none.

    A "slot" is one of the grid's G programs: a row of the tables, or
    (``tiles`` > 1) one of the ``tiles`` tiles of a row's queries, which walk
    the row's table one after the other, each to its own ``lengths_ref`` entry.
    ``causal``: a tile's queries lie on the scores' axis -2, the first at
    position ``starts_ref[slot]`` (one more scalar-prefetch operand) and each
    one position behind the one before it, and a query sees no row past its
    own position; what the tile's last query may not see is not walked at all,
    its ``lengths_ref`` entry being that query's position + 1 at most."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    starts_ref, q_ref, *refs = refs if causal else (None, *refs)
    n = (len(refs) - 3) // 2
    leaves, o_ref, bufs, (sems, state) = refs[:n], refs[n], refs[n + 1 : 2 * n + 1], refs[2 * n + 1 :]
    b, B = pl.program_id(0), pl.num_programs(0)
    Bs = bufs[0].shape[2]
    T = pages * Bs
    layer = layer_ref[0]

    def first_of(slot):
        """The first block the slot's query may see: block 0, or the one its window starts in."""
        return jnp.maximum(lengths_ref[slot] - window, 0) // Bs if window else 0

    def blocks_from(slot, step):
        """Blocks the slot holds from compute step ``step`` on."""
        return pl.cdiv(lengths_ref[slot], Bs) - first_of(slot) - step * pages

    def steps_of(slot):
        return pl.cdiv(blocks_from(slot, 0), pages)

    def starts(slot, step, at):
        """``start(i)``: start the copies of block ``i`` of compute step ``step`` of ``slot`` into buffer ``at``."""
        base = (slot // tiles if tiles > 1 else slot) * n_max + first_of(slot) + step * pages

        def start(i):
            block = tables_ref[base + i]
            for leaf, buf in zip(leaves, bufs):
                pltpu.make_async_copy(leaf.at[layer, block], buf.at[at, i], sems.at[at]).start()

        return start

    def wait(at, i):
        """Wait for one block's copies into buffer ``at``: any block's bytes."""
        for leaf, buf in zip(leaves, bufs):
            pltpu.make_async_copy(leaf.at[0, 0], buf.at[at, i], sems.at[at]).wait()

    def each_block(held, do, unrolled=True):
        """``do(i)`` for every block of a compute step of which the slot still
        holds ``held``: a whole step unrolled (straight-line code for the
        scalar core to start ``pages`` copies among the step's products; in a
        loop the kernel is a third slower), a slot's last, shorter step in a
        loop that stops at its last block. ``unrolled`` False: the loop for
        both (a copy traces in milliseconds, and a replica's start pays for it)."""
        if unrolled:
            @pl.when(held >= pages)
            def _():
                for i in range(pages):
                    do(i)

        @pl.when(held < pages if unrolled else held > 0)
        def _():
            lax.fori_loop(0, jnp.minimum(held, pages), lambda i, _: do(i), None)

    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0
        # What a short step leaves of a buffer is masked, and has to be finite to be.
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    n_steps = steps_of(b)
    q = q_ref[...]
    first = state[0]

    @pl.when((n_steps > 0) & (state[1] == 0))
    def _():  # nobody fetched ahead for this slot: the first, or one behind an empty slot
        each_block(blocks_from(b, 0), starts(b, 0, first), unrolled=False)

    next_slot = jnp.minimum(b + 1, B - 1)
    hand_over = (b + 1 < B) & (steps_of(next_slot) > 0)

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        at = (first + i) % 2
        # While this step is computed, the slot's next step is copied in, or the next slot's first.
        more = i + 1 < n_steps
        slot, step = jnp.where(more, b, next_slot), jnp.where(more, i + 1, 0)
        ahead = jnp.where(more | hand_over, blocks_from(slot, step), 0)
        each_block(ahead, starts(slot, step, 1 - at))
        each_block(blocks_from(b, i), lambda j: wait(at, j))
        rows = load(tuple(buf.at[at] for buf in bufs))
        s = scores(q, rows)
        by = s.shape[-2:] if causal else s.shape  # one mask for every head of a tile
        col = first_of(b) * Bs + i * T + lax.broadcasted_iota(jnp.int32, by, len(by) - 1)
        seen = col < lengths_ref[b]
        if window:
            seen &= col >= lengths_ref[b] - window
        if causal:
            seen &= col <= starts_ref[b] + lax.broadcasted_iota(jnp.int32, by, 0)
        s = jnp.where(seen, s, -jnp.inf)
        # Every step holds a column the query sees, so the maximum is finite from the first step on.
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * correction + p.sum(axis=-1, keepdims=True)
        return m_cur, l_cur, acc_prev * correction + sums(p, rows)

    m0 = jnp.full((*o_ref.shape[:-1], 1), -jnp.inf, jnp.float32)
    _, l, acc = lax.fori_loop(0, n_steps, body, (m0, jnp.zeros_like(m0), jnp.zeros(o_ref.shape, jnp.float32)))
    # A slot of length 0 read nothing and summed nothing: zeros over one.
    o_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)

    @pl.when(n_steps > 0)
    def _():
        state[0] = (first + n_steps) % 2
        state[1] = hand_over.astype(jnp.int32)


def walk_call(
    q, leaves, layer, block_tables, lengths, *, q_block, name: str, pages: int, interpret: bool,
    starts=None, tile_axis: int | None = None, vmem_limit_bytes: int | None = None, **products,
):
    """``_walk`` over ``leaves`` (each [L, N, Bs, ...]) for the queries ``q``
    [B, ...], a slot's block of them (and of the result, which has q's shape
    and dtype) ``q_block`` (None: an axis the kernel does not see). ``lengths``
    [G]: one program a row of ``block_tables``, or (``tile_axis``: the axis of
    q that ``q_block`` cuts into tiles) G / B programs a row, one a tile, in
    order; ``starts`` [G]: the position of each program's first query
    (``_walk``'s ``causal``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, n_max = block_tables.shape
    tiles = lengths.shape[0] // B

    def block_of(g, *_):
        if tile_axis is None:
            return (g,) + (0,) * (len(q_block) - 1)
        return tuple(g // tiles if d == 0 else g % tiles if d == tile_axis else 0 for d in range(len(q_block)))

    spec = pl.BlockSpec(q_block, block_of)
    scalars = (lengths, block_tables.reshape(-1), layer.reshape(1), *(() if starts is None else (starts,)))
    limit = {} if vmem_limit_bytes is None else {"vmem_limit_bytes": vmem_limit_bytes}
    return pl.pallas_call(
        functools.partial(_walk, pages=pages, n_max=n_max, tiles=tiles, causal=starts is not None, **products),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            in_specs=[spec, *[pl.BlockSpec(memory_space=pl.ANY)] * len(leaves)],
            out_specs=spec,
            grid=(lengths.shape[0],),
            scratch_shapes=(
                *[pltpu.VMEM((2, pages, *leaf.shape[2:]), leaf.dtype) for leaf in leaves],
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ),
        ),
        # Slots in order: the buffers' turn and the copies in flight pass from one to the next.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), **limit),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=name,
    )(*scalars, q, *leaves)


def _head_rows(ref):
    """A compute step's rows ``[pages, Bs, KV, Dh]`` in VMEM, by head: [KV, T, Dh]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pages, Bs, KV, Dh = ref.shape
    T = pages * Bs
    flat = ref.reshape(T * KV, Dh)
    if KV == 1:
        return flat[...][None]
    if ref.dtype != jnp.bfloat16 or KV % 2:
        return jnp.stack([flat[pl.ds(g, T, stride=KV), :] for g in range(KV)])
    # Two heads a 32-bit word, the even one low: each half, moved to a float32's upper bits, is that bfloat16.
    words = flat.bitcast(jnp.uint32)
    heads = []
    for pair in range(KV // 2):
        w = words[pl.ds(pair, T, stride=KV // 2), :]
        for half in (w << 16, w & jnp.uint32(0xFFFF0000)):
            heads.append(pltpu.bitcast(half, jnp.float32).astype(jnp.bfloat16))
    return jnp.stack(heads)


def _load(bufs):
    return tuple(_head_rows(buf) for buf in bufs)


def _scores(q, rows, *, sm_scale: float):
    """q [KV, G, Dh] against the step's keys [KV, T, Dh]: [KV, G, T]."""
    s = jax.lax.dot_general(q, rows[0], (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    return s * sm_scale


def _sums(p, rows):
    """p [KV, G, T] over the step's values [KV, T, Dh]: [KV, G, Dh]."""
    v = rows[1]
    return jax.lax.dot_general(p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)


def paged_attention(q, k, v, layer, block_tables, lengths, *, sm_scale: float, window: int = 0, interpret: bool | None = None):
    """q [B, 1, H, Dh] over layer ``layer`` (traced) of the pool leaves ``k``,
    ``v`` [L, N, Bs, KV, Dh] through ``block_tables`` [B, n_max]: slot b's
    query, at position ``lengths[b] - 1``, attends its first ``lengths[b]``
    rows in table order (the last ``window`` of them under a sliding window),
    query head h the rows of KV head ``h // (H / KV)``; a slot of length 0
    reads nothing and gets zeros. Returns [B, 1, H, Dh] in q's dtype, what
    ``generate._cache_attention`` returns over the gathered view.
    ``interpret`` None: compiled on a TPU, interpreted elsewhere (tests)."""
    return _call(
        q, k, v, jnp.asarray(layer, jnp.int32), jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
        sm_scale=sm_scale, window=window, pages=min(_PAGES, block_tables.shape[1]),
        interpret=not _on_tpu() if interpret is None else interpret,
    )


# Under ``jit``: a program that calls the kernel from several layer stacks traces and lowers it once.
@functools.partial(jax.jit, static_argnames=("sm_scale", "window", "pages", "interpret"))
def _call(q, k, v, layer, block_tables, lengths, *, sm_scale: float, window: int, pages: int, interpret: bool):
    B, _, H, Dh = q.shape
    KV = k.shape[3]
    o = walk_call(
        q.reshape(B, KV, H // KV, Dh), (k, v), layer, block_tables, lengths, q_block=(None, KV, H // KV, Dh),
        name="paged_attention", pages=pages, interpret=interpret, window=window,
        load=_load, scores=functools.partial(_scores, sm_scale=sm_scale), sums=_sums,
    )
    return o.reshape(B, 1, H, Dh)
