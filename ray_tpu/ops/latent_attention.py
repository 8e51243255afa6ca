"""Decode attention over a paged LATENT pool, read where it lies.

One query a slot against the slot's cached rows (MLA in absorbed form: a row
is the normed latent followed by the rotary key, ``generate._project_latent``;
every head scores and sums over the SAME rows). The XLA path gathers each
slot's whole block table into a ``[B, n_max * Bs, W]`` view, a layer at a
time, and reads that copy twice more; this kernel walks the table instead:

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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _on_tpu

# Blocks a compute step takes: 32 x 16 rows. A step costs ~0.4 us whatever it
# holds (the chain copy -> scores -> softmax -> weighted sum is not overlapped
# from step to step) and ~1.8 ns a row, and a slot's last step is scored
# whole. On a v5e over GLM-4.7-Flash's pool, 32 slots at 1000-3500 rows, 8
# layers (0.91 ms at the HBM's peak): 4 blocks 6.27 ms, 8 3.25, 16 2.23, 32
# 1.71, 64 1.57; the view it replaces 8.45 (my chip runs, PR 44).
_PAGES = 32


def _kernel(
    lengths_ref, tables_ref, layer_ref,  # scalar prefetch: [B], [B * n_max], [1]
    q_ref, ckv_ref,  # [H, W] of this slot; the pool [L, N, Bs, W] in HBM
    o_ref,  # [H, W] of this slot
    buf, sems, state,  # [2, pages, Bs, W]; DMA semaphores [2]; SMEM [2]: (buffer of the next step, 1 if its copies are in flight)
    *, pages: int, n_max: int, sm_scale: float,
):
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, B = pl.program_id(0), pl.num_programs(0)
    Bs, W = buf.shape[2:]
    T = pages * Bs
    layer = layer_ref[0]

    def steps_of(slot):
        return pl.cdiv(lengths_ref[slot], T)

    def blocks_from(slot, step):
        """Blocks the slot holds from compute step ``step`` on."""
        return pl.cdiv(lengths_ref[slot], Bs) - step * pages

    def start(slot, step, at, i):
        """Start the copy of block ``i`` of compute step ``step`` of ``slot`` into buffer ``at``."""
        block = tables_ref[slot * n_max + step * pages + i]
        pltpu.make_async_copy(ckv_ref.at[layer, block], buf.at[at, i], sems.at[at]).start()

    def wait(at, i):
        """Wait for one block's copy into buffer ``at``: any block's bytes."""
        pltpu.make_async_copy(ckv_ref.at[0, 0], buf.at[at, i], sems.at[at]).wait()

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
        buf[...] = jnp.zeros_like(buf)

    n_steps = steps_of(b)
    q = q_ref[...]
    H = q.shape[0]
    first = state[0]

    @pl.when((n_steps > 0) & (state[1] == 0))
    def _():  # nobody fetched ahead for this slot: the first, or one behind an empty slot
        each_block(blocks_from(b, 0), lambda i: start(b, 0, first, i), unrolled=False)

    next_slot = jnp.minimum(b + 1, B - 1)
    hand_over = (b + 1 < B) & (steps_of(next_slot) > 0)

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        at = (first + i) % 2
        # While this step is computed, the slot's next step is copied in, or the next slot's first.
        more = i + 1 < n_steps
        slot, step = jnp.where(more, b, next_slot), jnp.where(more, i + 1, 0)
        ahead = jnp.where(more | hand_over, blocks_from(slot, step), 0)
        each_block(ahead, lambda j: start(slot, step, 1 - at, j))
        each_block(blocks_from(b, i), lambda j: wait(at, j))
        rows = buf[at].reshape(T, W)
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale
        col = i * T + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < lengths_ref[b], s, -jnp.inf)
        # Every step holds a column under the length, so the maximum is finite from the first step on.
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * correction + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_cur, l_cur, acc_prev * correction + pv

    m0 = jnp.full((H, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    _, l, acc = lax.fori_loop(0, n_steps, body, (m0, l0, jnp.zeros((H, W), jnp.float32)))
    # A slot of length 0 read nothing and summed nothing: zeros over one.
    o_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)

    @pl.when(n_steps > 0)
    def _():
        state[0] = (first + n_steps) % 2
        state[1] = hand_over.astype(jnp.int32)


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
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, H, W = q.shape
    Bs = ckv.shape[2]
    n_max = block_tables.shape[1]
    # [B, H, 1, W] with the unit axis squeezed from the kernel's blocks (as
    # jax's own paged-attention kernel lays out heads that are no multiple of
    # the sublanes): the kernel sees [H, W] and the program one array of the
    # result's final shape, with no reshape behind it.
    spec = pl.BlockSpec((None, H, None, W), lambda b, *_: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, pages=pages, n_max=n_max, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec,
            grid=(B,),
            scratch_shapes=(
                pltpu.VMEM((2, pages, Bs, W), ckv.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ),
        ),
        # Slots in order: the buffers' turn and the copies in flight pass from one to the next.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, W), q.dtype),
        interpret=interpret,
        name="paged_latent_attention",
    )(lengths, block_tables.reshape(-1), layer.reshape(1), q.reshape(B, H, 1, W), ckv)
