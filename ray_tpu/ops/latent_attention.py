"""Decode attention over a paged LATENT pool, read where it lies.

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


def _scores(q, rows, *, sm_scale: float):
    """q [H, W] against the step's rows [T, W]: [H, T]."""
    return jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale


def _sums(p, rows):
    """p [H, T] over the step's rows [T, W]: [H, W]."""
    return jax.lax.dot_general(p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


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
