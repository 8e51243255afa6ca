"""Loss kernels.

``fused_lm_loss``: next-token cross-entropy fused with the LM-head matmul,
computed over chunks of tokens so the full ``[B*T, V]`` f32 logits tensor is
never materialized. The unfused loss writes those logits and their
log-softmax to HBM in the forward and reads them back in the backward (at
Mistral's training widths, T 4096 and V 32000, 0.5 GB a sequence each) —
pure bandwidth, no MXU work. The chunked form keeps one ``[chunk, V]`` tile
live at a time (64 MiB at chunk=512) and recomputes it in the backward:
classic flash-style trade of FLOPs for HBM, the same rematerialisation XLA
cannot do on its own across the matmul+softmax+gather boundary.

Forward per chunk: ``logits = x_c @ head; lse = logsumexp(logits);
nll_c = lse - logits[target]``. Backward per chunk:
``p = exp(logits - lse); p[target] -= 1; dx_c = g/N * (p @ head^T);
dhead += x_c^T @ (g/N * p)`` — the standard softmax-CE gradient, rebuilt
blockwise from the saved (tiny) ``lse`` rather than saved logits.

Under a mesh that splits the tokens (``dp``, ``fsdp``, ``sp``: the axes of
the ``batch`` and ``seq`` rules) each device runs those scans over its own
rows inside a ``jax.shard_map``. Left to the partitioner, the scan's leading
axis ``[N // chunk]`` is the sharded one and a scan walks it an index at a
time: the hidden states of the global batch were all-gathered, twice, and
every device computed the head and its backward for all of them (PR 36).
Across devices go the f32 sum of the log-likelihoods and, once after the
backward scan, the head's gradient in the compute dtype; hidden states and
targets never do. A mesh that splits the vocabulary (``tp`` > 1) keeps the
partitioner's path: a replicated head would be gathered and its matmul
repeated on every ``tp`` member, and a vocabulary-parallel loss is not
written yet.

(The reference delegates LM losses to torch/HF — SURVEY.md §5.7; this is
the TPU-native hot-path equivalent, same role as ops/attention.py.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def _pick_chunk(n: int, want: int) -> int:
    """Largest divisor of n that is <= want (prefer multiples of 128 for
    clean MXU tiling; n is B*T which is 128-aligned in practice)."""
    want = max(1, min(want, n))
    for c in range(want, 0, -1):
        if n % c == 0:
            return c
    return n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_lm_loss_sum(x, head, targets, chunk):
    """sum of per-token NLL. x: [N, D] (model dtype), head: [D, V],
    targets: [N] int32. Returns f32 scalar."""
    s, _ = _fused_fwd_scan(x, head, targets, chunk)
    return s


def _fused_fwd_scan(x, head, targets, chunk):
    N, D = x.shape
    xc = x.reshape(N // chunk, chunk, D)
    tc = targets.reshape(N // chunk, chunk)

    def body(total, ct):
        xb, tb = ct
        logits = jnp.dot(xb, head, preferred_element_type=jnp.float32)  # [c, V]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [c]
        tgt = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - tgt), lse

    total, lses = lax.scan(body, jnp.float32(0.0), (xc, tc))
    return total, lses.reshape(N)


def _fused_lm_loss_fwd(x, head, targets, chunk):
    total, lse = _fused_fwd_scan(x, head, targets, chunk)
    return total, (x, head, targets, lse)


def _fused_lm_loss_bwd(chunk, res, g):
    x, head, targets, lse = res
    N, D = x.shape
    xc = x.reshape(N // chunk, chunk, D)
    tc = targets.reshape(N // chunk, chunk)
    lc = lse.reshape(N // chunk, chunk)

    def body(dhead_acc, ct):
        xb, tb, lb = ct
        logits = jnp.dot(xb, head, preferred_element_type=jnp.float32)  # [c, V]
        p = jnp.exp(logits - lb[:, None])  # softmax, rebuilt from saved lse
        p = p - jax.nn.one_hot(tb, logits.shape[-1], dtype=p.dtype)
        pg = (p * g).astype(x.dtype)
        dxb = jnp.dot(pg, head.T, preferred_element_type=jnp.float32).astype(x.dtype)
        dhead_acc = dhead_acc + jnp.dot(
            xb.T, pg, preferred_element_type=jnp.float32
        )
        return dhead_acc, dxb

    dhead, dxc = lax.scan(body, jnp.zeros(head.shape, jnp.float32), (xc, tc, lc))
    return dxc.reshape(N, D), dhead.astype(head.dtype), None


_fused_lm_loss_sum.defvjp(_fused_lm_loss_fwd, _fused_lm_loss_bwd)


def _token_axes(mesh, shape):
    """The mesh axes that split a ``[B, T, ...]`` array's tokens, or None
    where the loss stays the partitioner's: no mesh, a split vocabulary,
    tokens that are not split or do not divide."""
    if mesh is None:
        return None
    from ray_tpu.parallel.mesh import DEFAULT_RULES

    def size(axes):
        return math.prod(mesh.shape.get(a, 1) for a in axes)

    batch, seq = DEFAULT_RULES["batch"], DEFAULT_RULES["seq"]
    if size(DEFAULT_RULES["vocab"]) > 1 or size(batch) * size(seq) == 1:
        return None
    if shape[0] % size(batch) or shape[1] % size(seq):
        return None
    return batch + seq


def fused_lm_loss(
    x,
    head,
    targets,
    *,
    chunk_size: int = 512,
    mean: bool = True,
    mesh=None,
):
    """Cross-entropy LM loss fused with the head projection.

    x: [B, T, D] or [N, D] final hidden states (bf16 fine — the matmul
    accumulates f32); head: [D, V]; targets: [B, T] or [N] int32.
    Numerically identical (f32 accumulation, logsumexp-stable) to
    ``log_softmax(x @ head)`` gathering, without ever holding [N, V].

    ``mesh``: the mesh x is sharded over, if any. Where it splits the tokens
    of a [B, T, D] x and not the vocabulary, each device scans its own rows
    (module docstring); any other mesh, and none, run the one program the
    partitioner is handed.
    """
    axes = _token_axes(mesh, x.shape) if x.ndim == 3 else None

    def total_nll(x, head, targets):
        x, targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
        chunk = _pick_chunk(x.shape[0], chunk_size)
        total = _fused_lm_loss_sum(x, head.astype(x.dtype), targets, chunk)
        return total if axes is None else lax.psum(total, axes)

    if axes is not None:
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.mesh import logical_to_spec

        # Cast outside, so that the head's gradient is summed across devices
        # in the compute dtype, as the layers' gradients are.
        head = head.astype(x.dtype)
        # check_vma=False: the forward scan's carry starts as a constant and
        # comes back varying over the token axes, which the check refuses;
        # the flash call's shard_map passes the same for its Mosaic kernels.
        total_nll = jax.shard_map(
            total_nll,
            mesh=mesh,
            in_specs=(
                logical_to_spec(("batch", "seq", None)), P(), logical_to_spec(("batch", "seq"))
            ),
            out_specs=P(),
            check_vma=False,
        )
    total = total_nll(x, head, targets)
    return total / targets.size if mean else total
