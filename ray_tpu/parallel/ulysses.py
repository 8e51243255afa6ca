"""Ulysses (DeepSpeed-style) sequence parallelism via all_to_all.

Absent from the reference (SURVEY.md §5.7); TPU-native version: inputs are
sequence-sharded [B, T/n, H, D]; an ``all_to_all`` over the ``sp`` axis
re-shards to head-sharded [B, T, H/n, D], each device runs *full-sequence*
attention for its head subset (any kernel — here ops.attention.flash_attention),
and a second all_to_all restores sequence sharding. Two all_to_alls ride ICI;
attention itself needs no communication — the right trade when
heads >= sp_degree and sequence lengths are moderate (ring_attention.py covers
the long-sequence regime).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import flash_attention


def ulysses_attention(
    q,
    k,
    v,
    mesh,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    sm_scale: float | None = None,
):
    """Exact attention over sequence-sharded inputs via head re-sharding.

    [B, T, H, D] sharded on T over `axis_name`; H must be divisible by the
    axis size.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis_name]
    H = q.shape[2]
    if H % n:
        raise ValueError(f"heads ({H}) must be divisible by sp axis size ({n})")

    def local_fn(q_loc, k_loc, v_loc):
        # [B, T/n, H, D] -> all_to_all -> [B, T, H/n, D]
        def seq_to_heads(x):
            return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

        def heads_to_seq(x):
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(q_loc), seq_to_heads(k_loc), seq_to_heads(v_loc)
        swap = lambda x: x.transpose(0, 2, 1, 3)  # flash_attention's layout is [B, H/n, T, D]
        out = flash_attention(swap(qh), swap(kh), swap(vh), causal=causal, sm_scale=sm_scale)
        return heads_to_seq(swap(out))

    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
