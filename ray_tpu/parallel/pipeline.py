"""Pipeline parallelism over the ``pp`` mesh axis.

The reference delegates PP to external frameworks (SURVEY.md §2.3); here it is
a collective program: layer parameters are stacked [n_stages, ...] and sharded
over ``pp``; activations flow stage-to-stage via ``lax.ppermute`` inside a
``lax.scan`` over microbatches + bubble steps (GPipe schedule). Everything is
one jitted SPMD program — XLA overlaps the ppermute with the next microbatch's
compute on ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn,
    stacked_params,
    x,
    mesh,
    *,
    axis_name: str = "pp",
    num_microbatches: int | None = None,
):
    """Run ``num_stages`` stacked stages over microbatched input.

    stage_fn(params_slice, x_mb) -> y_mb, where activations keep one shape.
    stacked_params: pytree with leading dim = num_stages (sharded over pp).
    x: [num_microbatches * mb, ...] global batch (replicated over pp).
    Returns y with x's batch shape.
    """
    n_stages = mesh.shape[axis_name]
    B = x.shape[0]
    M = num_microbatches or n_stages
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    mb = B // M
    x_mbs = x.reshape(M, mb, *x.shape[1:])

    def local_fn(params_loc, x_all):
        # params_loc: stage slice with leading dim 1; x_all: [M, mb, ...].
        params_stage = jax.tree.map(lambda p: p[0], params_loc)
        stage = lax.axis_index(axis_name)
        T = M + n_stages - 1  # total schedule steps incl. bubble
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        state = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros((M, mb) + x_all.shape[2:], x_all.dtype)

        def step(carry, t):
            state, outputs = carry
            # Stage 0 ingests microbatch t (while t < M); other stages use
            # the activation ppermuted from the previous stage.
            feed = jnp.where(t < M, 1, 0)
            x_in = x_all[jnp.minimum(t, M - 1)]
            state = jnp.where((stage == 0) & (feed == 1), x_in, state)
            y = stage_fn(params_stage, state)
            # Last stage writes its finished microbatch t - (n_stages - 1).
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            outputs = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), axis=0
                ),
                lambda o: o,
                outputs,
            )
            # Rotate activations forward around the ring.
            state = lax.ppermute(y, axis_name, fwd_perm)
            return (state, outputs), None

        (state, outputs), _ = lax.scan(step, (state, outputs), jnp.arange(T))
        # Only the last stage holds real outputs; broadcast to all stages so
        # the result is replicated over pp.
        outputs = lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis_name,
        )
        return outputs

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
    y_mbs = fn(stacked_params, x_mbs)
    return y_mbs.reshape(B, *y_mbs.shape[2:])
