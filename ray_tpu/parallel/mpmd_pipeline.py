"""MPMD pipeline parallelism over compiled graphs.

``parallel/pipeline.py`` is the single-controller SPMD GPipe program: one
jitted graph, one mesh, every stage lock-stepped inside one ``lax.scan`` —
bubbles paid in full, and every device marches to one program counter. This
module is the MPMD counterpart (PAPERS.md, "Scaling Deep Learning Training
with MPMD Pipeline Parallelism"): each stage is its OWN program — a
``@remote(tensor_transport="collective")`` actor owning its own mesh and
its own jitted stage fn — and the stages are stitched into a
``CompiledDAG`` (PR 7: shm channel rings + resident worker loops, zero
raylet RPCs per iteration) whose inter-stage edges carry device-object
DESCRIPTORS (PR 12, experimental/channel/device_envelope.py) while the
activations stream out of band over the ``util/collective`` p2p seam — no
tensor crosses the host object store between stages.

The schedule is interleaved 1F1B-style streaming: the driver pumps
microbatch ``m`` into stage 0 while stage ``k`` runs microbatch ``m-k`` —
each resident loop starts its next microbatch the moment the descriptor
slot for it lands, so stage k at microbatch m overlaps stage k+1 at m-1 and
the steady-state bubble fraction approaches ``(S-1)/(M+S-1)``. Per-stage
stall/busy counters (``channel_loop_stats``) make the bubble measurable
rather than theoretical (``stage_stats``, ``bubble_fraction``).

Outputs are bit-exact vs ``pipeline_apply`` on the same stacked params:
each stage computes the identical ``stage_fn(params_k, x_mb)`` dot, and
activations cross process boundaries through ``_private/serialization``'s
exact-bytes jax.Array reducer.
"""

from __future__ import annotations

import logging

import ray_tpu
from ray_tpu.dag import InputNode

logger = logging.getLogger(__name__)


@ray_tpu.remote(tensor_transport="collective")
class PipelineStageActor:
    """One pipeline stage: owns its params (on its own mesh) and its jitted
    stage fn. ``run`` executes inside the resident channel loop — the
    tensor_transport opt-in makes its jax.Array result leave as a
    descriptor slot instead of ring bytes."""

    def __init__(self, stage_fn, params, stage_idx: int, n_stages: int,
                 mesh_axes: dict | None = None):
        import jax

        self.idx = stage_idx
        self.n_stages = n_stages
        self.mesh = None
        if mesh_axes:
            from ray_tpu.parallel.mesh import MeshConfig, create_mesh, replicate_pytree

            self.mesh = create_mesh(MeshConfig(**mesh_axes))
            self.params = replicate_pytree(params, self.mesh)
        else:
            self.params = jax.device_put(params)
        self._fn = jax.jit(stage_fn)

    def ready(self) -> int:
        return self.idx

    def warmup(self, x):
        """Trace + compile the stage fn before the clock starts."""
        self._fn(self.params, x).block_until_ready()
        return True

    def run(self, x):
        return self._fn(self.params, x)

    def pid(self) -> int:
        import os

        return os.getpid()

    def devobj_stats(self) -> dict:
        from ray_tpu.experimental.device_object import device_object_stats

        return device_object_stats()

    def init_collective(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)


class MPMDPipeline:
    """N stage actors + one compiled DAG; ``apply`` streams microbatches.

    ``stage_fn(params_k, x_mb) -> y_mb`` with activations keeping one
    shape; ``stacked_params`` is a pytree with leading dim ``n_stages``
    (the same contract as ``pipeline_apply``, so the two are drop-in
    comparable on identical params/inputs)."""

    def __init__(
        self,
        stage_fn,
        stacked_params,
        *,
        n_stages: int | None = None,
        num_microbatches: int | None = None,
        max_in_flight: int = 16,
        stage_mesh_axes: dict | None = None,
        warmup_x=None,
    ):
        import jax

        leaves = jax.tree_util.tree_leaves(stacked_params)
        if not leaves:
            raise ValueError("stacked_params has no leaves")
        inferred = int(leaves[0].shape[0])
        self.n_stages = n_stages or inferred
        if self.n_stages != inferred:
            raise ValueError(
                f"n_stages={self.n_stages} but stacked_params lead dim is {inferred}"
            )
        self.num_microbatches = num_microbatches or self.n_stages
        self._max_in_flight = max(2, int(max_in_flight))
        # DAG class nodes (compiled graphs bind ClassNodes, not live
        # handles); resolve_actor_handle() gives the live gang for classic
        # calls (warmup, stats) — the same actors the compiled DAG uses,
        # via the shared per-DAG actor cache.
        self._stage_nodes = [
            PipelineStageActor.bind(
                stage_fn,
                jax.tree.map(lambda p, k=k: p[k], stacked_params),
                k,
                self.n_stages,
                stage_mesh_axes,
            )
            for k in range(self.n_stages)
        ]
        self.stages = [n.resolve_actor_handle() for n in self._stage_nodes]
        ray_tpu.get([s.ready.remote() for s in self.stages], timeout=300)
        if warmup_x is not None:
            ray_tpu.get(
                [s.warmup.remote(warmup_x) for s in self.stages], timeout=300
            )
        with InputNode() as inp:
            d = inp
            for node in self._stage_nodes:
                d = node.run.bind(d)
        self.compiled = d.experimental_compile(
            max_buffered_results=self._max_in_flight
        )
        self._torn_down = False

    # -- execution ------------------------------------------------------

    def apply(self, x, num_microbatches: int | None = None):
        """Run the full batch through the pipeline; returns y with x's
        batch shape. Microbatches are pumped ``max_in_flight`` deep so the
        stages overlap (1F1B streaming); outputs gather in order."""
        import jax.numpy as jnp

        M = num_microbatches or self.num_microbatches
        B = x.shape[0]
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
        mb = B // M
        x_mbs = x.reshape(M, mb, *x.shape[1:])
        window = self._max_in_flight - 1
        refs: list = []
        outs: list = []
        for m in range(M):
            refs.append(self.compiled.execute(x_mbs[m]))
            if len(refs) > window:
                outs.append(refs.pop(0).get())
        while refs:
            outs.append(refs.pop(0).get())
        return jnp.concatenate(outs, axis=0)

    # -- measurement ----------------------------------------------------

    def reset_stage_stats(self):
        self._each_loop_stats(reset=True)

    def stage_stats(self) -> list:
        """Per-stage stall/busy/resolve split (ns), ordered by stage index —
        read from each resident loop. The basis of the measured bubble
        fraction."""
        rows = [r for stats in self._each_loop_stats() for r in stats]
        return sorted(rows, key=lambda r: int(r["label"].split(":", 1)[0]))

    def bubble_fraction(self) -> float:
        """stall / (stall + busy) summed over stages since the last reset —
        the measured counterpart of (S-1)/(M+S-1)."""
        rows = self.stage_stats()
        stall = sum(r["stall_ns"] for r in rows)
        busy = sum(r["busy_ns"] for r in rows)
        total = stall + busy
        return stall / total if total else 0.0

    def _each_loop_stats(self, reset: bool = False) -> list:
        from ray_tpu._private import worker_context

        cw = worker_context.get_core_worker()
        out = []
        for addr in self.compiled._actor_addrs.values():
            resp = cw._owner_client(tuple(addr)).call(
                "channel_loop_stats",
                {"loop_id": self.compiled._dag_id, "reset": reset},
                timeout=10,
            )
            out.append(resp.get("stages") or [])
        return out

    def stage_devobj_stats(self) -> list:
        """Each stage process's device-object counters (the zero-host-copy
        evidence: transfers_host stays flat across a steady-state run)."""
        return ray_tpu.get(
            [s.devobj_stats.remote() for s in self.stages], timeout=60
        )

    # -- lifecycle ------------------------------------------------------

    def teardown(self, kill_actors: bool = True):
        if self._torn_down:
            return
        self._torn_down = True
        self.compiled.teardown()
        if kill_actors:
            for s in self.stages:
                try:
                    ray_tpu.kill(s)
                except Exception:
                    pass

    def __del__(self):
        try:
            if not self._torn_down:
                self.teardown(kill_actors=False)
        except Exception:
            pass


def mpmd_pipeline(stage_fn, stacked_params, **kwargs) -> MPMDPipeline:
    """Build an :class:`MPMDPipeline`; see the class docstring. Drop-in
    MPMD counterpart of ``pipeline_apply``::

        pipe = mpmd_pipeline(stage_fn, ws, num_microbatches=8)
        y = pipe.apply(x)         # bit-exact vs pipeline_apply(...)
        pipe.teardown()
    """
    return MPMDPipeline(stage_fn, stacked_params, **kwargs)
