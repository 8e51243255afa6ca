"""Collective plane tests (analog of the reference's
python/ray/util/collective/tests — NCCL/GLOO group tests re-targeted at the
XLA-over-mesh and object-store backends)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util.collective.types import ReduceOp


class TestTpuGroupSingleProcess:
    """world_size=1: the group degenerates to the local device mesh; ops are
    identity-like but compile the same shard_map programs."""

    def setup_method(self, _):
        from ray_tpu.util.collective.tpu_group import TpuCollectiveGroup

        self.group = TpuCollectiveGroup("g1", world_size=1, rank=0)

    def test_allreduce_identity(self):
        x = np.arange(8, dtype=np.float32)
        out = np.asarray(self.group.allreduce(x))
        np.testing.assert_allclose(out, x)

    def test_allgather(self):
        x = np.arange(4, dtype=np.float32)
        out = np.asarray(self.group.allgather(x))
        assert out.shape == (1, 4)


def test_cpu_collective_group_over_actors(ray_start_regular):
    """Full multi-member collective over the object-store backend."""

    @ray_tpu.remote
    class Member:
        def init_collective(self, world, rank, backend, group_name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, backend=backend, group_name=group_name)
            self.rank = rank
            return rank

        def do_allreduce(self):
            from ray_tpu.util import collective as col

            out = col.allreduce(np.full((4,), float(self.rank + 1)))
            return np.asarray(out)

        def do_broadcast(self):
            from ray_tpu.util import collective as col

            return np.asarray(col.broadcast(np.full((2,), float(self.rank)), src_rank=1))

        def do_allgather(self):
            from ray_tpu.util import collective as col

            return np.asarray(col.allgather(np.array([float(self.rank)])))

    from ray_tpu.util import collective as col

    members = [Member.remote() for _ in range(3)]
    col.create_collective_group(members, backend="cpu")
    outs = ray_tpu.get([m.do_allreduce.remote() for m in members], timeout=120)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 1.0 + 2.0 + 3.0))
    outs = ray_tpu.get([m.do_broadcast.remote() for m in members], timeout=120)
    for out in outs:
        np.testing.assert_allclose(out, np.full((2,), 1.0))
    outs = ray_tpu.get([m.do_allgather.remote() for m in members], timeout=120)
    for out in outs:
        np.testing.assert_allclose(out.ravel(), [0.0, 1.0, 2.0])


def test_multiprocess_tpu_backend_psum(ray_start_regular):
    """Two actor processes form a real XLA world (jax.distributed over the
    gloo CPU transport in tests; identical code path bootstraps ICI worlds on
    TPU pods) and allreduce through a compiled shard_map psum."""

    @ray_tpu.remote
    class XlaMember:
        def init_collective(self, world, rank, backend, group_name):
            # Workers inherit the 8-virtual-CPU-device XLA_FLAGS from the test
            # env: world=2 -> a 2x8 global mesh, psum over the proc axis.
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, backend=backend, group_name=group_name)
            self.rank = rank
            return rank

        def do_allreduce(self):
            from ray_tpu.util import collective as col

            out = col.allreduce(np.full((4,), float(self.rank + 1), dtype=np.float32))
            return np.asarray(out)

    from ray_tpu.util import collective as col

    members = [XlaMember.remote() for _ in range(2)]
    col.create_collective_group(members, backend="tpu")
    outs = ray_tpu.get([m.do_allreduce.remote() for m in members], timeout=300)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 3.0, dtype=np.float32))


def test_tpu_group_destroy_and_reform(ray_start_regular):
    """Gang-restart lifecycle (SURVEY hard part #1): a 2-process XLA world
    forms, allreduces, is destroyed (jax.distributed.shutdown + epoch bump),
    and re-forms under the SAME group name with a fresh epoch."""

    @ray_tpu.remote
    class XlaMember:
        def init_collective(self, world, rank, backend, group_name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, backend=backend, group_name=group_name)
            self.rank = rank
            return col.get_group(group_name).epoch

        def do_allreduce(self):
            from ray_tpu.util import collective as col

            return np.asarray(
                col.allreduce(np.full((4,), float(self.rank + 1), dtype=np.float32), group_name="reform")
            )

        def destroy(self, group_name):
            from ray_tpu.util import collective as col

            col.destroy_collective_group(group_name)
            return True

    from ray_tpu.util import collective as col

    members = [XlaMember.remote() for _ in range(2)]
    epochs = col.create_collective_group(members, backend="tpu", group_name="reform")
    assert len(set(epochs)) == 1
    outs = ray_tpu.get([m.do_allreduce.remote() for m in members], timeout=300)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 3.0, dtype=np.float32))

    ray_tpu.get([m.destroy.remote("reform") for m in members], timeout=120)

    epochs2 = col.create_collective_group(members, backend="tpu", group_name="reform")
    assert len(set(epochs2)) == 1 and epochs2[0] == epochs[0] + 1
    outs = ray_tpu.get([m.do_allreduce.remote() for m in members], timeout=300)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 3.0, dtype=np.float32))


def test_rendezvous_advertises_node_ip(ray_start_regular):
    """The coordinator address published in the KV must carry the node's
    GCS-registered IP (round-1 bug: hardwired 127.0.0.1 cannot span hosts).
    On this single-host fixture the registered address IS loopback, so
    instead assert the epoch-scoped key layout and that the IP equals the
    node's registered address rather than a constant."""

    @ray_tpu.remote
    class XlaMember:
        def init_collective(self, world, rank, backend, group_name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, backend=backend, group_name=group_name)
            return True

        def coordinator_in_kv(self, group_name):
            from ray_tpu._private import worker_context

            cw = worker_context.get_core_worker_if_initialized()
            epoch = int(bytes(cw.gcs.call("kv_get", {"key": f"collective/{group_name}/epoch"})["value"]).decode())
            resp = cw.gcs.call("kv_get", {"key": f"collective/{group_name}/coord/{epoch}"})
            nodes = cw.gcs.call("get_nodes")["nodes"]
            my_ip = nodes[cw.node_id]["address"][0]
            return bytes(resp["value"]).decode(), my_ip

    from ray_tpu.util import collective as col

    members = [XlaMember.remote() for _ in range(2)]
    col.create_collective_group(members, backend="tpu", group_name="ipcheck")
    coord, node_ip = ray_tpu.get(members[0].coordinator_in_kv.remote("ipcheck"), timeout=120)
    assert coord.split(":")[0] == node_ip


def test_tpu_group_member_kill_and_reform(ray_start_regular):
    """Gang-restart drill: a collective member is KILLED (no graceful
    destroy — worker death mid-step) and the group re-forms under the same
    name with a survivor + a replacement. The epoch bump is what makes the
    stale epoch's state unreachable (tpu_group.py _rendezvous)."""

    @ray_tpu.remote
    class XlaMember:
        def do_allreduce(self, group_name):
            from ray_tpu.util import collective as col

            return np.asarray(
                col.allreduce(
                    np.full((4,), float(self.rank + 1), dtype=np.float32),
                    group_name=group_name,
                )
            )

        def init_collective(self, world, rank, backend, group_name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, backend=backend, group_name=group_name)
            self.rank = rank
            return col.get_group(group_name).epoch

    from ray_tpu.util import collective as col

    members = [XlaMember.remote() for _ in range(2)]
    epochs = col.create_collective_group(members, backend="tpu", group_name="drill")
    outs = ray_tpu.get([m.do_allreduce.remote("drill") for m in members], timeout=300)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 3.0, dtype=np.float32))

    # Kill a member outright mid-lifecycle: no destroy, no epoch cleanup.
    # Whole-gang restart follows (BackendExecutor semantics: a dead member
    # invalidates the world, so every survivor is torn down too — one
    # process can host at most one multi-process XLA world, and a dead
    # peer's coordination service state cannot be re-joined).
    ray_tpu.kill(members[1])
    ray_tpu.kill(members[0])
    gang = [XlaMember.remote() for _ in range(2)]
    epochs2 = col.create_collective_group(gang, backend="tpu", group_name="drill")
    assert len(set(epochs2)) == 1 and epochs2[0] > epochs[0]
    outs = ray_tpu.get([m.do_allreduce.remote("drill") for m in gang], timeout=300)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 3.0, dtype=np.float32))
