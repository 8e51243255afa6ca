"""Multi-node (multi-raylet single-host) tests — the reference's
cluster_utils.Cluster pattern (python/ray/tests/conftest.py:396)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util.placement_group import placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy


def test_two_node_scheduling(ray_start_cluster):
    cluster = ray_start_cluster
    n1 = cluster.add_node(num_cpus=1, resources={"a": 1})
    n2 = cluster.add_node(num_cpus=1, resources={"b": 1})
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote
    def whoami():
        import os

        return os.environ.get("RAY_TPU_NODE_ID")

    on_a = whoami.options(resources={"a": 1}).remote()
    on_b = whoami.options(resources={"b": 1}).remote()
    node_a, node_b = ray_tpu.get([on_a, on_b], timeout=120)
    assert node_a == n1.node_id
    assert node_b == n2.node_id


def test_the_first_task_for_a_node_that_just_joined_does_not_wait_a_lease_out(ray_start_cluster):
    """wait_for_nodes() asks the GCS; the driver's raylet learns of its peers a
    heartbeat later. A lease request that arrives in between finds no node for
    its resource and is parked: when the view catches up it has to be asked of
    the node that fits, not handed there as a bare spec (whose grant found no
    requester, so that the driver waited the 30 s of worker_lease_timeout_s)."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    n2 = cluster.add_node(num_cpus=1, resources={"b": 1})
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(resources={"b": 1})
    def whoami():
        import os

        return os.environ.get("RAY_TPU_NODE_ID")

    t0 = time.monotonic()
    assert ray_tpu.get(whoami.remote(), timeout=120) == n2.node_id
    assert time.monotonic() - t0 < 10.0


def test_cross_node_object_transfer(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1, resources={"a": 1})
    cluster.add_node(num_cpus=1, resources={"b": 1})
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(resources={"a": 1})
    def produce():
        return np.full((512, 512), 7.0, dtype=np.float32)  # 1MB -> plasma

    @ray_tpu.remote(resources={"b": 1})
    def consume(x):
        return float(x.sum())

    ref = produce.remote()
    out = ray_tpu.get(consume.remote(ref), timeout=120)
    assert out == 7.0 * 512 * 512


@pytest.mark.parametrize("who", ["owner", "borrower"])
def test_wait_on_a_result_in_another_nodes_store(ray_start_cluster, who):
    """A plasma-sized result that lies in ANOTHER node's store is ready for
    wait() once its owner knows it sealed; fetch_local=True (the default) also
    pulls it into the waiter's node, fetch_local=False moves nothing."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2, resources={"a": 1})  # the driver's node
    cluster.add_node(num_cpus=1, resources={"b": 1})
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(resources={"b": 1}, num_returns=2)
    def produce():
        return "sealed", np.full((512, 512), 7.0, dtype=np.float32)  # 1MB -> plasma on b

    def waits(refs, **how):
        from ray_tpu._private import worker_context

        t0 = time.monotonic()
        ready, rest = ray_tpu.wait(refs, timeout=60, **how)
        local = worker_context.get_core_worker().store.contains(refs[0].hex())
        return len(ready), len(rest), time.monotonic() - t0 < 5.0, local

    if who == "borrower":  # a task on the driver's node, handed the ref unresolved
        on_a = ray_tpu.remote(resources={"a": 1})(waits)
        waits = lambda refs, **how: ray_tpu.get(on_a.remote(refs, **how), timeout=120)  # noqa: E731

    done, big = produce.remote()
    assert ray_tpu.get(done, timeout=120) == "sealed"
    assert waits([big], fetch_local=False) == (1, 0, True, False)
    assert waits([big]) == (1, 0, True, True)
    if who == "owner":
        assert ray_tpu.wait([big], timeout=60) == ([big], [])


def test_a_burst_of_actors_spreads_over_the_nodes_with_room(ray_start_cluster):
    """Three one-CPU actors created at once over three one-CPU nodes: the GCS
    places all three within one heartbeat, so it has to count what it has just
    placed. Scoring the same stale row thrice sent all three to ONE node, where
    two waited for a CPU that never came free (Serve's replicas, for 90 s)."""
    cluster = ray_start_cluster
    nodes = [cluster.add_node(num_cpus=1) for _ in range(3)]
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(num_cpus=1)
    class Where:
        def node(self):
            import os

            return os.environ.get("RAY_TPU_NODE_ID")

    actors = [Where.remote() for _ in range(3)]
    where = ray_tpu.get([a.node.remote() for a in actors], timeout=30)
    assert sorted(where) == sorted(n.node_id for n in nodes)


def test_node_affinity(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    n2 = cluster.add_node(num_cpus=1)
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote
    def whoami():
        import os

        return os.environ.get("RAY_TPU_NODE_ID")

    strategy = NodeAffinitySchedulingStrategy(node_id=n2.node_id)
    ref = whoami.options(scheduling_strategy=strategy).remote()
    assert ray_tpu.get(ref, timeout=120) == n2.node_id


def test_placement_group_strict_spread(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    cluster.connect()
    cluster.wait_for_nodes()

    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD")
    assert pg.ready(timeout=30)
    nodes = {pg.bundle_node(0), pg.bundle_node(1)}
    assert len(nodes) == 2

    @ray_tpu.remote
    def whoami():
        import os

        return os.environ.get("RAY_TPU_NODE_ID")

    from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

    r0 = whoami.options(
        scheduling_strategy=PlacementGroupSchedulingStrategy(pg, 0)
    ).remote()
    r1 = whoami.options(
        scheduling_strategy=PlacementGroupSchedulingStrategy(pg, 1)
    ).remote()
    got = set(ray_tpu.get([r0, r1], timeout=120))
    assert got == nodes
    remove_placement_group(pg)


def test_placement_group_strict_pack_tpu_slice(ray_start_cluster):
    """STRICT_PACK = one ICI domain: all TPU bundles land on one node."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1, num_tpus=4, labels={"tpu_slice": "v5e-4"})
    cluster.add_node(num_cpus=1, num_tpus=4, labels={"tpu_slice": "v5e-4"})
    cluster.connect()
    cluster.wait_for_nodes()

    pg = placement_group([{"TPU": 2}, {"TPU": 2}], strategy="STRICT_PACK")
    assert pg.ready(timeout=30)
    assert pg.bundle_node(0) == pg.bundle_node(1)
    remove_placement_group(pg)


def test_infeasible_pg_pending(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    cluster.connect()
    pg = placement_group([{"CPU": 64}], strategy="PACK")
    from ray_tpu.exceptions import PlacementGroupUnavailableError

    with pytest.raises(PlacementGroupUnavailableError):
        pg.ready(timeout=1.0)


def test_infeasible_tasks_dont_block_runnable_ones(ray_start_cluster):
    """Tasks whose resources don't exist yet park in the infeasible queue
    (reference keeps one too) — a block of them ahead of runnable CPU tasks
    must not delay the runnable ones."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(resources={"phantom_accel": 1})
    def needs_phantom():
        return "never"

    @ray_tpu.remote
    def runnable(x):
        return x * 2

    blocked = [needs_phantom.remote() for _ in range(50)]
    # Starvation shows up as this get timing out (the queue scan would only
    # revisit the runnable tasks on slow heartbeat-paced rotation).
    out = ray_tpu.get([runnable.remote(i) for i in range(8)], timeout=30)
    assert out == [i * 2 for i in range(8)]
    # The infeasible tasks are still pending (not failed, not run).
    ready, _ = ray_tpu.wait(blocked, num_returns=1, timeout=0.5)
    assert not ready
