"""Group broadcast on the device-object collective plane (ISSUE 15).

- cpu_group payload parity: ``broadcast`` round-trips a SHARDED jax.Array
  bit-exact (sharding preserved), ``allgather`` stacks bit-exact, and
  non-uniform shapes are rejected with a typed CollectiveError naming the
  per-rank shapes.
- Typed timeouts: the two collective paths that used to raise raw
  TimeoutError (ring ``_collect``, p2p ``mailbox_recv``) now raise
  CollectiveTimeoutError naming group/ranks/tag (the chaos-matrix typed
  contract).
- Group-broadcast descriptor resolution on all three consumer paths:
  same-process (live array), same-group (direct-mailbox landing zone,
  zero pull round trips), and the host fallback (cut-through relay copy /
  devobj_pull for non-members).
- Chaos: a sampler SIGKILLed MID-BROADCAST (seeded kill plan firing while
  it answers the fan-out's p2p_ack) surfaces CollectiveBroadcastError
  NAMING the dead rank while surviving ranks complete and consume their
  payload; device-object residents return to baseline after teardown.

One module-scoped cluster for the ring/resolution tests (cluster spin-up
dominates tier-1 wall otherwise); the kill test builds its own 2-node
Cluster because it needs worker handles to push the seeded plan into.
"""

import gc
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import (
    CollectiveBroadcastError,
    CollectiveError,
    CollectiveTimeoutError,
    RayTpuError,
)


@pytest.fixture(scope="module")
def coll_cluster():
    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _store_objects() -> int:
    """Objects in this node's shm store: a payload that rode the direct
    mailboxes leaves the count where it was."""
    from ray_tpu._private import worker_context

    return worker_context.get_core_worker().raylet.call("get_state")["store"]["num_objects"]


def _sharded(n=64):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    x = jnp.arange(float(n), dtype=jnp.float32).reshape(8, n // 8)
    return jax.device_put(x, NamedSharding(mesh, P("dp", "tp")))


@ray_tpu.remote
class Member:
    """One collective-group member: joins groups, runs SPMD ring ops, and
    consumes device-object refs (arg resolution exercises the broadcast
    landing zone / pull fallback)."""

    def pid(self):
        return os.getpid()

    def init_collective(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)
        return rank

    def ring_broadcast_sharded(self, group_name, src_rank, is_src):
        """All ranks call broadcast; src contributes a sharded array.
        Returns (values, device_count_of_result_sharding)."""
        import jax.numpy as jnp

        from ray_tpu.util import collective as col

        x = _sharded() if is_src else jnp.zeros((8, 8), jnp.float32)
        out = col.broadcast(x, src_rank=src_rank, group_name=group_name)
        devices = len(getattr(getattr(out, "sharding", None), "device_set", [None]))
        return np.asarray(out), devices

    def ring_allgather(self, group_name, value):
        from ray_tpu.util import collective as col

        return np.asarray(col.allgather(np.asarray(value), group_name=group_name))

    def ring_allgather_shaped(self, group_name, shape):
        from ray_tpu.util import collective as col

        try:
            col.allgather(np.ones(shape, np.float32), group_name=group_name)
            return "no-error"
        except CollectiveError as e:
            return f"typed:{type(e).__name__}:{e}"

    def consume(self, w):
        return float(np.asarray(w).reshape(-1)[0]), int(np.asarray(w).size)

    def coll_stats(self):
        from ray_tpu.util.collective.p2p import COLL

        return {k: getattr(COLL, k) for k in COLL.__slots__}

    def bcast_recv(self, group_name, src_rank, tag, timeout=30.0):
        from ray_tpu.util import collective as col

        out = col.get_group(group_name).bcast_recv_payload(src_rank, tag, timeout=timeout)
        return np.asarray(out).sum().item()

    def bcast_send(self, group_name, tag, n):
        import jax.numpy as jnp

        from ray_tpu.util import collective as col

        return col.get_group(group_name).bcast_send_payload(
            jnp.ones((n,), jnp.float32), tag
        )

    def devobj_stats(self):
        from ray_tpu.experimental.device_object import device_object_stats

        return device_object_stats()


@ray_tpu.remote(tensor_transport="collective")
class Holder:
    def init_collective(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)
        return rank

    def make(self, n=4096):
        import jax.numpy as jnp

        return jnp.arange(float(n), dtype=jnp.float32)

    def residents(self):
        from ray_tpu.experimental.device_object import device_object_stats

        return device_object_stats()["resident_count"]


# ---------------------------------------------------------------------------
# cpu_group payload parity
# ---------------------------------------------------------------------------


def test_ring_broadcast_sharded_payload_parity(coll_cluster):
    """broadcast() hands every rank the src's jax.Array AS POSTED: values
    bit-exact AND the 4-device sharding layout survives the hop."""
    a, b = Member.remote(), Member.remote()
    ray_tpu.get([a.init_collective.remote(2, 0, "cpu", "parity2"),
                 b.init_collective.remote(2, 1, "cpu", "parity2")], timeout=60)
    ra = a.ring_broadcast_sharded.remote("parity2", 0, True)
    rb = b.ring_broadcast_sharded.remote("parity2", 0, False)
    (va, _), (vb, dev_b) = ray_tpu.get([ra, rb], timeout=60)
    expected = np.arange(64.0, dtype=np.float32).reshape(8, 8)
    np.testing.assert_array_equal(va, expected)
    np.testing.assert_array_equal(vb, expected)  # bit-exact across the hop
    assert dev_b == 4  # sharding layout re-landed on the receiver's devices


def test_ring_allgather_parity_and_typed_shape_error(coll_cluster):
    a, b = Member.remote(), Member.remote()
    ray_tpu.get([a.init_collective.remote(2, 0, "cpu", "gather2"),
                 b.init_collective.remote(2, 1, "cpu", "gather2")], timeout=60)
    ra = a.ring_allgather.remote("gather2", np.full((3,), 1.5, np.float32))
    rb = b.ring_allgather.remote("gather2", np.full((3,), 2.5, np.float32))
    va, vb = ray_tpu.get([ra, rb], timeout=60)
    expected = np.stack([np.full((3,), 1.5), np.full((3,), 2.5)]).astype(np.float32)
    np.testing.assert_array_equal(va, expected)
    np.testing.assert_array_equal(vb, expected)
    # Non-uniform shapes: every rank gets the TYPED error naming shapes.
    ra = a.ring_allgather_shaped.remote("gather2", (3,))
    rb = b.ring_allgather_shaped.remote("gather2", (4,))
    outs = ray_tpu.get([ra, rb], timeout=60)
    for out in outs:
        assert out.startswith("typed:CollectiveError"), out
        assert "uniform shapes" in out, out


# ---------------------------------------------------------------------------
# typed timeouts (chaos-matrix contract: no raw TimeoutError)
# ---------------------------------------------------------------------------


def test_collect_timeout_typed_names_missing_ranks(coll_cluster):
    from ray_tpu.util import collective as col

    group = col.init_collective_group(2, 0, backend="cpu", group_name="lonely2")
    try:
        group._post("allreduce", np.ones((2,), np.float32))
        with pytest.raises(CollectiveTimeoutError) as ei:
            group._collect("allreduce", timeout=0.3)
        assert ei.value.group == "lonely2"
        assert ei.value.ranks == [1]  # the rank that never posted, named
        assert isinstance(ei.value, RayTpuError)
        assert not isinstance(ei.value, TimeoutError)  # typed, not a bare timeout
    finally:
        col.destroy_collective_group("lonely2")


def test_mailbox_recv_timeout_typed_names_group_rank_tag(coll_cluster):
    from ray_tpu.util import collective as col

    group = col.init_collective_group(2, 0, backend="cpu", group_name="lonely3")
    try:
        with pytest.raises(CollectiveTimeoutError) as ei:
            group.recv(src_rank=1, tag="w17", timeout=0.3)
        assert ei.value.group == "lonely3"
        assert ei.value.ranks == [1]
        assert ei.value.tag == "w17"
    finally:
        col.destroy_collective_group("lonely3")


def test_bcast_recv_blocked_before_send_catches_direct_delivery(coll_cluster):
    """A receiver already parked in bcast_recv_payload when the sender
    starts (normal blocking-collective ordering) must catch the DIRECT
    delivery whenever it lands — the recv watches both landing zones for
    the whole window, not the direct mailbox for just the first second."""
    a, b = Member.remote(), Member.remote()
    ray_tpu.get([a.init_collective.remote(2, 0, "cpu", "recv2"),
                 b.init_collective.remote(2, 1, "cpu", "recv2")], timeout=60)
    pending = b.bcast_recv.remote("recv2", 0, "t1", 30.0)
    time.sleep(2.0)  # receiver is parked well past the old 1s direct probe
    info = ray_tpu.get(a.bcast_send.remote("recv2", "t1", 2048), timeout=60)
    assert info["ok_ranks"] == [1], info
    assert ray_tpu.get(pending, timeout=60) == 2048.0


# ---------------------------------------------------------------------------
# group-broadcast descriptor resolution: all three consumer paths
# ---------------------------------------------------------------------------


def test_broadcast_resolution_same_process(coll_cluster):
    import jax.numpy as jnp

    arr = jnp.arange(1024.0, dtype=jnp.float32)
    ref = ray_tpu.put(arr, tensor_transport="collective")
    assert ray_tpu.get(ref) is arr  # the live array, zero payload copies
    del ref
    gc.collect()


def test_broadcast_resolution_same_group_rides_inbox(coll_cluster):
    from ray_tpu.experimental import device_object

    holder = Holder.remote()
    consumers = [Member.remote() for _ in range(2)]
    ray_tpu.get(
        [holder.init_collective.remote(3, 0, "cpu", "res3")]
        + [c.init_collective.remote(3, i + 1, "cpu", "res3") for i, c in enumerate(consumers)],
        timeout=60,
    )
    ref = holder.make.remote(4096)
    info = device_object.broadcast(ref, "res3", timeout=60)
    assert sorted(info["ok_ranks"]) == [1, 2], info
    assert info["failed"] == {}
    vals = ray_tpu.get([c.consume.remote(ref) for c in consumers], timeout=60)
    assert vals == [(0.0, 4096), (0.0, 4096)]
    for c in consumers:
        stats = ray_tpu.get(c.coll_stats.remote(), timeout=30)
        assert stats["bcast_recvs"] >= 1, stats  # resolved FROM the landing zone
    # A second resolve of the same ref (inbox consumed) falls back to the
    # pull path and still produces the value.
    again = ray_tpu.get(consumers[0].consume.remote(ref), timeout=60)
    assert again == (0.0, 4096)
    del ref, info
    gc.collect()


def test_group_broadcast_mints_no_store_object_and_residents_drain(coll_cluster):
    """A learner's weight sync, twice over: the holder makes a payload, one
    group broadcast lands it at both consumers' inboxes, both apply it. The
    node's store gains no object (the payload rode the direct mailboxes),
    and once each sync's ref is dropped the holder's residents drain to 0."""
    from ray_tpu.experimental import device_object

    holder = Holder.remote()
    consumers = [Member.remote() for _ in range(2)]
    ray_tpu.get(
        [holder.init_collective.remote(3, 0, "cpu", "wsync3")]
        + [c.init_collective.remote(3, i + 1, "cpu", "wsync3") for i, c in enumerate(consumers)],
        timeout=60,
    )
    store0 = _store_objects()
    for _ in range(2):
        ref = holder.make.remote(64 * 1024)
        info = device_object.broadcast(ref, "wsync3", timeout=60)
        assert sorted(info["ok_ranks"]) == [1, 2] and info["failed"] == {}, info
        vals = ray_tpu.get([c.consume.remote(ref) for c in consumers], timeout=60)
        assert vals == [(0.0, 64 * 1024)] * 2
        del ref, info
    gc.collect()
    assert _store_objects() == store0
    deadline = time.monotonic() + 30
    while ray_tpu.get(holder.residents.remote(), timeout=30) > 0 and time.monotonic() < deadline:
        time.sleep(0.2)
    assert ray_tpu.get(holder.residents.remote(), timeout=30) == 0
    for a in [holder] + consumers:
        ray_tpu.kill(a)


def test_broadcast_resolution_host_fallback(coll_cluster):
    """A consumer OUTSIDE the group resolves the same broadcast ref over the
    host path; and the no-group broadcast() seals an arena copy the whole
    cluster's store plane can serve."""
    from ray_tpu._private import worker_context
    from ray_tpu.experimental import device_object

    holder = Holder.remote()
    outsider = Member.remote()  # never joins any group
    ray_tpu.get(holder.init_collective.remote(1, 0, "cpu", "solo1"), timeout=60)
    ref = holder.make.remote(4096)
    val = ray_tpu.get(outsider.consume.remote(ref), timeout=60)
    assert val == (0.0, 4096)  # pull/host fallback
    # Host-path broadcast: holder materializes, relay tree replicates (one
    # node here, so pushed_nodes == 0 but the arena copy must exist).
    info = device_object.broadcast(ref, timeout=60)
    assert info["kind"] == "plasma"
    cw = worker_context.get_core_worker()
    oid = ref.hex()
    deadline = time.monotonic() + 10
    while not cw.store.contains(oid) and time.monotonic() < deadline:
        time.sleep(0.1)
    assert cw.store.contains(oid)
    # Local-arena fast path: the driver (not the holder) resolves from its
    # node's store without waking the holder.
    got = ray_tpu.get(ref, timeout=60)
    assert float(np.asarray(got)[1]) == 1.0
    del ref
    gc.collect()


# ---------------------------------------------------------------------------
# relay-tree broadcast (ISSUE 16): topology, sub-O(K) root egress
# ---------------------------------------------------------------------------


def test_tree_broadcast_topology_and_sub_o_k_root_egress(coll_cluster):
    """A 5-rank group broadcast rides the binomial relay tree: the root
    streams only to its tree children (ranks 1, 2, 4 — sub-O(K) egress),
    rank 1 relays the payload onward to rank 3 (its COLL relay counters
    prove the mid-tree forward), and every member still lands the exact
    payload with a direct per-rank ack."""
    import jax.numpy as jnp

    from ray_tpu.util import collective as col

    members = [Member.remote() for _ in range(4)]
    group = "tree5"
    col.init_collective_group(5, 0, backend="cpu", group_name=group)
    try:
        ray_tpu.get(
            [m.init_collective.remote(5, i + 1, "cpu", group) for i, m in enumerate(members)],
            timeout=60,
        )
        payload = jnp.arange(448 * 1024, dtype=jnp.float32)  # 1.75 MiB -> 4 chunks
        store0 = _store_objects()
        info = col.get_group(group).bcast_send_payload(payload, "t16", timeout=60)
        assert info["topology"] == "tree", info
        assert info["root_children"] == [1, 2, 4], info
        assert sorted(info["ok_ranks"]) == [1, 2, 3, 4], info
        assert info["failed"] == {} and info["retried_ranks"] == []
        # Sub-O(K): the root pushed the payload to its 3 tree children,
        # not all 4 members — rank 3's copy came from the rank-1 relay.
        assert info["root_egress_bytes"] == 3 * info["bytes"], info
        sums = ray_tpu.get(
            [m.bcast_recv.remote(group, 0, "t16", 30.0) for m in members], timeout=60
        )
        expected = float(np.asarray(payload).sum())
        assert sums == [expected] * 4
        stats1 = ray_tpu.get(members[0].coll_stats.remote(), timeout=30)
        assert stats1["relay_forwards"] >= 1, stats1
        assert stats1["relay_bytes"] >= info["bytes"], stats1
        # Root fan-out and relay forwards alike rode the direct mailboxes.
        assert _store_objects() == store0
    finally:
        col.destroy_collective_group(group)


# ---------------------------------------------------------------------------
# chaos: sampler SIGKILLed mid-broadcast (seeded kill plan)
# ---------------------------------------------------------------------------


def test_sampler_sigkill_mid_broadcast_names_dead_rank():
    """A seeded kill plan makes one sampler SIGKILL itself while answering
    the fan-out's p2p_ack — mid-broadcast, at a reproducible protocol
    point. The broadcast surfaces CollectiveBroadcastError NAMING the dead
    rank, the surviving ranks complete AND consume their payload, and the
    driver's device-object residents drain back to baseline."""
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.experimental import device_object
    from ray_tpu.util import collective as col

    cluster = Cluster()
    try:
        nodes = [
            cluster.add_node(num_cpus=2, object_store_memory=96 * 1024 * 1024)
            for _ in range(2)
        ]
        cluster.connect()
        cluster.wait_for_nodes()
        samplers = [Member.remote() for _ in range(3)]
        group = "chaosg"
        col.init_collective_group(4, 0, backend="cpu", group_name=group)
        ray_tpu.get(
            [s.init_collective.remote(4, i + 1, "cpu", group) for i, s in enumerate(samplers)],
            timeout=60,
        )
        pids = ray_tpu.get([s.pid.remote() for s in samplers], timeout=60)
        victim_pid = pids[1]  # rank 2 dies
        plan = {
            "rules": [
                {"kind": "kill", "method": ["p2p_ack"], "side": "resp",
                 "after": 0, "times": 1}
            ]
        }
        io = EventLoopThread.get()
        pushed = False
        for n in nodes:
            for w in n.workers.values():
                if w.pid == victim_pid and w.client is not None:
                    io.run(
                        w.client.acall(
                            "chaos_set_plan", {"plan": plan, "seed": 7},
                            timeout=5, retries=0,
                        ),
                        timeout=6,
                    )
                    pushed = True
        assert pushed, "victim worker not found for plan push"

        import jax.numpy as jnp

        ref = ray_tpu.put(
            jnp.arange(65536.0, dtype=jnp.float32), tensor_transport="collective"
        )
        with pytest.raises(CollectiveBroadcastError) as ei:
            device_object.broadcast(ref, group, timeout=30)
        err = ei.value
        assert list(err.failed) == [2], err.failed  # dead rank NAMED
        assert sorted(err.info.get("ok_ranks", [])) == [1, 3], err.info  # survivors completed
        assert isinstance(err, RayTpuError) and not isinstance(err, TimeoutError)
        # Survivors hold the payload: their resolve comes from the inbox.
        vals = ray_tpu.get(
            [samplers[0].consume.remote(ref), samplers[2].consume.remote(ref)],
            timeout=60,
        )
        assert vals == [(0.0, 65536), (0.0, 65536)]
        # Teardown: drop the ref; the driver-held device object frees. The
        # ExceptionInfo must go too — its traceback pins broadcast()'s
        # frame, whose locals include the ref.
        from ray_tpu.experimental.device_object.manager import active_manager

        del ref, err, ei
        gc.collect()
        deadline = time.monotonic() + 30
        mgr = active_manager()
        while mgr.usage()["resident_count"] > 0 and time.monotonic() < deadline:
            time.sleep(0.2)
        usage = mgr.usage()
        assert usage["resident_count"] == 0, usage
        assert usage["spilled_count"] == 0, usage
    finally:
        cluster.shutdown()


def test_mid_tree_relay_sigkill_reparents_orphans():
    """A seeded kill plan SIGKILLs a MID-TREE relay rank at its first
    forward attempt (outbound p2p_data), so its subtree never gets the
    payload from the tree. The broadcast NAMES the dead relay with its
    orphaned subtree, re-delivers the orphan DIRECTLY (flat fallback —
    rank 3 lands in ``retried_ranks`` and succeeds), every survivor
    completes AND consumes, and the driver's residents drain."""
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.experimental import device_object
    from ray_tpu.util import collective as col

    cluster = Cluster()
    try:
        nodes = [
            cluster.add_node(num_cpus=3, object_store_memory=96 * 1024 * 1024)
            for _ in range(2)
        ]
        cluster.connect()
        cluster.wait_for_nodes()
        samplers = [Member.remote() for _ in range(4)]
        group = "chaostree"
        col.init_collective_group(5, 0, backend="cpu", group_name=group)
        ray_tpu.get(
            [s.init_collective.remote(5, i + 1, "cpu", group) for i, s in enumerate(samplers)],
            timeout=60,
        )
        pids = ray_tpu.get([s.pid.remote() for s in samplers], timeout=60)
        # Rank 1 is a RELAY (tree order [0,1,2,3,4]: rank 1 forwards to
        # rank 3). Its first outbound p2p_data IS that forward — the kill
        # fires there, before its own multi-chunk payload completes, so it
        # never acks and its subtree starves.
        victim_pid = pids[0]
        plan = {
            "rules": [
                {"kind": "kill", "method": ["p2p_data"], "side": "send",
                 "after": 0, "times": 1}
            ]
        }
        io = EventLoopThread.get()
        pushed = False
        for n in nodes:
            for w in n.workers.values():
                if w.pid == victim_pid and w.client is not None:
                    io.run(
                        w.client.acall(
                            "chaos_set_plan", {"plan": plan, "seed": 16},
                            timeout=5, retries=0,
                        ),
                        timeout=6,
                    )
                    pushed = True
        assert pushed, "victim worker not found for plan push"

        import jax.numpy as jnp

        n_elems = 448 * 1024  # 1.75 MiB -> 4 chunks: dies mid-payload
        ref = ray_tpu.put(
            jnp.arange(float(n_elems), dtype=jnp.float32),
            tensor_transport="collective",
        )
        with pytest.raises(CollectiveBroadcastError) as ei:
            device_object.broadcast(ref, group, timeout=12)
        err = ei.value
        assert list(err.failed) == [1], err.failed  # dead RELAY named
        reason = err.failed[1]
        assert "orphaned subtree ranks [3]" in reason, reason
        assert "re-delivered directly: [3]" in reason, reason
        assert sorted(err.info.get("ok_ranks", [])) == [2, 3, 4], err.info
        assert 3 in err.info.get("retried_ranks", []), err.info
        assert isinstance(err, RayTpuError) and not isinstance(err, TimeoutError)
        # Survivors — INCLUDING the re-parented orphan rank 3 — consume.
        vals = ray_tpu.get(
            [s.consume.remote(ref) for s in samplers[1:]], timeout=60
        )
        assert vals == [(0.0, n_elems)] * 3
        from ray_tpu.experimental.device_object.manager import active_manager

        del ref, err, ei
        gc.collect()
        deadline = time.monotonic() + 30
        mgr = active_manager()
        while mgr.usage()["resident_count"] > 0 and time.monotonic() < deadline:
            time.sleep(0.2)
        usage = mgr.usage()
        assert usage["resident_count"] == 0, usage
    finally:
        cluster.shutdown()
