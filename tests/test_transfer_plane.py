"""Transfer-plane overhaul (ISSUE 10): striped pulls with ranked failover,
the pull admission byte budget, raw-frame negotiation fallback, chunk
boundary bit-exactness on the real node-to-node path, and cut-through
broadcast relays.

One module-scoped cluster (tier-1 budget: a cluster per test would dominate
wall time); the multi-node broadcast sweep builds its own wider cluster and
is marked `slow`. Node "SIGKILL" is simulated with Cluster.remove_node —
the in-process multi-raylet cluster is the reference's
multi-node-without-a-cluster trick, and remove_node is its node-death lever
(cluster_utils.py).
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.config import get_config
from ray_tpu._private.rpc import EventLoopThread
from ray_tpu._private.transfer_stats import TRANSFER

CHUNK = get_config().object_transfer_chunk_bytes


def _oid(tag: str) -> str:
    """Deterministic, valid ObjectID hex (the native store index decodes
    ids from hex, so test ids must be real 28-byte hex strings)."""
    return tag.encode().hex().ljust(56, "0")[:56]


@pytest.fixture(scope="module")
def transfer_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    nodes = [
        cluster.add_node(num_cpus=1, object_store_memory=192 * 1024 * 1024)
        for _ in range(4)
    ]
    cluster.connect()
    cluster.wait_for_nodes()
    yield cluster, nodes
    cluster.shutdown()


def _io():
    return EventLoopThread.get()


def _seal_raw(node, oid: str, data: bytes):
    """Plant an exact-size object straight in a node's store (ray_tpu.put
    adds serialization framing; wire-boundary tests need byte-exact sizes)."""
    io = _io()
    offset = io.run(node.store.create(oid, len(data)))
    assert offset is not None
    node.arena.write(offset, data)
    node.store.seal(oid)
    io.run(
        node.gcs.acall(
            "add_object_location", {"object_id": oid, "node_id": node.node_id}
        )
    )


def _read_copy(node, oid: str) -> bytes:
    io = _io()
    offset, size = io.run(node.store.get(oid))
    try:
        return bytes(node.arena.read(offset, size))
    finally:
        node.store.release(oid)


def _broadcast(root, oid: str, targets, timeout=120.0):
    return _io().run(
        root.rpc_broadcast_object(
            {
                "object_id": oid,
                "targets": [
                    {"node_id": n.node_id, "address": list(n.address)} for n in targets
                ],
                "timeout": timeout,
            }
        ),
        timeout=timeout,
    )


def _free(nodes, oid: str):
    for n in nodes:
        try:
            n.store.delete(oid)
        except Exception:
            pass


@pytest.mark.parametrize(
    "size", [1, CHUNK - 1, CHUNK, CHUNK + 1], ids=["1B", "chunk-1", "chunk", "chunk+1"]
)
def test_push_bit_exact_at_chunk_boundaries(transfer_cluster, size):
    """Raw-frame push lands bit-exact for sizes straddling chunk edges."""
    cluster, nodes = transfer_cluster
    head, target = nodes[0], nodes[1]
    rng = np.random.default_rng(size)
    data = rng.integers(0, 255, size, dtype=np.uint8).tobytes()
    oid = _oid(f"boundary{size}")
    raw_before = TRANSFER.chunks_raw_out
    _seal_raw(head, oid, data)
    resp = _broadcast(head, oid, [target])
    assert resp["ok"], resp
    assert _read_copy(target, oid) == data
    # The negotiated default on this cluster IS the raw path.
    assert TRANSFER.chunks_raw_out > raw_before
    _free(nodes, oid)


def test_push_negotiation_falls_back_to_msgpack(transfer_cluster):
    """A receiver that does not advertise raw (mixed-version peer /
    transfer_raw_frames=False) gets the object over msgpack chunks —
    bit-exact, no raw frames on the session."""
    cluster, nodes = transfer_cluster
    head, target = nodes[0], nodes[2]
    data = np.arange(CHUNK + 123, dtype=np.uint8).tobytes()
    oid = _oid("fallback")
    _seal_raw(head, oid, data)
    target.raw_frames_enabled = False
    raw_before = TRANSFER.chunks_raw_out
    mp_before = TRANSFER.chunks_msgpack_out
    try:
        resp = _broadcast(head, oid, [target])
        assert resp["ok"], resp
        assert _read_copy(target, oid) == data
        assert TRANSFER.chunks_msgpack_out > mp_before
        assert TRANSFER.chunks_raw_out == raw_before
    finally:
        target.raw_frames_enabled = True
    _free(nodes, oid)


def test_pull_stripes_across_two_replicas(transfer_cluster):
    """A pull with two known locations fetches chunks from BOTH (striping),
    and the result is bit-exact."""
    cluster, nodes = transfer_cluster
    head, replica, puller = nodes[0], nodes[1], nodes[3]
    data = np.random.default_rng(7).integers(
        0, 255, 16 * 1024 * 1024, dtype=np.uint8
    ).tobytes()
    oid = _oid("striped")
    _seal_raw(head, oid, data)
    resp = _broadcast(head, oid, [replica])
    assert resp["ok"], resp
    sources_before = TRANSFER.pull_sources
    ok = _io().run(puller.pull_manager.pull(oid, 60.0), timeout=90)
    assert ok
    assert _read_copy(puller, oid) == data
    assert TRANSFER.pull_sources - sources_before == 2
    _free(nodes, oid)


def test_pull_completes_when_source_node_dies_mid_pull(transfer_cluster):
    """Chaos (the ISSUE 10 satellite): kill a source node while it is
    serving chunks of an in-flight pull. The pull manager demotes the dead
    source and completes from the surviving replica."""
    cluster, nodes = transfer_cluster
    head, puller = nodes[0], nodes[3]
    victim = cluster.add_node(num_cpus=1, object_store_memory=192 * 1024 * 1024)
    cluster.wait_for_nodes()
    data = np.random.default_rng(13).integers(
        0, 255, 32 * 1024 * 1024, dtype=np.uint8
    ).tobytes()
    oid = _oid("failover")
    _seal_raw(head, oid, data)
    assert _broadcast(head, oid, [victim])["ok"]

    # Slow the victim's chunk serving so the kill is guaranteed mid-pull,
    # and flag the first chunk request so the kill happens only once the
    # victim is actually serving this pull.
    serving = threading.Event()
    orig = victim.server._handlers["fetch_object_chunk"]

    async def slow_fetch(req):
        serving.set()
        await asyncio.sleep(0.4)
        return await orig(req)

    victim.server._handlers["fetch_object_chunk"] = slow_fetch

    demotions_before = TRANSFER.source_demotions
    pull_fut = _io().spawn(puller.pull_manager.pull(oid, 120.0))
    assert serving.wait(timeout=30), "victim never served a chunk"
    cluster.remove_node(victim)  # node death mid-pull
    assert pull_fut.result(timeout=120)
    assert _read_copy(puller, oid) == data
    assert TRANSFER.source_demotions > demotions_before
    _free(nodes, oid)


def test_pull_admission_budget_stalls_and_completes(transfer_cluster):
    """Two concurrent pulls larger than the byte budget: the second queues
    (admission_stall flight event + counter) instead of over-committing the
    arena, then runs when the first releases its reservation."""
    from ray_tpu._private import flight_recorder

    cluster, nodes = transfer_cluster
    head, puller = nodes[0], nodes[3]
    datas, oids = [], []
    for i in range(2):
        data = np.random.default_rng(20 + i).integers(
            0, 255, 12 * 1024 * 1024, dtype=np.uint8
        ).tobytes()
        oid = _oid(f"admit{i}")
        _seal_raw(head, oid, data)
        datas.append(data)
        oids.append(oid)

    stalls_before = TRANSFER.admission_stalls
    budget_before = puller.pull_manager.budget
    puller.pull_manager.budget = 8 * 1024 * 1024  # < one object
    try:
        io = _io()
        futs = [io.spawn(puller.pull_manager.pull(oid, 120.0)) for oid in oids]
        assert all(f.result(timeout=120) for f in futs)
    finally:
        puller.pull_manager.budget = budget_before
    for oid, data in zip(oids, datas):
        assert _read_copy(puller, oid) == data
    assert TRANSFER.admission_stalls > stalls_before
    events = (flight_recorder.dump() or {"events": []})["events"]
    assert any(e["type"] == "admission_stall" for e in events)
    for oid in oids:
        _free(nodes, oid)


def test_cut_through_relay_forwards_before_seal(transfer_cluster):
    """Broadcast through a relay chain records transfer_relay (the child
    began forwarding from its in-flight session, not after sealing) and
    every node ends bit-exact."""
    from ray_tpu._private import flight_recorder

    cluster, nodes = transfer_cluster
    head, targets = nodes[0], nodes[1:4]
    data = np.random.default_rng(42).integers(
        0, 255, 20 * 1024 * 1024, dtype=np.uint8
    ).tobytes()
    oid = _oid("cutthru")
    relays_before = TRANSFER.relays
    _seal_raw(head, oid, data)
    resp = _broadcast(head, oid, targets)
    assert resp["ok"], resp
    for t in targets:
        assert _read_copy(t, oid) == data
    # 3 targets -> binomial split (child+1-subtree, child+0) -> >=1 relay.
    assert TRANSFER.relays > relays_before
    events = (flight_recorder.dump() or {"events": []})["events"]
    assert any(e["type"] == "transfer_relay" for e in events)
    _free(nodes, oid)


@pytest.mark.slow
def test_broadcast_sweep_many_nodes():
    """Wider cut-through sweep: 8 nodes, 32 MiB, every copy bit-exact and
    aggregate throughput recorded. Slow-marked: tier-1 is past its wall
    budget."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.object_transfer import broadcast_object

    cluster = Cluster()
    try:
        for _ in range(8):
            cluster.add_node(num_cpus=1, object_store_memory=96 * 1024 * 1024)
        cluster.connect()
        cluster.wait_for_nodes()
        data = np.random.default_rng(0).integers(
            0, 255, 32 * 1024 * 1024, dtype=np.uint8
        )
        ref = ray_tpu.put(data)
        t0 = time.perf_counter()
        pushed = broadcast_object(ref, timeout=600)
        dt = time.perf_counter() - t0
        assert pushed == 7
        out = ray_tpu.get(ref)
        np.testing.assert_array_equal(np.asarray(out), data)
        assert dt < 600
    finally:
        cluster.shutdown()
