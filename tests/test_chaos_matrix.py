"""Cluster-level chaos matrix (ISSUE 13): workloads x seeded fault cells
over a real multi-raylet cluster, the partition_node/heal_node network
tear, and the pinning regression tests for the recovery bugs the matrix
exposed.

Layout (tier-1 budget): ONE module-scoped 3-node cluster hosts the matrix
cells; the full sweep is marked `slow` and a 4-cell deterministic
subset runs in tier-1. The partition/rejoin test builds its own
tiny cluster (it deliberately drives a node through declared-dead, which
must not pollute the shared cluster's GCS state).
"""

import os
import threading
import time

import pytest

import ray_tpu
from chaos_matrix import FAULTS, WORKLOAD_NAMES, assert_cell, run_cell
from ray_tpu._private import chaos
from ray_tpu._private.rpc import EventLoopThread

# Worker processes read config through RAY_TPU_* env only, so the knobs
# that bound recovery budgets must be env-set BEFORE the cluster spawns
# workers (the driver side gets them through _system_config as well).
_ENV_KNOBS = {
    "RAY_TPU_TASK_DONE_ACK_TIMEOUT_S": "2.0",
    "RAY_TPU_RPC_RETRY_BACKOFF_MAX_MS": "500",
    "RAY_TPU_LOST_TASK_SWEEP_INTERVAL_S": "4.0",
    "RAY_TPU_LOST_TASK_AGE_S": "6.0",
}


@pytest.fixture(scope="module")
def chaos_cluster():
    from ray_tpu.cluster_utils import Cluster

    saved = {k: os.environ.get(k) for k in _ENV_KNOBS}
    os.environ.update(_ENV_KNOBS)
    cluster = Cluster()
    try:
        nodes = [
            cluster.add_node(num_cpus=1, object_store_memory=96 * 1024 * 1024)
            for _ in range(3)
        ]
        cluster.connect()
        cluster.wait_for_nodes()
        ctx = {
            "cluster": cluster,
            "nodes": nodes,
            "io": EventLoopThread.get(),
        }
        # Warm the task path once so matrix cells measure recovery, not
        # first-worker spawn.
        @ray_tpu.remote
        def warm():
            return 1

        assert ray_tpu.get([warm.remote() for _ in range(3)], timeout=60) == [1, 1, 1]
        yield ctx
    finally:
        chaos.clear()
        cluster.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# tier-1 deterministic subset: four cells, four fault kinds —
# including ONE crash cell (LLM stream x kill: a seeded plan makes the
# streaming worker SIGKILL itself mid-stream; retry completes the stream).
# ---------------------------------------------------------------------------

_SUBSET = [
    ("pull", "reset"),
    ("broadcast", "dup"),
    ("actors", "delay"),
    ("llm", "kill"),
]


@pytest.mark.parametrize("workload,fault", _SUBSET, ids=[f"{w}x{f}" for w, f in _SUBSET])
def test_matrix_subset(chaos_cluster, workload, fault):
    # The kill cell pays worker respawn, a jax import and the engine's two
    # compiles per crash (up to one crash per armed worker when retries land
    # on armed peers): ~30 s on a quiet box, all of it CPU-bound, so beside
    # the five other xdist workers of a tier-1 run its clock reads the box's
    # load, not the recovery. Here the cell is held to EVENTS: the stream ends
    # with every token (or a typed error), a seeded kill fired, nothing
    # leaked; the clock only catches a hang. test_matrix_full holds the same
    # cell to its 60 s on a quiet box.
    budget = 300.0 if fault == "kill" else 30.0
    res = run_cell(chaos_cluster, workload, fault, seed=13, budget_s=budget)
    assert_cell(res, budget_s=budget)
    if fault != "partition":
        assert res.injected > 0, "cell ran but nothing was injected"


# ---------------------------------------------------------------------------
# the full sweep (slow): every workload x every fault kind
# ---------------------------------------------------------------------------

_FULL = [
    (w, f)
    for w in WORKLOAD_NAMES
    for f in FAULTS
    if (w, f) not in _SUBSET or f == "kill"  # tier-1 has the rest, on the clock too
]


@pytest.mark.slow
@pytest.mark.parametrize("workload,fault", _FULL, ids=[f"{w}x{f}" for w, f in _FULL])
def test_matrix_full(chaos_cluster, workload, fault):
    res = run_cell(chaos_cluster, workload, fault, seed=13, budget_s=60.0)
    assert_cell(res, budget_s=60.0)


# ---------------------------------------------------------------------------
# partition_node / heal_node (satellite) + rejoin-after-dead (pinned bug)
# ---------------------------------------------------------------------------


def test_partition_node_short_tear_and_heal(chaos_cluster):
    """A short tear (under node_death_timeout_s): the severed node's links
    fail fast with ConnectionLost, node-local links stay up, and after
    heal_node the cluster is exactly as before (node never left ALIVE)."""
    cluster, nodes, io = (
        chaos_cluster["cluster"], chaos_cluster["nodes"], chaos_cluster["io"],
    )
    victim = nodes[1]
    cluster.partition_node(victim)
    try:
        # Severed: a peer's RPC to the victim fails fast (no 10s connect spin).
        t0 = time.monotonic()
        with pytest.raises(Exception):
            io.run(
                nodes[0]._peer(victim.node_id, victim.address).acall(
                    "get_state", {}, timeout=3, retries=0
                ),
                timeout=5,
            )
        assert time.monotonic() - t0 < 2.0
    finally:
        cluster.heal_node(victim)
    # Healed: the same call lands.
    st = io.run(
        nodes[0]._peer(victim.node_id, victim.address).acall(
            "get_state", {}, timeout=10
        ),
        timeout=15,
    )
    assert st["node_id"] == victim.node_id
    # And the GCS still lists every node ALIVE (tear was under the death
    # timeout).
    alive = sum(1 for n in cluster.gcs.nodes.values() if n["state"] == "ALIVE")
    assert alive == len(nodes)


def test_partition_outlives_death_timeout_then_rejoins():
    """PINNED RECOVERY BUG: a partition that outlives node_death_timeout_s
    gets the node declared DEAD; on heal the raylet's next heartbeat is
    answered with dead=True, and an IN-PROCESS raylet used to os._exit(1)
    — killing the whole host process (driver, GCS, and every sibling node
    with it). Now it REJOINS: re-registers under its node id, republishes
    its object locations, and serves traffic again."""
    from ray_tpu._private import config as config_mod
    from ray_tpu._private import worker_context
    from ray_tpu.cluster_utils import Cluster

    # This test builds its own cluster (declared-dead must not pollute the
    # shared module cluster's GCS); snapshot the module cluster's driver
    # context + config so they survive this cluster's init/shutdown.
    prev_cw = worker_context.get_core_worker_if_initialized()
    prev_cfg = config_mod._config
    cluster = Cluster(
        _system_config={"node_death_timeout_s": 1.2, "heartbeat_interval_s": 0.3}
    )
    try:
        nodes = [cluster.add_node(num_cpus=1) for _ in range(2)]
        cluster.connect()
        cluster.wait_for_nodes()
        victim = nodes[1]
        cluster.partition_node(victim)
        try:
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if cluster.gcs.nodes[victim.node_id]["state"] == "DEAD":
                    break
                time.sleep(0.1)
            assert cluster.gcs.nodes[victim.node_id]["state"] == "DEAD"
        finally:
            cluster.heal_node(victim)
        # The raylet heartbeats into the dead verdict and rejoins (before
        # the fix: os._exit(1) here killed this very test process).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if cluster.gcs.nodes[victim.node_id]["state"] == "ALIVE":
                break
            time.sleep(0.1)
        assert cluster.gcs.nodes[victim.node_id]["state"] == "ALIVE", (
            "severed node did not rejoin after heal"
        )
        # The rejoined cluster schedules work end to end.
        @ray_tpu.remote(max_retries=4)
        def ping():
            return os.getpid()

        assert ray_tpu.get([ping.remote() for _ in range(4)], timeout=60)
    finally:
        cluster.shutdown()
        with config_mod._config_lock:
            config_mod._config = prev_cfg
        if prev_cw is not None:
            worker_context.set_core_worker(prev_cw)


# ---------------------------------------------------------------------------
# runtime plan control (satellite): chaos_set_plan RPC + worker fan-out
# ---------------------------------------------------------------------------


def test_chaos_set_plan_broadcast_reaches_workers(chaos_cluster):
    """The raylet's chaos_set_plan RPC with broadcast=True installs the
    plan in its WORKER processes (verified from inside a task) and clears
    it the same way — faults are flippable mid-workload."""
    nodes, io = chaos_cluster["nodes"], chaos_cluster["io"]

    @ray_tpu.remote
    def plan_active():
        from ray_tpu._private import chaos as _c

        return _c.active() is not None

    # Ensure at least one worker is up, then fan the plan out on every node.
    assert ray_tpu.get(plan_active.remote(), timeout=30) is False
    reached = 0
    plan = {"rules": [{"kind": "delay", "method": "no_such_method", "times": 1}]}
    for n in nodes:
        resp = io.run(
            n.rpc_chaos_set_plan({"plan": plan, "seed": 5, "broadcast": True})
        )
        assert resp["ok"]
        reached += resp["workers_reached"]
    try:
        assert reached >= 1
        assert ray_tpu.get(plan_active.remote(), timeout=30) is True
    finally:
        for n in nodes:
            io.run(n.rpc_chaos_set_plan({"plan": None, "broadcast": True}))
        chaos.clear()  # the in-process raylet handler also set the driver plan
    assert ray_tpu.get(plan_active.remote(), timeout=30) is False


# ---------------------------------------------------------------------------
# pinned recovery bugs (found by the matrix, fixed in this PR)
# ---------------------------------------------------------------------------


def test_silently_dropped_task_done_heals_within_ack_budget(chaos_cluster):
    """PINNED RECOVERY BUG: a task_done/tasks_done one-way frame lost
    WITHOUT a connection reset (receiver drop; chaos drop models it) used
    to hang the owner's get() forever on the lease path — the worker's
    send_nowait future never resolves, nothing re-delivered, and the
    owner's lease probe pings the WORKER, which is alive. The ack watchdog
    (task_done_ack_timeout_s) now re-delivers through the acked retrying
    path; the owner drops the duplicate by cid."""
    nodes, io = chaos_cluster["nodes"], chaos_cluster["io"]

    @ray_tpu.remote
    def work():
        return "done"

    # Warm a worker, then make every worker drop its next completion frame.
    assert ray_tpu.get(work.remote(), timeout=30) == "done"
    worker_plan = {
        "rules": [
            {"kind": "drop", "method": ["tasks_done", "task_done"], "times": 1}
        ]
    }
    pushed = 0
    for n in nodes:
        for w in n.workers.values():
            if w.client is not None and w.state not in ("starting", "dead"):
                try:
                    io.run(w.client.acall(
                        "chaos_set_plan", {"plan": worker_plan}, timeout=5, retries=0
                    ), timeout=6)
                    pushed += 1
                except Exception:
                    pass
    assert pushed >= 1
    try:
        t0 = time.monotonic()
        # Ack timeout is 2s (module env): the dropped frame re-delivers in
        # ~2s — far under the 15s lease failover / lost-task sweep, and not
        # the forever-hang it used to be.
        assert ray_tpu.get(work.remote(), timeout=30) == "done"
        assert time.monotonic() - t0 < 12.0
    finally:
        for n in nodes:
            for w in n.workers.values():
                if w.client is not None and w.state not in ("starting", "dead"):
                    try:
                        io.run(w.client.acall(
                            "chaos_set_plan", {"plan": None}, timeout=5, retries=0
                        ), timeout=6)
                    except Exception:
                        pass


def test_duplicated_actor_call_executes_once(chaos_cluster):
    """PINNED RECOVERY BUG: a duplicated actor_call frame (at-least-once
    wire; chaos dup models it) used to EXECUTE THE METHOD TWICE — actor
    state mutated twice per call. The worker now tombstones received task
    ids and answers duplicates from its result cache."""
    @ray_tpu.remote
    class Acc:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    a = Acc.remote()
    try:
        assert ray_tpu.get(a.bump.remote(), timeout=30) == 1  # warm
        chaos.install(
            {"rules": [{"kind": "dup", "method": "actor_call", "times": 2}]},
            seed=3,
        )
        try:
            assert ray_tpu.get(a.bump.remote(), timeout=30) == 2
            assert ray_tpu.get(a.bump.remote(), timeout=30) == 3
        finally:
            chaos.clear()
        # State advanced exactly once per call despite duplicated frames.
        assert ray_tpu.get(a.bump.remote(), timeout=30) == 4
    finally:
        ray_tpu.kill(a)


def test_dropped_actor_call_heals_by_probe_resend(chaos_cluster):
    """PINNED RECOVERY BUG: an actor_call frame silently lost (connection
    up, no reset) used to park the call FOREVER — no timeout, no sweep
    covers actor calls. The owner now probes the worker over the same FIFO
    connection after each unacked interval; 'never received' proves loss
    and triggers a deduped resend."""
    @ray_tpu.remote
    class Echo:
        def ping(self, x):
            return x

    a = Echo.remote()
    try:
        assert ray_tpu.get(a.ping.remote(1), timeout=30) == 1  # warm
        chaos.install(
            {"rules": [{"kind": "drop", "method": "actor_call", "times": 1}]},
            seed=4,
        )
        try:
            t0 = time.monotonic()
            # Ack interval is 2s (module env): loss heals in ~2-4s, not never.
            assert ray_tpu.get(a.ping.remote(2), timeout=30) == 2
            assert time.monotonic() - t0 < 15.0
        finally:
            chaos.clear()
    finally:
        ray_tpu.kill(a)


def test_lost_register_actor_reply_is_idempotent(chaos_cluster):
    """PINNED RECOVERY BUG: actor registration had no ack bound — a lost
    register_actor reply parked .remote() forever — and the naive retry
    would have scheduled a SECOND creation (the GCS handler re-ran its
    body). Now the retry is served the remembered outcome and exactly one
    actor serves calls."""
    @ray_tpu.remote
    class One:
        def who(self):
            return os.getpid()

    chaos.install(
        {"rules": [{"kind": "drop", "method": "register_actor", "side": "resp",
                    "times": 1}]},
        seed=6,
    )
    try:
        t0 = time.monotonic()
        a = One.remote()  # first reply dropped; bounded retry lands
        pids = {ray_tpu.get(a.who.remote(), timeout=30) for _ in range(3)}
        assert len(pids) == 1
        assert time.monotonic() - t0 < 40.0
    finally:
        chaos.clear()
        ray_tpu.kill(a)


def test_push_commit_reply_lost_retry_serves_remembered_outcome(chaos_cluster):
    """Partition/reset during push_commit: the first commit reply is
    dropped (side=resp), the sender's bounded retry must be served the
    REMEMBERED outcome (raylet._commit_results) — the push completes and
    the replica is intact, instead of a guessed verdict or a hang."""
    import numpy as np

    from chaos_matrix import _free_all, _oid, _seal_raw

    nodes, io = chaos_cluster["nodes"], chaos_cluster["io"]
    data = np.random.default_rng(99).integers(0, 255, 2 * 1024 * 1024,
                                              dtype=np.uint8).tobytes()
    oid = _oid("commitretry")
    chaos.install(
        {"rules": [{"kind": "drop", "method": "push_commit", "side": "resp",
                    "times": 1}]},
        seed=2,
    )
    try:
        _seal_raw(io, nodes[0], oid, data)
        resp = io.run(
            nodes[0].push_manager.push(
                oid, nodes[1].node_id, nodes[1].address, timeout=8.0
            ),
            timeout=30,
        )
        assert resp["ok"], resp
        offset, size = io.run(nodes[1].store.get(oid))
        try:
            assert bytes(nodes[1].arena.read(offset, size)) == data
        finally:
            nodes[1].store.release(oid)
    finally:
        chaos.clear()
        _free_all(nodes, oid)
