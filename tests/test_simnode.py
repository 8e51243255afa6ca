"""Control-plane scale harness tests (ISSUE 19).

Tier-1 coverage for the sim-mode shells (_private/simnode), the GCS fan-in
hardening they exist to exercise (versioned delta heartbeat sync, per-node
location index, drop-oldest task-event ring), the jittered rejoin backoff,
and locality-aware placement on the REAL raylet path. The 1k-node sweep and
chaos-at-scale cells are marked `slow` (tier-2); tier-1 keeps a 128-shell
smoke that boots, converges, and pushes 10k stub tasks in well under 30s.
"""

import asyncio
import random
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.config import init_config
from ray_tpu._private.raylet import apply_heartbeat_view, rejoin_backoff_delay
from ray_tpu._private.sched_core import create_sched_core
from ray_tpu._private.simnode import SimCluster, SimTraffic


# ---------------------------------------------------------------------------
# Sim smoke (tier-1): module-scoped cluster — boot once, share across tests.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sim_cluster():
    c = SimCluster(
        128,
        resources_per_node={"CPU": 8},
        num_entry_nodes=16,
        _system_config={
            "heartbeat_interval_s": 0.25,
            "node_death_timeout_s": 2.0,
            "rejoin_backoff_base_s": 0.02,
            "rejoin_backoff_max_s": 0.5,
        },
    )
    c.start()
    c.wait_for_view(timeout=60)
    try:
        yield c
    finally:
        c.shutdown()


def test_sim_smoke_128_shells_10k_tasks(sim_cluster):
    """128 shells over the real GCS wire push 10k stub tasks inside the
    tier-1 budget. Every shell's delta-synced view converged (fixture), and
    placement throughput holds four digits even on a 1-core box."""
    c = sim_cluster
    base = c.done_count
    n = 10_000

    async def _burst():
        step = 500
        for i in range(0, n, step):
            await asyncio.gather(
                *[c.asubmit(c.make_spec(sim_ms=1.0)) for _ in range(step)]
            )

    t0 = time.monotonic()
    c._io.run(_burst(), timeout=120)
    assert c.wait_done(base + n, timeout=60)
    wall = time.monotonic() - t0
    assert wall < 30.0, f"10k stub tasks took {wall:.1f}s (budget 30s)"
    assert all(len(node.cluster_view) == 128 for node in c.nodes[:8])


def test_sim_heartbeats_are_delta_synced(sim_cluster):
    """Steady state: idle heartbeats carry ZERO view rows — the O(N^2)
    bytes/interval hot spot is gone. A fresh shell's first contact is the
    only full-view reply in the window."""
    c = sim_cluster
    time.sleep(0.6)  # let any task-burst availability churn settle
    c.gcs.hb_stats = {"replies": 0, "rows": 0, "full_replies": 0, "view_bytes": 0}
    c.gcs.hb_account = True
    time.sleep(1.0)
    c.gcs.hb_account = False
    hb = c.gcs.hb_stats
    assert hb["replies"] >= 128, hb  # everyone beat at least once
    assert hb["full_replies"] == 0, hb
    assert hb["rows"] == 0, hb  # idle deltas are EMPTY
    assert hb["view_bytes"] == 0, hb


def test_sim_closed_loop_traffic_no_untyped_failures(sim_cluster):
    stats = SimTraffic(
        sim_cluster, users=8, pattern="diurnal", think_s=0.01,
        sim_ms=2.0, task_timeout_s=5.0, seed=5,
    ).run(1.5)
    assert stats["completed"] > 50
    assert stats["failures"] == {}, stats


def test_sim_ref_arg_tasks_land_on_their_holder(sim_cluster):
    """Every task whose reference arg lives on one shell is placed ON that
    shell, through whichever entry shell it was submitted to, and each such
    placement is counted (``locality_hits``) by the shell that made it."""
    c = sim_cluster
    holders = c.nodes[32:40]
    pairs = []
    for i, h in enumerate(holders):
        oid = f"b{i:055x}"
        c.seed_object(h, oid)
        pairs.append((oid, h.node_id))
    time.sleep(0.5)  # holder rows settle into the entry shells' caches
    hits0 = sum(n.locality_hits for n in c.nodes)
    n_tasks = 64

    async def _ref_burst():
        futs = []
        for i in range(n_tasks):
            oid, holder = pairs[i % len(pairs)]
            spec = c.make_spec(args=[("r", oid, None)], sim_ms=2.0)
            fut = c.register_waiter(spec.task_id)
            await c.asubmit(spec)
            futs.append((fut, holder))
        return [(await asyncio.wait_for(fut, 30), holder) for fut, holder in futs]

    landed = c._io.run(_ref_burst(), timeout=120)
    assert all(ran_on == holder for ran_on, holder in landed), landed
    assert sum(n.locality_hits for n in c.nodes) - hits0 > 0


@pytest.mark.parametrize(
    "key", ["heartbeat_delta_sync", "gcs_location_index", "locality_aware_scheduling"]
)
def test_a_removed_baseline_arm_is_an_unknown_config_key(key):
    """The full-view heartbeat, the full-scan node death and the no-locality
    placement are gone with the options that selected them: naming one is
    refused like any other unknown key."""
    from ray_tpu._private.config import Config

    with pytest.raises(ValueError, match="Unknown system config key"):
        Config().apply_overrides({key: False})


# ---------------------------------------------------------------------------
# Delta-sync protocol edges (satellite 3)
# ---------------------------------------------------------------------------


def test_delta_resync_after_missed_generations():
    """A client whose view version predates the pruned tombstone floor must
    get a FULL view resync — deltas would silently skip removals it never
    saw. Driven against a live GCS over the wire via one sim shell."""
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.rpc import RpcClient

    init_config({"heartbeat_interval_s": 30.0, "node_death_timeout_s": 120.0})
    gcs = GcsServer()
    cli = RpcClient(gcs.address, label="t-resync")
    try:
        for i in range(3):
            cli.call(
                "register_node",
                {
                    "node_id": f"n{i}",
                    "address": ["127.0.0.1", 10000 + i],
                    "resources": {"CPU": 1},
                },
                timeout=10,
            )
        # First contact: version 0 is always a full resync.
        r = cli.call("heartbeat", {"node_id": "n0", "view_version": 0}, timeout=10)
        assert r["view_full"] is True
        ver = r["view_version"]
        assert set(r["view"]) == {"n0", "n1", "n2"}

        # Still-current client gets an EMPTY delta.
        r = cli.call("heartbeat", {"node_id": "n0", "view_version": ver}, timeout=10)
        assert r["view_full"] is False and r["view"] == {} and r["view_removed"] == []

        # Age the tombstone history past its bound: the old version now
        # predates the pruned floor.
        for j in range(1100):
            gcs._bump_view(f"ghost{j}", removed=True)
        assert gcs._removals_floor > ver
        r = cli.call("heartbeat", {"node_id": "n0", "view_version": ver}, timeout=10)
        assert r["view_full"] is True, "pruned-floor client must full-resync"

        # A client "from the future" (GCS restarted, versions reset) also
        # falls back to a full view instead of a bogus delta.
        r = cli.call(
            "heartbeat",
            {"node_id": "n0", "view_version": r["view_version"] + 999},
            timeout=10,
        )
        assert r["view_full"] is True
    finally:
        cli.close()
        gcs.stop()


def test_stale_view_echo_never_clobbers_local_ledger():
    """The never-self guard: a heartbeat delta carrying a STALE row for this
    node (pre-acquire availability echoed back) must not overwrite the local
    ledger — in-flight acquires are authoritative."""

    class Shell:
        pass

    node = Shell()
    node.node_id = "me"
    node.cluster_view = {}
    node._synced_peers = set()
    node._view_version = 0
    node._sched = create_sched_core()
    node._sched.node_upsert("me", {"CPU": 4}, {"CPU": 4})
    assert node._sched.try_acquire("me", {"CPU": 3})  # in-flight work

    stale_echo = {
        "view": {
            "me": {
                "address": ["127.0.0.1", 1],
                "resources_total": {"CPU": 4},
                "resources_available": {"CPU": 4},  # pre-acquire lie
                "labels": {},
                "state": "ALIVE",
            },
            "peer": {
                "address": ["127.0.0.1", 2],
                "resources_total": {"CPU": 2},
                "resources_available": {"CPU": 2},
                "labels": {},
                "state": "ALIVE",
            },
        },
        "view_removed": [],
        "view_full": True,
        "view_version": 7,
    }
    apply_heartbeat_view(stale_echo, node)
    assert node._view_version == 7
    # Self: untouched — the acquire survives the echo.
    assert node._sched.node_avail("me", "CPU") == pytest.approx(1.0)
    # Peer: mirrored.
    assert node._sched.node_avail("peer", "CPU") == pytest.approx(2.0)

    # Removal tombstones drop peers from the mirror — but never self.
    apply_heartbeat_view(
        {"view": {}, "view_removed": ["peer"], "view_full": False,
         "view_version": 8},
        node,
    )
    assert "peer" not in node.cluster_view
    assert node._sched.node_avail("me", "CPU") == pytest.approx(1.0)
    node._sched.close()


def test_optimistic_debit_expires_when_no_delta_arrives():
    """The scale harness caught this: under delta sync a forward-time mirror
    debit is only overwritten when the peer's row CHANGES at the GCS. A peer
    that acquires and releases between its own heartbeats never changes its
    row, no delta arrives, and the debit would stick forever — the forwarder
    permanently under-estimates an idle peer. The ledger must credit it back
    after its deadline; an authoritative row must cancel it instead."""
    from ray_tpu._private.raylet import OptimisticDebitLedger

    sched = create_sched_core()
    sched.node_upsert("peer", {"CPU": 2}, {"CPU": 2})

    # Expiry path: debit, no delta ever arrives, deadline passes → credited.
    ledger = OptimisticDebitLedger()
    assert sched.try_acquire("peer", {"CPU": 1})
    ledger.note("peer", {"CPU": 1}, interval_s=0.02)
    assert sched.node_avail("peer", "CPU") == pytest.approx(1.0)
    time.sleep(0.15)  # past the 2.5x-interval deadline (interval floor 0.05)
    ledger.expire(sched)
    assert sched.node_avail("peer", "CPU") == pytest.approx(2.0)

    # Authoritative-row path: a delta for the peer supersedes the debit —
    # expire() afterwards must NOT double-credit on top of the fresh row.
    assert sched.try_acquire("peer", {"CPU": 1})
    ledger.note("peer", {"CPU": 1}, interval_s=0.02)
    ledger.on_authoritative_rows({"peer"})
    sched.node_upsert("peer", {"CPU": 2}, {"CPU": 0.5})  # the real row
    time.sleep(0.15)
    ledger.expire(sched)
    assert sched.node_avail("peer", "CPU") == pytest.approx(0.5)

    # A late credit for a tombstoned node is harmless (release no-ops).
    ledger.note("ghost", {"CPU": 1}, interval_s=0.02)
    time.sleep(0.15)
    ledger.expire(sched)
    sched.close()


# ---------------------------------------------------------------------------
# Rejoin backoff (satellite 1)
# ---------------------------------------------------------------------------


def test_rejoin_backoff_delay_jitters_and_caps():
    cfg = init_config({"rejoin_backoff_base_s": 0.05, "rejoin_backoff_max_s": 2.0})
    rng = random.Random(42)
    # Full jitter: attempt k draws uniform [0, min(max, base*2^k)].
    for attempt, ceiling in [(0, 0.05), (1, 0.1), (3, 0.4), (10, 2.0)]:
        draws = [rejoin_backoff_delay(attempt, cfg, rng) for _ in range(200)]
        assert all(0 <= d <= ceiling + 1e-9 for d in draws), (attempt, max(draws))
        assert max(draws) > ceiling * 0.8  # actually spans the range
    # Distinct node seeds de-correlate: two raylets don't retry in lockstep.
    a = [rejoin_backoff_delay(2, cfg, random.Random("node-a")) for _ in range(8)]
    b = [rejoin_backoff_delay(2, cfg, random.Random("node-b")) for _ in range(8)]
    assert a != b


def test_gcs_restart_rejoin_storm_no_duplicate_rows():
    """Restart the GCS under 3 REAL raylets: every raylet hits `unknown` on
    its next heartbeat and rejoins with jittered backoff. Afterwards: same
    node ids, no duplicate rows, and sealed-object locations republished."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(
        _system_config={
            "heartbeat_interval_s": 0.2,
            "node_death_timeout_s": 5.0,
            "rejoin_backoff_base_s": 0.02,
            "rejoin_backoff_max_s": 0.3,
        }
    )
    try:
        for _ in range(3):
            cluster.add_node(num_cpus=1)
        cluster.connect()
        cluster.wait_for_nodes()
        ids_before = {n.node_id for n in cluster.nodes}

        ref = ray_tpu.put(np.zeros(300 * 1024, dtype=np.uint8))  # plasma-sized
        oid = ref.hex()

        cluster.restart_gcs()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            alive = {
                nid
                for nid, n in cluster.gcs.nodes.items()
                if n["state"] == "ALIVE"
            }
            if alive == ids_before:
                break
            time.sleep(0.1)
        assert set(cluster.gcs.nodes) == ids_before, "duplicate/lost node rows"
        assert all(n["state"] == "ALIVE" for n in cluster.gcs.nodes.values())

        # Location rows for the sealed object came back via the republish.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if cluster.gcs.object_locations.get(oid):
                break
            time.sleep(0.1)
        assert cluster.gcs.object_locations.get(oid), "locations not republished"
        # And the object is still fetchable end to end.
        assert ray_tpu.get(ref, timeout=60).nbytes == 300 * 1024
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# Locality-aware scheduling on the REAL raylet path (tentpole acceptance)
# ---------------------------------------------------------------------------


def test_locality_task_lands_on_holder_and_spills_when_saturated():
    """A task whose plasma-sized arg lives on node B runs ON node B
    (flight-evidenced via locality_hit), and when B is saturated the same
    shape spills to another node instead of queueing behind B."""
    from ray_tpu._private import flight_recorder
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(
        _system_config={
            "heartbeat_interval_s": 0.2,
            "locality_cache_ttl_s": 0.2,
        }
    )
    try:
        cluster.add_node(num_cpus=1)
        n2 = cluster.add_node(num_cpus=1)
        cluster.add_node(num_cpus=1)
        cluster.connect()
        cluster.wait_for_nodes()

        @ray_tpu.remote
        def produce():
            return np.ones(300 * 1024, dtype=np.uint8)  # > inline cutoff

        @ray_tpu.remote
        def consume(x):
            import os

            return (int(x[0]), os.environ.get("RAY_TPU_NODE_ID"))

        @ray_tpu.remote
        def hog():
            time.sleep(4.0)
            return 1

        from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

        big = produce.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(node_id=n2.node_id)
        ).remote()
        # fetch_local=False: the holder keeps the ONLY copy, or locality would
        # have two nodes to choose from.
        assert ray_tpu.wait([big], timeout=60, fetch_local=False) == ([big], [])
        # Deterministic settle: the head's MIRROR of the holder must show a
        # free CPU again (produce released it; the delta takes ~2 heartbeat
        # intervals to propagate) or locality would correctly refuse a
        # saturated holder and the assertion below would test the race, not
        # the policy.
        head = cluster.nodes[0]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if head._sched.node_avail(n2.node_id, "CPU") >= 1.0:
                break
            time.sleep(0.05)
        time.sleep(0.2)  # location row publish

        val, ran_on = ray_tpu.get(consume.remote(big), timeout=60)
        assert val == 1
        assert ran_on == n2.node_id, "large-arg task must land on the holder"
        evs = (flight_recorder.dump() or {}).get("events", [])
        assert any(e["type"] == "locality_hit" for e in evs), (
            "locality placement must leave flight evidence"
        )

        # The first consume leased a worker ON the holder; a cached idle
        # lease would satisfy the next submit without consulting placement
        # at all (and still hold the holder's CPU). Wait for the idle-lease
        # release so the spill phase exercises the scheduler, not the cache.
        from ray_tpu._private import worker_context

        lm = worker_context.get_core_worker_if_initialized()._lease_mgr
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if lm is None or not any(s.leases for s in lm._shapes.values()):
                break
            time.sleep(0.1)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:  # holder idle again, mirror caught up
            if head._sched.node_avail(n2.node_id, "CPU") >= 1.0:
                break
            time.sleep(0.05)

        # Saturate the holder, resubmit the same shape: it must SPILL to a
        # different node, not camp on B's queue.
        blocker = hog.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(node_id=n2.node_id)
        ).remote()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:  # holder saturation visible at head
            if head._sched.node_avail(n2.node_id, "CPU") < 1.0:
                break
            time.sleep(0.05)
        t0 = time.monotonic()
        val, ran_on = ray_tpu.get(consume.remote(big), timeout=60)
        spill_wall = time.monotonic() - t0
        assert val == 1
        assert ran_on != n2.node_id, "saturated holder: task must spill"
        assert spill_wall < 3.5, (
            f"spill took {spill_wall:.1f}s — it queued behind the hog instead"
        )
        assert ray_tpu.get(blocker, timeout=60) == 1
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# Task-event drop-oldest ring (satellite 2)
# ---------------------------------------------------------------------------


def test_task_event_ring_drops_oldest_counts_and_flares():
    from ray_tpu._private import flight_recorder
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.rpc import RpcClient

    init_config(
        {
            "heartbeat_interval_s": 30.0,
            "node_death_timeout_s": 120.0,
            "task_events_buffer_size": 100,
        }
    )
    gcs = GcsServer()
    cli = RpcClient(gcs.address, label="t-events")
    try:
        r = cli.call(
            "record_task_events",
            {"events": [{"task_id": f"a{i}", "state": "FINISHED"} for i in range(60)]},
            timeout=10,
        )
        assert r["dropped"] == 0 and gcs.events_dropped_total == 0

        r = cli.call(
            "record_task_events",
            {"events": [{"task_id": f"b{i}", "state": "FINISHED"} for i in range(80)]},
            timeout=10,
        )
        assert r["dropped"] == 40  # 60 + 80 - 100
        assert gcs.events_dropped_total == 40
        assert len(gcs.task_events) == 100
        # Drop-OLDEST: the survivors are the newest 100 (a40..a59 + b0..b79).
        ids = [e["task_id"] for e in gcs.task_events]
        assert ids[0] == "a40" and ids[-1] == "b79"

        # get_task_events serves the ring, bounded by limit.
        got = cli.call("get_task_events", {"limit": 10}, timeout=10)
        assert len(got["events"]) == 10

        evs = (flight_recorder.dump() or {}).get("events", [])
        assert any(e["type"] == "gcs_overload" for e in evs), (
            "overflow must flare a gcs_overload flight event"
        )
    finally:
        cli.close()
        gcs.stop()


def test_gcs_location_index_tracks_add_remove_death():
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.rpc import RpcClient

    init_config({"heartbeat_interval_s": 30.0, "node_death_timeout_s": 120.0})
    gcs = GcsServer()
    cli = RpcClient(gcs.address, label="t-locidx")
    try:
        cli.call(
            "register_node",
            {"node_id": "nx", "address": ["127.0.0.1", 1], "resources": {"CPU": 1}},
            timeout=10,
        )
        for i in range(5):
            cli.call(
                "add_object_location",
                {"object_id": f"o{i}", "node_id": "nx"},
                timeout=10,
            )
        assert gcs._locations_by_node["nx"] == {f"o{i}" for i in range(5)}
        cli.call(
            "remove_object_location", {"object_id": "o0", "node_id": "nx"}, timeout=10
        )
        assert "o0" not in gcs._locations_by_node["nx"]

        # Node death via the index drops exactly this node's rows.
        gcs._io.run(gcs._on_node_death("nx"), timeout=10)
        assert "nx" not in gcs._locations_by_node
        assert all("nx" not in holders for holders in gcs.object_locations.values())
    finally:
        cli.close()
        gcs.stop()


# ---------------------------------------------------------------------------
# Tier-2 (slow): the 1k sweep and chaos at scale
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sim_1k_shells_boot_and_schedule():
    c = SimCluster(
        1000,
        resources_per_node={"CPU": 8},
        num_entry_nodes=32,
        _system_config={
            "heartbeat_interval_s": 0.5,
            "node_death_timeout_s": 5.0,
        },
    )
    try:
        t0 = time.monotonic()
        c.start()
        c.wait_for_view(timeout=300)
        boot = time.monotonic() - t0

        n = 5000
        async def _burst():
            for i in range(0, n, 500):
                await asyncio.gather(
                    *[c.asubmit(c.make_spec(sim_ms=1.0)) for _ in range(500)]
                )

        c._io.run(_burst(), timeout=300)
        assert c.wait_done(n, timeout=180)
        assert boot < 180, f"1k boot+converge took {boot:.0f}s"
        # Delta sync holds at 1k: idle steady-state rows are zero.
        time.sleep(1.0)
        c.gcs.hb_stats = {"replies": 0, "rows": 0, "full_replies": 0, "view_bytes": 0}
        time.sleep(2.0)
        assert c.gcs.hb_stats["full_replies"] == 0
        assert c.gcs.hb_stats["rows"] == 0
    finally:
        c.shutdown()


@pytest.mark.slow
def test_sim_chaos_matrix_at_scale():
    from chaos_matrix import run_sim_matrix

    cells = run_sim_matrix(num_nodes=256, seed=7, quick=False)
    bad = [r.summary() for r in cells if not r.ok]
    assert not bad, f"sim SLO cells failed: {bad}"
