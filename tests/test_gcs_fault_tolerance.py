"""GCS fault-tolerance tests.

Modeled on the reference's python/ray/tests/test_gcs_fault_tolerance.py: the
GCS restarts from its persisted snapshot on the same address; raylets detect
the restart, re-register, and republish object locations; named actors and
the KV survive; the cluster keeps executing tasks.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private import worker_context
from ray_tpu._private.config import init_config
from ray_tpu._private.core_worker import DRIVER, CoreWorker
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet


def test_gcs_restart_preserves_state(tmp_path):
    init_config(None)
    persist = str(tmp_path / "gcs_snapshot.pkl")
    session_dir = str(tmp_path / "session")
    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)

    gcs = GcsServer(persist_path=persist)
    host, port = gcs.address
    raylet = Raylet(gcs.address, session_dir, resources={"CPU": 2})
    cw = CoreWorker(
        mode=DRIVER,
        gcs_address=gcs.address,
        raylet_address=raylet.address,
        arena_name=raylet.arena_name,
        node_id=raylet.node_id,
        session_dir=session_dir,
    )
    worker_context.set_core_worker(cw)
    try:

        @ray_tpu.remote(name="ft-actor")
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get(c.inc.remote(), timeout=60) == 1
        cw.gcs.call("kv_put", {"key": "ft:probe", "value": b"survives", "overwrite": True})
        # Ensure the state is in the snapshot before the "crash".
        gcs.save_snapshot()
        gcs.stop()

        # Restart the GCS on the SAME address from the snapshot.
        gcs2 = GcsServer(host=host, port=port, persist_path=persist)
        try:
            # Raylet heartbeats hit "unknown", re-register, and come back.
            deadline = time.time() + 30
            alive = False
            while time.time() < deadline:
                nodes = gcs2.nodes
                if any(n.get("state") == "ALIVE" for n in nodes.values()):
                    alive = True
                    break
                time.sleep(0.2)
            assert alive, "raylet did not re-register after GCS restart"

            # KV survived.
            resp = cw.gcs.call("kv_get", {"key": "ft:probe"})
            assert resp.get("found") and bytes(resp["value"]) == b"survives"

            # Named actor survived (table restored) and still serves calls
            # (the actor process never died; calls are direct transport).
            h = ray_tpu.get_actor("ft-actor")
            assert ray_tpu.get(h.inc.remote(), timeout=60) == 2

            # New tasks still schedule.
            @ray_tpu.remote
            def f():
                return "post-restart"

            assert ray_tpu.get(f.remote(), timeout=60) == "post-restart"
        finally:
            gcs2.stop()
    finally:
        worker_context.set_core_worker(None)
        try:
            cw.shutdown()
        except Exception:
            pass
        raylet.stop()


def _boot(tmp_path, num_cpus=2):
    init_config(None)
    persist = str(tmp_path / "gcs_snapshot.pkl")
    session_dir = str(tmp_path / "session")
    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
    gcs = GcsServer(persist_path=persist)
    raylet = Raylet(gcs.address, session_dir, resources={"CPU": num_cpus})
    cw = CoreWorker(
        mode=DRIVER,
        gcs_address=gcs.address,
        raylet_address=raylet.address,
        arena_name=raylet.arena_name,
        node_id=raylet.node_id,
        session_dir=session_dir,
    )
    worker_context.set_core_worker(cw)
    return gcs, raylet, cw, persist


def _teardown(cw, raylet, gcs2):
    worker_context.set_core_worker(None)
    try:
        cw.shutdown()
    except Exception:
        pass
    raylet.stop()
    if gcs2 is not None:
        gcs2.stop()


def _restart_gcs(gcs, persist):
    """Kill + restart the GCS on the same address, from its snapshot."""
    host, port = gcs.address
    gcs.stop()
    return GcsServer(host=host, port=port, persist_path=persist)


def test_a_driver_whose_gcs_is_gone_shuts_down_at_once(tmp_path):
    """shutdown()'s last flushes to the GCS (task events, metrics, usage, the
    job's end) are best effort and share one bound of 2 s: a driver that
    outlives its head does not wait out the client's retries for each."""
    gcs, raylet, cw, _ = _boot(tmp_path)
    try:

        @ray_tpu.remote
        def f():
            return 1

        assert ray_tpu.get(f.remote(), timeout=60) == 1  # task events to flush
        gcs.stop()
    finally:
        worker_context.set_core_worker(None)
        t0 = time.monotonic()
        cw.shutdown()
        took = time.monotonic() - t0
        raylet.stop()
    assert took < 5.0, f"shutdown against a stopped GCS took {took:.1f} s"


def test_gcs_restart_under_running_tasks(tmp_path):
    """Tasks submitted before, DURING, and after a GCS restart all complete:
    the data plane (leases + direct transport) rides out the control-plane
    outage (reference: test_gcs_fault_tolerance.py worker-reconnect cases)."""
    gcs, raylet, cw, persist = _boot(tmp_path)
    gcs2 = None
    try:

        @ray_tpu.remote
        def work(i):
            import time as _t

            _t.sleep(0.4)
            return i * 2

        before = [work.remote(i) for i in range(8)]
        time.sleep(0.3)  # let snapshots capture the function export
        gcs2 = _restart_gcs(gcs, persist)
        during = [work.remote(i) for i in range(8, 12)]
        assert ray_tpu.get(before, timeout=120) == [i * 2 for i in range(8)]
        assert ray_tpu.get(during, timeout=120) == [i * 2 for i in range(8, 12)]
        # Post-restart submissions too.
        assert ray_tpu.get([work.remote(99)], timeout=120) == [198]
    finally:
        _teardown(cw, raylet, gcs2 if gcs2 is not None else gcs)


def test_gcs_restart_during_pg_creation(tmp_path):
    """A placement group snapshotted PENDING (infeasible at creation time)
    completes after the restart once capacity exists: restored PGs are
    re-driven (reference: gcs_placement_group_manager recovery)."""
    from ray_tpu.util.placement_group import placement_group

    gcs, raylet, cw, persist = _boot(tmp_path, num_cpus=1)
    gcs2 = None
    second = None
    try:
        # Demands 3 CPUs; the single 1-CPU node cannot host it -> PENDING.
        pg = placement_group([{"CPU": 1}, {"CPU": 1}, {"CPU": 1}], strategy="SPREAD")
        time.sleep(0.4)  # PENDING state reaches the snapshot
        gcs2 = _restart_gcs(gcs, persist)

        # Add capacity AFTER the restart: two more raylets.
        session_dir = str(tmp_path / "session")
        second = [
            Raylet(gcs2.address, session_dir, resources={"CPU": 1}) for _ in range(2)
        ]
        deadline = time.time() + 60
        created = False
        while time.time() < deadline:
            info = gcs2.placement_groups.get(pg.id.hex())
            if info is not None and info["state"] == "CREATED":
                created = True
                break
            time.sleep(0.2)
        assert created, "restored PENDING placement group was never created"
    finally:
        if second:
            for r in second:
                r.stop()
        _teardown(cw, raylet, gcs2 if gcs2 is not None else gcs)


def test_actor_restart_across_gcs_restart(tmp_path):
    """An actor with max_restarts dies AFTER a GCS restart; the restarted
    GCS still owns the restart machinery (reference: actor FT across GCS
    failover)."""
    gcs, raylet, cw, persist = _boot(tmp_path)
    gcs2 = None
    try:

        @ray_tpu.remote(max_restarts=2, name="phoenix")
        class Phoenix:
            def __init__(self):
                self.n = 0

            def ping(self):
                self.n += 1
                return self.n

            def die(self):
                os._exit(1)

        p = Phoenix.remote()
        assert ray_tpu.get(p.ping.remote(), timeout=60) == 1
        time.sleep(0.4)  # ALIVE state reaches the snapshot
        gcs2 = _restart_gcs(gcs, persist)

        # Wait for the raylet to re-register with the restarted GCS.
        deadline = time.time() + 30
        while time.time() < deadline:
            if any(n.get("state") == "ALIVE" for n in gcs2.nodes.values()):
                break
            time.sleep(0.2)

        try:
            ray_tpu.get(p.die.remote(), timeout=30)
        except Exception:
            pass  # the kill call dies with the actor
        # The restarted GCS restarts the actor; state resets (fresh __init__).
        deadline = time.time() + 90
        value = None
        while time.time() < deadline:
            try:
                value = ray_tpu.get(p.ping.remote(), timeout=10)
                break
            except Exception:
                time.sleep(0.5)
        assert value == 1, f"actor did not restart after GCS failover (got {value})"
    finally:
        _teardown(cw, raylet, gcs2 if gcs2 is not None else gcs)


def _hard_kill_gcs(gcs):
    """Simulate SIGKILL: tear the server down WITHOUT writing a snapshot.
    Whatever survives must come from the write-ahead log."""
    gcs._health_task.cancel()
    if gcs._persist_task is not None:
        gcs._persist_task.cancel()
    for c in gcs._raylet_clients.values():
        c.close()
    gcs.server.stop()


def test_gcs_wal_survives_kill_after_acknowledged_mutation(tmp_path):
    """The debounced snapshot alone had a ~150ms loss window; the WAL closes
    it (reference durability bar: redis_store_client.h — every acknowledged
    mutation survives). Snapshots are disabled entirely here, so restart
    state comes purely from WAL replay."""
    gcs, raylet, cw, persist = _boot(tmp_path)
    # No snapshots ever: durability must come from the WAL alone.
    gcs._persist_task.cancel()
    gcs._persist_task = None
    host, port = gcs.address
    gcs2 = None
    try:

        @ray_tpu.remote(name="wal-actor")
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get(c.inc.remote(), timeout=60) == 1
        cw.gcs.call("kv_put", {"key": "wal:probe", "value": b"durable", "overwrite": True})
        # Immediately after the acknowledged mutations: hard kill, no snapshot.
        _hard_kill_gcs(gcs)
        assert not os.path.exists(persist), "snapshot must not exist — WAL only"
        assert os.path.exists(persist + ".wal")

        gcs2 = GcsServer(host=host, port=port, persist_path=persist)
        # KV mutation survived the kill.
        resp = cw.gcs.call("kv_get", {"key": "wal:probe"})
        assert resp.get("found") and bytes(resp["value"]) == b"durable"
        # Actor registration survived: named actor resolvable and serving
        # (the actor process itself never died).
        h = ray_tpu.get_actor("wal-actor")
        assert ray_tpu.get(h.inc.remote(), timeout=60) == 2
    finally:
        _teardown(cw, raylet, gcs2)


def test_gcs_wal_fsync_knob(tmp_path, monkeypatch):
    """RAY_TPU_WAL_FSYNC policies actually reach os.fsync/os.fdatasync:
    "1" syncs inside the mutating append, "everysec" batches an fdatasync
    from the persist loop within ~1s, "0" never syncs (flush only)."""
    from ray_tpu._private import gcs as gcs_module
    from ray_tpu._private.config import Config
    from ray_tpu._private.rpc import RpcClient

    # The env knob plumbs through the config registry.
    monkeypatch.setenv("RAY_TPU_WAL_FSYNC", "1")
    cfg = Config()
    cfg.apply_overrides(None)
    assert cfg.wal_fsync == "1"
    monkeypatch.delenv("RAY_TPU_WAL_FSYNC")

    init_config(None)
    calls = {"fsync": 0, "fdatasync": 0}
    real_fsync, real_fdatasync = os.fsync, os.fdatasync

    def counting_fsync(fd):
        calls["fsync"] += 1
        return real_fsync(fd)

    def counting_fdatasync(fd):
        calls["fdatasync"] += 1
        return real_fdatasync(fd)

    monkeypatch.setattr(gcs_module.os, "fsync", counting_fsync)
    monkeypatch.setattr(gcs_module.os, "fdatasync", counting_fdatasync)

    persist = str(tmp_path / "gcs_snapshot.pkl")
    gcs = GcsServer(persist_path=persist)
    client = RpcClient(tuple(gcs.address), label="gcs")
    try:
        # Mode "1": fsync before the handler replies.
        gcs._wal_fsync = "1"
        client.call("kv_put", {"key": "k1", "value": b"v", "overwrite": True})
        assert calls["fsync"] >= 1

        # Mode "0": no syncing at all.
        gcs._wal_fsync = "0"
        before = (calls["fsync"], calls["fdatasync"])
        client.call("kv_put", {"key": "k0", "value": b"v", "overwrite": True})
        assert (calls["fsync"], calls["fdatasync"]) == before

        # Mode "everysec" (the default): the persist loop fdatasyncs the
        # dirty WAL within ~1s and clears the dirty bit.
        # Mode "everysec": disable snapshot compaction (it fsyncs the
        # snapshot and truncates the WAL, legitimately clearing the dirty
        # bit before the 1s window) so the fdatasync branch itself runs.
        gcs._wal_fsync = "everysec"
        gcs.persist_path = ""
        client.call("kv_put", {"key": "ke", "value": b"v", "overwrite": True})
        deadline = time.time() + 5
        while time.time() < deadline and calls["fdatasync"] == before[1]:
            time.sleep(0.1)
        assert calls["fdatasync"] > before[1]
    finally:
        client.close()
        gcs.stop()


def test_gcs_wal_torn_tail_is_discarded(tmp_path):
    """A crash mid-append leaves a torn trailing record; replay applies the
    complete prefix and drops the tail instead of refusing to start."""
    gcs, raylet, cw, persist = _boot(tmp_path)
    gcs._persist_task.cancel()
    gcs._persist_task = None
    host, port = gcs.address
    gcs2 = None
    try:
        cw.gcs.call("kv_put", {"key": "wal:keep", "value": b"kept", "overwrite": True})
        _hard_kill_gcs(gcs)
        # Append a torn record (length prefix promises more bytes than exist).
        with open(persist + ".wal", "ab") as f:
            f.write((1 << 20).to_bytes(4, "big") + b"\x00\x01\x02")
        gcs2 = GcsServer(host=host, port=port, persist_path=persist)
        resp = cw.gcs.call("kv_get", {"key": "wal:keep"})
        assert resp.get("found") and bytes(resp["value"]) == b"kept"
    finally:
        _teardown(cw, raylet, gcs2)
