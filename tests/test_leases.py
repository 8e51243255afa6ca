"""Direct task transport (worker leases) — lease_manager.py + raylet grants.

Mirrors the reference's direct_task_transport tests
(python/ray/tests/test_basic_2.py lease reuse, test_failure_4.py worker
crash retries): tasks ride leased workers, leases are returned when idle,
placement-sensitive tasks keep the classic path, and a killed leased
worker fails over with retries.
"""

import os
import time

import pytest


def test_lease_path_correctness(ray_start_regular):
    import ray_tpu

    @ray_tpu.remote
    def add(x, y):
        return x + y

    # Chains (dependency through owned refs) and fan-out both cross the
    # lease transport.
    assert ray_tpu.get(add.remote(1, 2)) == 3
    assert ray_tpu.get(add.remote(add.remote(1, 2), 10)) == 13
    assert ray_tpu.get([add.remote(i, i) for i in range(50)]) == [2 * i for i in range(50)]


def test_lease_reused_and_returned(ray_start_regular):
    import ray_tpu
    from ray_tpu._private.worker_context import get_core_worker

    @ray_tpu.remote
    def pid():
        return os.getpid()

    # A sync loop should reuse one leased worker (no per-call spawn).
    pids = {ray_tpu.get(pid.remote()) for _ in range(10)}
    assert len(pids) <= 2  # warmup may use a second worker

    cw = get_core_worker()
    lm = cw._lease_mgr
    assert lm is not None
    held = sum(len(s.leases) for s in lm._shapes.values())
    assert held >= 1
    # After the linger the lease is returned to the raylet.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        held = sum(len(s.leases) for s in lm._shapes.values())
        if held == 0:
            break
        time.sleep(0.2)
    assert held == 0, "idle lease was never returned"
    # ... and the worker goes back to the raylet's idle pool (reusable by
    # the next lease or classic dispatch), not into limbo.
    raylet = getattr(ray_tpu._global_node, "raylet", None)
    if raylet is not None:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(w.state == "idle" for w in raylet.workers.values()):
                break
            time.sleep(0.2)
        assert any(w.state == "idle" for w in raylet.workers.values()), (
            "released lease did not return its worker to the idle pool"
        )


def test_warm_lease_reuse_skips_raylet(monkeypatch):
    """Steady-state sync loop: the raylet grants ONE lease up front; the
    following tasks ship worker-direct — request_worker_lease is not called
    again and every task runs in the same worker process. (The in-process
    raylet shares the test's IO loop, so its handler call counts are
    directly observable.)"""
    import ray_tpu
    from ray_tpu._private.rpc import EventLoopThread

    # Long linger so the maintenance loop cannot return the lease between
    # sync calls on a slow/loaded box.
    monkeypatch.setenv("RAY_TPU_LEASE_IDLE_RELEASE_S", "30")
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:

        @ray_tpu.remote
        def pid():
            return os.getpid()

        first = ray_tpu.get(pid.remote())  # cold: requests the lease
        stats = EventLoopThread.get().handler_stats
        key = next((k for k in stats if k.endswith(".request_worker_lease")), None)
        assert key is not None, "no lease request ever reached the raylet"
        grants_before = stats[key][0]
        pids = [ray_tpu.get(pid.remote()) for _ in range(3)]
        assert pids == [first] * 3, "warm tasks left the leased worker"
        assert stats[key][0] == grants_before, (
            "warm-lease tasks contacted the raylet for new leases"
        )
    finally:
        ray_tpu.shutdown()


def test_sigkill_warm_leased_worker_fails_over(ray_start_regular):
    """SIGKILL of the warm-leased worker: the next task fails over to a
    fresh lease (new worker) without a lost task."""
    import signal

    import ray_tpu

    @ray_tpu.remote(max_retries=2)
    def pid():
        return os.getpid()

    victim = ray_tpu.get(pid.remote())  # lease now warm on this worker
    os.kill(victim, signal.SIGKILL)
    survivor = ray_tpu.get(pid.remote(), timeout=90)
    assert survivor != victim


def test_classic_path_for_placement_sensitive_tasks(ray_start_regular):
    import ray_tpu
    from ray_tpu._private.task_spec import TaskSpec
    from ray_tpu._private.worker_context import get_core_worker

    cw = get_core_worker()
    spread = TaskSpec(task_id="x", job_id="j", name="t", scheduling_strategy="SPREAD")
    pg = TaskSpec(task_id="x", job_id="j", name="t", placement_group_id="abc")
    streaming = TaskSpec(task_id="x", job_id="j", name="t", num_returns="streaming")
    normal = TaskSpec(task_id="x", job_id="j", name="t")
    assert not cw._lease_eligible(spread)
    assert not cw._lease_eligible(pg)
    assert not cw._lease_eligible(streaming)
    assert cw._lease_eligible(normal)

    @ray_tpu.remote(scheduling_strategy="SPREAD")
    def f():
        return "spread-ok"

    assert ray_tpu.get(f.remote()) == "spread-ok"


def test_leased_worker_death_fails_over(ray_start_regular):
    import ray_tpu

    @ray_tpu.remote(max_retries=3)
    def die_once(marker_dir):
        marker = os.path.join(marker_dir, "died")
        if not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # hard kill mid-lease
        return "recovered"

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(die_once.remote(d), timeout=60) == "recovered"


def test_leased_worker_death_without_retries_errors(ray_start_regular):
    import ray_tpu
    from ray_tpu.exceptions import WorkerCrashedError

    @ray_tpu.remote(max_retries=0)
    def die():
        os._exit(1)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=60)


def test_lease_demand_reaches_autoscaler_load(ray_start_regular):
    """Owner-side backlog must surface in the raylet's demand report
    (reference: backlog_size on lease requests)."""
    import ray_tpu

    @ray_tpu.remote
    def slow():
        time.sleep(0.2)  # 200 of them on four CPUs: a backlog for ~10 s
        return 1

    refs = [slow.remote() for _ in range(200)]
    # The in-process raylet: reach it via the global node handle.
    node = ray_tpu._global_node
    raylet = getattr(node, "raylet", None)
    if raylet is None:
        pytest.skip("in-process raylet not reachable")
    # 45s window, peak-tracking: under full-suite load on one core the
    # 200-task backlog can drain through the observation polls — track the
    # MAX seen, and a lower bar still proves backlog reaches the report
    # (flaked in-suite at 15s/50, passes standalone).
    deadline = time.monotonic() + 45
    seen = 0
    while time.monotonic() < deadline:
        load = raylet._pending_load()
        seen = max(seen, sum(e["count"] for e in load))
        if seen >= 50:
            break
        time.sleep(0.1)
    assert seen >= 20, f"demand report never saw the backlog (saw {seen})"
    ray_tpu.get(refs, timeout=300)
