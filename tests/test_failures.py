"""Failure / fault-tolerance tests (analog of the reference's test_failure*.py,
test_chaos.py with the NodeKillerActor fault injector, test_utils.py:1360)."""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import TaskError, WorkerCrashedError


def test_task_retry_on_worker_crash(ray_start_regular):
    """A task that kills its worker is retried (reference: task_manager.h:335)."""
    marker = f"/tmp/rtpu_retry_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray_tpu.remote(max_retries=2)
    def flaky(path):
        import os as _os

        if not _os.path.exists(path):
            with open(path, "w") as f:
                f.write("1")
            _os._exit(1)  # kill the worker on first attempt
        return "recovered"

    assert ray_tpu.get(flaky.remote(marker), timeout=120) == "recovered"
    os.unlink(marker)


def test_task_no_retry_exhausted(ray_start_regular):
    @ray_tpu.remote(max_retries=0)
    def die():
        import os as _os

        _os._exit(1)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=120)


def test_retry_exceptions(ray_start_regular):
    marker = f"/tmp/rtpu_retryexc_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky(path):
        import os as _os

        if not _os.path.exists(path):
            with open(path, "w") as f:
                f.write("1")
            raise RuntimeError("transient")
        return "ok"

    assert ray_tpu.get(flaky.remote(marker), timeout=120) == "ok"
    os.unlink(marker)


def test_lineage_reconstruction(ray_start_cluster):
    """A lost plasma object is rebuilt by re-executing its creating task
    (reference: object_recovery_manager.h:90)."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1, resources={"head": 1})
    victim = cluster.add_node(num_cpus=1, resources={"victim": 1})
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(resources={"victim": 1}, max_retries=2)
    def produce():
        return np.ones((512, 512), dtype=np.float32)  # 1MB -> plasma on victim

    ref = produce.remote()
    # The object exists before its node dies, and fetch_local=False leaves the
    # only copy there.
    assert ray_tpu.wait([ref], timeout=60, fetch_local=False) == ([ref], [])
    # Kill the node holding the only copy.
    cluster.remove_node(victim)
    cluster.add_node(num_cpus=1, resources={"victim": 1})
    time.sleep(1.0)
    out = ray_tpu.get(ref, timeout=120)
    assert out.shape == (512, 512)


def test_a_task_running_on_a_node_that_dies_is_retried_in_seconds(ray_start_cluster, tmp_path):
    """The owner learns that a leased worker died with its node from the lease's
    renewal: the raylet that holds the lease no longer answers, so the worker
    is pinged, and the task in flight on it goes to another node. (Found only
    after 30 s without a completion, behind the renewal's own retries against
    the dead raylet: 40-85 s.)"""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1, resources={"head": 1})
    victim = cluster.add_node(num_cpus=1, resources={"victim": 1})
    cluster.connect()
    cluster.wait_for_nodes()
    started = str(tmp_path / "started")

    @ray_tpu.remote(resources={"victim": 1}, max_retries=2)
    def work(path):
        import os as _os
        import time as _time

        if not _os.path.exists(path):  # the first attempt: in flight when its node dies
            open(path, "w").close()
            _time.sleep(300)
        return "retried"

    ref = work.remote(started)
    deadline = time.monotonic() + 60
    while not os.path.exists(started) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert os.path.exists(started)
    cluster.remove_node(victim)
    cluster.add_node(num_cpus=1, resources={"victim": 1})
    t0 = time.monotonic()
    assert ray_tpu.get(ref, timeout=120) == "retried"
    assert time.monotonic() - t0 < 30.0


def test_a_worker_that_never_registers_is_given_up_and_another_is_started():
    """worker_startup_timeout_s: a spawned worker that does not register within
    it (a fork that hung, a child stuck before its first line) is killed and
    counted dead. Until then dispatch counts it as coming and starts no other,
    so without the limit the tasks behind it waited for good."""
    import sys

    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(_system_config={"worker_startup_timeout_s": 1.0, "worker_zygote_enabled": False})
    try:
        raylet = cluster.add_node(num_cpus=1)
        spawn, stuck = raylet._popen_worker, []

        def first_one_never_registers(handle, delta, log_path, argv=None):
            if stuck:
                return spawn(handle, delta, log_path, argv=argv)
            stuck.append(handle)
            return spawn(handle, delta, log_path, argv=[sys.executable, "-c", "import time; time.sleep(600)"])

        raylet._popen_worker = first_one_never_registers
        cluster.connect()

        @ray_tpu.remote
        def f():
            return "ran"

        assert ray_tpu.get(f.remote(), timeout=30) == "ran"
        assert stuck and stuck[0].state == "dead"
        assert stuck[0].proc.wait(timeout=5) is not None  # killed, not left behind
    finally:
        cluster.shutdown()


def test_node_death_detected(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    extra = cluster.add_node(num_cpus=1)
    cluster.connect()
    cluster.wait_for_nodes()
    extra_id = extra.node_id
    cluster.remove_node(extra)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        states = {n["node_id"]: n["state"] for n in ray_tpu.nodes()}
        if states.get(extra_id) == "DEAD":
            return
        time.sleep(0.2)
    pytest.fail("node death not detected")


def test_chaos_task_retry(ray_start_cluster):
    """Tasks survive a node being killed mid-workload (reference:
    test_chaos.py:66 test_chaos_task_retry)."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2, resources={"stable": 2})
    victim = cluster.add_node(num_cpus=2)
    cluster.connect()
    cluster.wait_for_nodes()

    @ray_tpu.remote(max_retries=3)
    def work(i):
        time.sleep(0.1)
        return i

    refs = [work.remote(i) for i in range(12)]
    time.sleep(0.3)
    cluster.remove_node(victim)
    out = ray_tpu.get(refs, timeout=180)
    assert out == list(range(12))


def test_memory_monitor_kills_and_surfaces_oom():
    """With the threshold forced to 0, any running task worker is killed by
    the memory monitor and the error surfaces as OutOfMemoryError after
    retries are exhausted (reference: test_memory_pressure / worker killing
    policy)."""
    import time as _time

    from ray_tpu.exceptions import OutOfMemoryError

    ray_tpu.init(
        num_cpus=2,
        object_store_memory=64 * 1024 * 1024,
        _system_config={
            "memory_usage_threshold": 0.0,  # everything is "over threshold"
            "memory_monitor_interval_s": 0.2,
        },
    )
    try:

        @ray_tpu.remote(max_retries=1)
        def hog():
            _time.sleep(30)
            return "finished"

        with pytest.raises(OutOfMemoryError):
            ray_tpu.get(hog.remote(), timeout=120)
    finally:
        ray_tpu.shutdown()


def test_memory_monitor_disabled_by_config():
    ray_tpu.init(
        num_cpus=2,
        object_store_memory=64 * 1024 * 1024,
        _system_config={
            "memory_usage_threshold": 0.0,
            "memory_monitor_enabled": False,
        },
    )
    try:

        @ray_tpu.remote
        def quick():
            return "ok"

        assert ray_tpu.get(quick.remote(), timeout=60) == "ok"
    finally:
        ray_tpu.shutdown()
