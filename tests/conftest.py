"""Test fixtures.

Analog of the reference's python/ray/tests/conftest.py: `ray_start_regular`
boots a real one-process-tree cluster per test; `ray_start_cluster` yields a
multi-raylet single-host Cluster (the reference's multi-node-without-a-cluster
trick, cluster_utils.py:99).

JAX is forced onto a virtual 8-device CPU mesh BEFORE first import so sharding
tests exercise real multi-device paths without TPU hardware.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_NUM_TPUS", "0")
# Dynamic backup for the graftlint static affinity checks: @loop_only /
# @blocking markers (ray_tpu/_private/concurrency.py) install cheap runtime
# asserts when this is set BEFORE first import. Driven by the lease/worker
# test modules (test_leases, test_basic, test_actors, test_cancel, ...);
# enabled process-wide because marker behavior binds at import and the suite
# shares one interpreter — worker subprocesses inherit it, so the asserts
# also run inside every spawned worker's IO loop and exec thread.
os.environ.setdefault("RAY_TPU_DEBUG_AFFINITY", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`); wide sweeps and "
        "long soak tests",
    )


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        yield cluster
    finally:
        cluster.shutdown()
