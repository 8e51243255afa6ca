"""Shared by the benchmark's tests: the recorded trace slices and one CPU
cluster with four chips' worth of TPU resource, as ``tests/test_chip_smoke.py``
has it. The toy sizes are in ``bench_toy.py``."""

import json
import os

import pytest
from bench_toy import REPO

from benchmarks.harness import registry, trace

FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")


@pytest.fixture(scope="session")
def manifest():
    return registry.load_manifest()


@pytest.fixture(scope="session")
def fixture_raw():
    """A slice of traces recorded on the v5e (PR 23): see fixtures/README.md."""
    def load(name):
        with open(os.path.join(FIXTURES, name)) as f:
            return json.load(f)

    return load


@pytest.fixture(scope="session")
def fixture_reduced(fixture_raw, manifest):
    def load(name, config):
        programs = registry.load_cell(manifest, config)["config"]["trace_programs"]
        return trace.reduce(fixture_raw(name), programs)

    return load


@pytest.fixture(scope="module")
def fake_chips():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()
