"""Process-mode chaos: SIGKILL real raylet process trees under load.

Reference: python/ray/tests/test_chaos.py:193 + test_utils.py:1360
(NodeKillerActor): the control plane (GCS) and every node (raylet) run as
REAL OS processes (their standalone main()s), a killer loop SIGKILLs random
worker-node process trees while a workload runs, and completion is asserted
via task retries + lineage reconstruction and trainer gang restart — the
in-process chaos tests (test_failures.py) cannot exercise process death.

The driver's own node is a zero-CPU "head" so every task/actor lands on a
killable victim node.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import worker_context
from ray_tpu._private.config import init_config
from ray_tpu._private.core_worker import DRIVER, CoreWorker

_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "RAY_TPU_NUM_TPUS": "0",
}


def _wait_file(path, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        time.sleep(0.1)
    raise TimeoutError(f"{path} never appeared")


def _start_gcs(tmp, name="gcs"):
    addr_file = os.path.join(tmp, f"{name}.json")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.gcs", "--address-file", addr_file],
        env=_ENV,
        stdout=open(os.path.join(tmp, f"{name}.log"), "ab"),
        stderr=subprocess.STDOUT,
    )
    info = _wait_file(addr_file)
    return proc, tuple(info["address"])


def _start_raylet(tmp, gcs_addr, cpus, tag):
    addr_file = os.path.join(tmp, f"raylet-{tag}-{time.monotonic_ns()}.json")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ray_tpu._private.raylet",
            "--gcs-address", json.dumps(list(gcs_addr)),
            "--session-dir", os.path.join(tmp, "session"),
            "--resources", json.dumps({"CPU": cpus}),
            "--address-file", addr_file,
        ],
        env=_ENV,
        stdout=open(os.path.join(tmp, f"raylet-{tag}.log"), "ab"),
        stderr=subprocess.STDOUT,
    )
    info = _wait_file(addr_file)
    return proc, info


def _kill_tree(proc):
    """SIGKILL a raylet and every descendant (zygote, workers) — the
    reference's NodeKillerActor kill shape."""
    import psutil

    try:
        parent = psutil.Process(proc.pid)
        children = parent.children(recursive=True)
    except psutil.NoSuchProcess:
        children = []
    for p in children:
        try:
            p.send_signal(signal.SIGKILL)
        except Exception:
            pass
    try:
        proc.send_signal(signal.SIGKILL)
    except Exception:
        pass
    proc.wait(timeout=10)


@pytest.fixture
def process_cluster(tmp_path):
    """GCS + zero-CPU head + 3 victim raylets, all real OS processes."""
    init_config(None)
    tmp = str(tmp_path)
    os.makedirs(os.path.join(tmp, "session", "logs"), exist_ok=True)
    gcs_proc, gcs_addr = _start_gcs(tmp)
    head_proc, head = _start_raylet(tmp, gcs_addr, cpus=0, tag="head")
    victims = [_start_raylet(tmp, gcs_addr, cpus=2, tag=f"v{i}") for i in range(3)]
    cw = CoreWorker(
        mode=DRIVER,
        gcs_address=gcs_addr,
        raylet_address=tuple(head["address"]),
        arena_name=head["arena"],
        node_id=head["node_id"],
        session_dir=os.path.join(tmp, "session"),
    )
    worker_context.set_core_worker(cw)
    state = {"gcs_addr": gcs_addr, "tmp": tmp, "victims": [v[0] for v in victims]}
    try:
        yield state
    finally:
        worker_context.set_core_worker(None)
        try:
            cw.shutdown()
        except Exception:
            pass
        for proc in state["victims"] + [head_proc, gcs_proc]:
            try:
                _kill_tree(proc)
            except Exception:
                pass


class _NodeKiller(threading.Thread):
    """Kill a random victim's process tree every `interval`, then start a
    replacement node so capacity recovers (the autoscaler's role in the
    reference's chaos suite)."""

    def __init__(self, state, interval=6.0, kills=2):
        super().__init__(daemon=True)
        self.state = state
        self.interval = interval
        self.kills = kills
        self.killed = 0

    def run(self):
        import random

        for _ in range(self.kills):
            time.sleep(self.interval)
            victims = self.state["victims"]
            if not victims:
                return
            proc = victims.pop(random.randrange(len(victims)))
            _kill_tree(proc)
            self.killed += 1
            replacement, _ = _start_raylet(
                self.state["tmp"], self.state["gcs_addr"], cpus=2,
                tag=f"r{self.killed}",
            )
            victims.append(replacement)


def test_tasks_and_shuffle_survive_node_kills(process_cluster):
    """A task wave + a dataset shuffle complete while raylet process trees
    are SIGKILLed: retries resubmit, lineage rebuilds lost objects."""
    from ray_tpu import data

    @ray_tpu.remote(max_retries=8)
    def chunk(i):
        time.sleep(0.3)
        return i

    killer = _NodeKiller(process_cluster, interval=5.0, kills=2)
    killer.start()
    refs = [chunk.remote(i) for i in range(60)]
    ds = data.range(400, parallelism=8).random_shuffle(seed=0)
    total = ds.sum("id")
    assert total == sum(range(400))
    assert sorted(ray_tpu.get(refs, timeout=420)) == list(range(60))
    killer.join(timeout=60)
    assert killer.killed == 2, "node killer did not complete its kills"
    # The cluster still works after the chaos.
    assert ray_tpu.get(chunk.remote(123), timeout=120) == 123


def test_checkpointed_trainer_survives_node_kill(process_cluster):
    """A 2-worker JaxTrainer run rides out a node SIGKILL via whole-gang
    restart (reference: Train fault tolerance under chaos)."""
    from ray_tpu.air import session
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.air.config import CheckpointConfig, FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    def loop(config):
        import jax
        import jax.numpy as jnp

        from ray_tpu.util import collective as col

        w = jnp.zeros((4,))

        def loss_fn(w):
            return jnp.sum((w - 3.0) ** 2)

        for step_i in range(16):
            g = jax.grad(loss_fn)(w)
            g = jnp.asarray(col.allreduce(g, group_name="train")) / session.get_world_size()
            w = w - 0.1 * g
            time.sleep(0.4)  # stretch the run across the kill window
            session.report(
                {"step": step_i, "loss": float(loss_fn(w))},
                checkpoint=Checkpoint.from_dict({"step": step_i}),
            )

    killer = _NodeKiller(process_cluster, interval=8.0, kills=1)
    killer.start()
    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            storage_path=os.path.join(process_cluster["tmp"], "train"),
            checkpoint_config=CheckpointConfig(num_to_keep=2),
            failure_config=FailureConfig(max_failures=4),
        ),
    )
    result = trainer.fit()
    killer.join(timeout=60)
    assert result.error is None, f"trainer failed under chaos: {result.error}"
    assert result.metrics["step"] == 15
    assert result.metrics["loss"] < 1.0
    assert killer.killed == 1
