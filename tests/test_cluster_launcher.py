"""Cluster launcher (`ray_tpu up/down/exec`) + bandits + tuned-example tests.

Reference analogs: `ray up/down` (scripts.py:1235/1311) with the fake
multi-node provider, rllib/algorithms/bandit tests, tuned_examples regression
runs.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cluster_up_exec_down(tmp_path):
    cfg = tmp_path / "cluster.yaml"
    cfg.write_text(
        """
cluster_name: launcher_test
max_workers: 2
head_node:
  resources: {CPU: 2}
provider:
  type: fake
available_node_types:
  cpu_worker:
    resources: {CPU: 2}
    max_workers: 2
"""
    )
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    up = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.scripts", "up", str(cfg)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert up.returncode == 0, up.stdout + up.stderr
    assert "is up" in up.stdout
    try:
        with open("/tmp/ray_tpu/clusters/launcher_test.json") as f:
            info = json.load(f)
        # exec: a driver against the launched cluster sees it via env.
        script = tmp_path / "probe.py"
        script.write_text(
            "import ray_tpu\n"
            "ray_tpu.init(address='auto')\n"
            "print('CPUS', int(ray_tpu.cluster_resources().get('CPU', 0)))\n"
            "ray_tpu.shutdown()\n"
        )
        ex = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts.scripts", "exec", str(cfg),
             f"{sys.executable} {script}"],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert ex.returncode == 0, ex.stdout + ex.stderr
        assert "CPUS" in ex.stdout
    finally:
        down = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts.scripts", "down", str(cfg)],
            env=env, capture_output=True, text=True, timeout=120,
        )
    assert down.returncode == 0, down.stdout + down.stderr
    assert not os.path.exists("/tmp/ray_tpu/clusters/launcher_test.json")


class _ContextBanditEnv:
    """2-arm contextual bandit: arm 0 pays when ctx[0] > 0, else arm 1."""

    import gymnasium as gym

    observation_space = gym.spaces.Box(-1, 1, (2,), np.float32)
    action_space = gym.spaces.Discrete(2)

    def __init__(self, config=None):
        self._rng = np.random.default_rng(0)
        self._ctx = None

    def _next(self):
        self._ctx = self._rng.uniform(-1, 1, 2).astype(np.float32)
        return self._ctx

    def reset(self, *, seed=None, options=None):
        return self._next(), {}

    def step(self, action):
        good = 0 if self._ctx[0] > 0 else 1
        r = 1.0 if int(action) == good else 0.0
        return self._next(), r, True, False, {}

    def close(self):
        pass


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("cls_name", ["BanditLinUCB", "BanditLinTS"])
def test_bandits_learn_context(ray_start_regular, cls_name):
    import ray_tpu.rllib as rllib

    cls = getattr(rllib, cls_name)
    cfg = cls.get_default_config().environment(lambda config: _ContextBanditEnv(config))
    cfg.steps_per_iter = 200
    algo = cfg.build()
    try:
        for _ in range(5):
            r = algo.step()
        # Random play gets 0.5; a fitted linear model should be near-perfect.
        assert r["mean_reward"] > 0.8, r
        assert algo.compute_single_action(np.array([0.9, 0.0], np.float32)) == 0
        assert algo.compute_single_action(np.array([-0.9, 0.0], np.float32)) == 1
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


def test_tuned_example_runs(ray_start_regular, capsys):
    from ray_tpu.rllib.train import run_tuned_example

    path = os.path.join(REPO, "ray_tpu", "rllib", "tuned_examples", "cartpole-ppo.yaml")
    out = run_tuned_example(path, max_iters_override=2)
    assert "cartpole-ppo" in out
    assert "episode_reward_mean" in out["cartpole-ppo"]
    printed = capsys.readouterr().out
    assert "[cartpole-ppo] iter 1" in printed
