"""Async env-runner + connector pipelines.

Reference: rllib/evaluation/sampler.py:309 (AsyncSampler),
env_runner_v2.py:199 (EnvRunnerV2), rllib/connectors/{agent,action}.
The async runner keeps stepping envs in a background thread while the
learner updates; fragments queue up with backpressure and episode stats
ride along with them.
"""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _local_worker(env="CartPole-v1", **kw):
    import gymnasium as gym
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib.core import rl_module
    from ray_tpu.rllib.evaluation.rollout_worker import RolloutWorker
    from ray_tpu.rllib.models import ModelCatalog

    probe = gym.make(env)
    spec = ModelCatalog.get_model_spec(
        probe.observation_space, probe.action_space,
        {"fcnet_hiddens": (32,), "conv_filters": None},
    )
    probe.close()
    worker = RolloutWorker(env, spec, worker_index=0, num_envs=1, seed=1, **kw)
    worker.set_weights(rl_module.init_params(__import__("jax").random.PRNGKey(0), spec))
    return worker


def test_async_runner_produces_in_background():
    # The producer thread must fill the fragment queue with NO sampling
    # calls from the consumer — that is the property that lets the learner
    # overlap its update with environment stepping.
    w = _local_worker()
    try:
        w.start_async(fragment_len=32, queue_size=4)
        deadline = time.monotonic() + 30
        while w.async_queue_depth() < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert w.async_queue_depth() >= 2, "producer thread made no fragments"
        items = w.get_async(max_items=8, timeout=5)
        assert len(items) >= 2
        for item in items:
            assert len(item["batch"]) >= 32
            assert "episode_rewards" in item
        # Production continues after a drain.
        items2 = w.get_async(max_items=8, timeout=20)
        assert len(items2) >= 1
    finally:
        w.stop_async()
        w.stop()


def test_async_collects_while_consumer_is_busy():
    # Sync sampling by construction collects ZERO steps while the learner
    # is busy; the async runner keeps going. Simulate a slow update with a
    # sleep and check fragments accumulated during it.
    w = _local_worker()
    try:
        w.start_async(fragment_len=16, queue_size=8)
        # Drain whatever the warmup produced.
        w.get_async(max_items=100, timeout=20)
        time.sleep(2.0)  # "learner update" — no sampling calls
        items = w.get_async(max_items=100, timeout=5)
        steps = sum(len(it["batch"]) for it in items)
        assert steps >= 32, f"only {steps} steps collected during the update gap"
    finally:
        w.stop_async()
        w.stop()


def test_box_envs_get_action_clipping_connector():
    # Continuous envs auto-install a ClipActions stage (the gaussian sample
    # is unbounded); discrete envs install none.
    wc = _local_worker("Pendulum-v1")
    try:
        assert len(wc.action_connectors.connectors) == 1
        batch = wc.sample(8)
        assert len(batch) >= 8  # env accepted the (clipped) actions
    finally:
        wc.stop()
    wd = _local_worker("CartPole-v1")
    try:
        assert len(wd.action_connectors.connectors) == 0
    finally:
        wd.stop()


def test_agent_connector_pipeline_shapes_observations():
    from ray_tpu.rllib.connectors import ClipObservations
    from ray_tpu.rllib.policy.sample_batch import OBS

    w = _local_worker(agent_connectors=[ClipObservations(-0.05, 0.05)])
    try:
        batch = w.sample(16)
        assert np.all(batch[OBS] <= 0.05) and np.all(batch[OBS] >= -0.05)
    finally:
        w.stop()


@pytest.mark.slow  # a learning curve: up to 80 iterations, ~25 s; the next test is its quick case
def test_impala_async_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import IMPALAConfig

    cfg = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4, rollout_fragment_length=128)
        .training(lr=1e-3, train_batch_size=2048, entropy_coeff=0.01, async_sampling=True)
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(80):
            r = algo.step()
            m = r.get("episode_reward_mean")
            if m is not None and np.isfinite(m):
                best = max(best, m)
            if best >= 100:
                break
        assert best >= 100, f"async IMPALA failed to learn CartPole (best={best})"
    finally:
        algo.cleanup()


def test_impala_async_two_iterations_report_and_restore(ray_cluster):
    """The learning run's stand-in in tier-1: two iterations fed by the
    background samplers report finite losses and importance ratios, and a
    checkpoint restores the policy's actions."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import IMPALAConfig

    cfg = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4, rollout_fragment_length=128)
        .training(lr=1e-3, train_batch_size=2048, entropy_coeff=0.01, async_sampling=True)
        .debugging(seed=0)
    )
    keys = ("policy_loss", "vf_loss", "total_loss", "entropy", "mean_rho", "grad_norm", "episode_reward_mean")
    with two_iterations_then_a_restored_twin(cfg, keys, 4) as (r, algo, _):
        assert r["timesteps_total"] >= 2 * 2048
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
