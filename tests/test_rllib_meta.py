"""Tests for the meta/model-based RL genre: MAML and MBMPO.

Mirrors the reference's rllib/algorithms/{maml,mbmpo}/tests: the
learning-shaped assertion is the ADAPTATION DELTA — a meta-trained policy
must gain more from one inner step on a fresh task than an untrained one —
plus supervised sanity on the learned dynamics ensemble for MBMPO.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.env.meta_env import PointGoalEnv


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_point_goal_env_task_api():
    env = PointGoalEnv({"seed": 3})
    tasks = env.sample_tasks(4)
    assert len(tasks) == 4
    env.set_task(tasks[0])
    assert np.allclose(env.get_task(), tasks[0])
    obs, _ = env.reset()
    assert obs.shape == (2,)
    total = 0
    for _ in range(env.horizon):
        obs, r, term, trunc, _ = env.step(np.array([1.0, 0.0], np.float32))
        assert not term
        total += 1
        if trunc:
            break
    assert total == env.horizon


@pytest.mark.slow  # a learning curve: 15 meta-iterations, ~40 s; the next test is its quick case
def test_maml_learns_to_adapt(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import MAMLConfig

    cfg = (
        MAMLConfig()
        .environment(PointGoalEnv, env_config={"seed": 0})
        .rollouts(num_rollout_workers=2)
        .training(
            lr=5e-3, inner_lr=0.3, meta_batch_size=8, episodes_per_task=8,
            maml_optimizer_steps=5, model_hiddens=(32, 32),
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    try:
        deltas, posts = [], []
        for _ in range(15):
            r = algo.step()
            deltas.append(r["adaptation_delta"])
            posts.append(r["post_adaptation_reward_mean"])
        # Meta-training must produce positive adaptation gain on held-out
        # tasks (goals are freshly sampled every iteration) and the
        # post-adaptation return must improve over training.
        assert np.mean(deltas[-5:]) > 0.5, f"no adaptation gain: {deltas}"
        assert np.mean(posts[-4:]) > np.mean(posts[:4]) + 1.0, (
            f"post-adaptation return did not improve: {posts}"
        )
        # Public deploy-time adaptation API.
        task = algo._task_env.sample_tasks(1)[0]
        adapted = algo.adapt_to_task(task)
        assert set(adapted.keys()) == set(algo.get_policy_weights().keys())
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


def test_maml_two_iterations_report_adapt_and_restore(ray_cluster):
    """The learning run's stand-in in tier-1: two meta-iterations report finite
    numbers before and after adaptation, the deploy-time adaptation API gives a
    whole set of weights, and a checkpoint restores the policy's actions."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import MAMLConfig

    cfg = (
        MAMLConfig()
        .environment(PointGoalEnv, env_config={"seed": 0})
        .rollouts(num_rollout_workers=2)
        .training(
            lr=5e-3, inner_lr=0.3, meta_batch_size=4, episodes_per_task=4,
            maml_optimizer_steps=2, model_hiddens=(32, 32),
        )
        .debugging(seed=0)
    )
    keys = ("meta_loss", "pre_adaptation_reward_mean", "post_adaptation_reward_mean", "adaptation_delta")
    with two_iterations_then_a_restored_twin(cfg, keys, 2) as (r, algo, _):
        assert r["adaptation_delta"] == pytest.approx(
            r["post_adaptation_reward_mean"] - r["pre_adaptation_reward_mean"]
        )
        task = algo._task_env.sample_tasks(1)[0]
        adapted = algo.adapt_to_task(task)
        assert set(adapted.keys()) == set(algo.get_policy_weights().keys())


def test_mbmpo_model_based_progress(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import MBMPOConfig

    cfg = (
        MBMPOConfig()
        .environment(PointGoalEnv, env_config={"seed": 0})
        .training(
            lr=1e-3, inner_lr=0.2, maml_optimizer_steps=3,
            ensemble_size=3, dynamics_train_epochs=60,
            real_episodes_per_iter=15, imagined_episodes_per_task=16,
            model_hiddens=(32, 32),
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        results = [algo.step() for _ in range(8)]
        dyn_losses = [r["dynamics_loss"] for r in results]
        rewards = [r["real_episode_reward_mean"] for r in results]
        # The ensemble must actually fit the (linear) point dynamics...
        assert dyn_losses[-1] < dyn_losses[0] * 0.5, f"model not learning: {dyn_losses}"
        assert dyn_losses[-1] < 1e-2
        # ...and policy updates computed ONLY on imagined data must move
        # the REAL-env return up.
        assert np.mean(rewards[-3:]) > np.mean(rewards[:3]) + 0.5, (
            f"no real-env progress from imagined training: {rewards}"
        )
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


def test_mbmpo_learned_dynamics_match_truth(ray_cluster):
    """The ensemble's mean prediction should approximate the true
    transition function on in-distribution states."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ray_tpu.rllib import MBMPOConfig
    from ray_tpu.rllib.algorithms.mbmpo.mbmpo import _dyn_apply

    cfg = (
        MBMPOConfig()
        .environment(PointGoalEnv, env_config={"seed": 1})
        .training(
            ensemble_size=3, dynamics_train_epochs=80,
            real_episodes_per_iter=25, imagined_episodes_per_task=8,
            maml_optimizer_steps=1, model_hiddens=(32, 32),
        )
        .debugging(seed=1)
    )
    algo = cfg.build()
    try:
        algo.step()
        algo.step()  # two rounds of real data + ensemble fitting
        obs = jnp.asarray(algo._replay_obs[:64])
        act = jnp.asarray(algo._replay_act[:64])
        true_next = PointGoalEnv.transition_fn(obs, act, step_size=0.15)
        preds = []
        for k in range(cfg.ensemble_size):
            model = algo._model_slice(k)
            preds.append(obs + _dyn_apply(model, jnp.concatenate([obs, act], -1)))
        mean_pred = jnp.mean(jnp.stack(preds), axis=0)
        max_err = float(jnp.abs(mean_pred - true_next).max())
        mean_err = float(jnp.abs(mean_pred - true_next).mean())
        assert max_err < 0.15, f"learned dynamics off by {max_err} (max)"
        assert mean_err < 0.05, f"learned dynamics off by {mean_err} (mean)"
    finally:
        algo.cleanup()
