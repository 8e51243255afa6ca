"""Tests for the round-4 algorithm families: SimpleQ, A3C, DDPPO, ApexDDPG.

Same tiering as test_rllib_algorithms.py (mirroring the reference's
rllib/algorithms/*/tests): learning checks for the on-policy families on
CartPole, compile-and-improve smoke tests for the off-policy/distributed
ones.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_simple_q_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import SimpleQConfig

    cfg = (
        SimpleQConfig()
        .environment("CartPole-v1")
        .rollouts(num_envs_per_worker=4)
        .training(
            lr=1e-3, train_batch_size=64, learning_starts=500,
            epsilon_timesteps=4000, rollout_steps_per_iter=500,
            model_hiddens=(64, 64),
        )
        .debugging(seed=0)
    )
    assert not cfg.double_q and not cfg.prioritized_replay
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(16):
            r = algo.step()
            if np.isfinite(r["episode_reward_mean"]):
                best = max(best, r["episode_reward_mean"])
            if best >= 80:
                break
        assert best >= 80, f"SimpleQ failed to improve on CartPole (best={best})"
    finally:
        algo.cleanup()


def test_simple_q_rejects_dqn_extensions():
    from ray_tpu.rllib import SimpleQConfig

    with pytest.raises(ValueError):
        SimpleQConfig().training(double_q=True)
    with pytest.raises(ValueError):
        SimpleQConfig().training(prioritized_replay=True)


def test_a3c_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import A3CConfig

    cfg = (
        A3CConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=8, rollout_fragment_length=40)
        .training(lr=2e-3, entropy_coeff=0.005, grad_clip=1.0, grads_per_step=12)
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(40):
            r = algo.step()
            if np.isfinite(r["episode_reward_mean"]):
                best = max(best, r["episode_reward_mean"])
            if best >= 100:
                break
        assert best >= 100, f"A3C failed to improve on CartPole (best={best})"
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


@pytest.mark.slow  # a learning curve: up to 40 iterations, ~40 s; the next test is its quick case
def test_ddppo_learns_cartpole_in_lockstep(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import DDPPOConfig

    cfg = (
        DDPPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=8, rollout_fragment_length=60)
        .training(lr=1e-3, entropy_coeff=0.005, num_sgd_iter=4, sgd_minibatch_size=120)
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    best = 0.0
    try:
        # training_step itself asserts the workers' weight digests agree
        # (decentralized updates must stay bit-identical).
        for _ in range(40):
            r = algo.step()
            if np.isfinite(r["episode_reward_mean"]):
                best = max(best, r["episode_reward_mean"])
            if best >= 100:
                break
        assert best >= 100, f"DDPPO failed to improve on CartPole (best={best})"
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
    finally:
        algo.cleanup()


def test_ddppo_two_iterations_stay_in_lockstep_and_restore(ray_cluster):
    """The learning run's stand-in in tier-1: two decentralized iterations (each
    asserts in training_step that the workers' weight digests agree) report
    finite losses, and a checkpoint restores the policy's actions."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import DDPPOConfig

    cfg = (
        DDPPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=8, rollout_fragment_length=60)
        .training(lr=1e-3, entropy_coeff=0.005, num_sgd_iter=4, sgd_minibatch_size=120)
        .debugging(seed=0)
    )
    keys = ("policy_loss", "vf_loss", "total_loss", "kl", "entropy", "episode_reward_mean")
    with two_iterations_then_a_restored_twin(cfg, keys, 4) as (r, algo, _):
        assert r["timesteps_total"] == 2 * 2 * 8 * 60
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)


def test_apex_ddpg_pendulum_smoke(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import ApexDDPGConfig

    cfg = (
        ApexDDPGConfig()
        .environment("Pendulum-v1")
        .rollouts(num_rollout_workers=2)
        .training(
            lr=1e-3, train_batch_size=64, learning_starts=300,
            rollout_fragment_length=50, train_rounds_per_iter=3,
            updates_per_round=2, model_hiddens=(32, 32),
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        for _ in range(2):
            r = algo.step()
        assert np.isfinite(r["critic_loss"])
        assert r["replay_size"] > 0
        a = algo.compute_single_action(np.zeros(3, np.float32))
        assert -2.0 <= float(np.asarray(a).ravel()[0]) <= 2.0
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()
