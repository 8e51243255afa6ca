"""Tests for the wider algorithm family (A2C/APPO/SAC/DDPG/TD3/ES/CQL).

Mirrors the reference's per-algorithm test style (rllib/algorithms/*/tests):
a learning check for the on-policy actor-critics on CartPole, compile-and-
improve smoke tests for the off-policy/offline/black-box families (their full
learning runs live in the reference's nightly tier, not unit CI).
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_a2c_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import A2CConfig

    cfg = (
        A2CConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=8)
        .training(lr=2e-3, train_batch_size=2000, entropy_coeff=0.005, grad_clip=1.0)
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(40):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 100:
                break
        assert best >= 100, f"A2C failed to improve on CartPole (best={best})"
    finally:
        algo.cleanup()


@pytest.mark.slow  # a learning curve: up to 40 iterations, ~25 s; the next test is its quick case
def test_appo_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import APPOConfig

    cfg = (
        APPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4)
        .training(lr=1e-3, train_batch_size=2048, entropy_coeff=0.01, num_sgd_iter=2, kl_coeff=0.0)
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    best = 0.0
    try:
        for _ in range(40):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 100:
                break
        assert best >= 100, f"APPO failed to learn CartPole (best={best})"
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
    finally:
        algo.cleanup()


def test_appo_two_iterations_report_and_restore(ray_cluster):
    """The learning run's stand-in in tier-1: two asynchronous iterations report
    finite losses over the batches they took, and a checkpoint restores the
    policy's actions."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import APPOConfig

    cfg = (
        APPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4)
        .training(lr=1e-3, train_batch_size=2048, entropy_coeff=0.01, num_sgd_iter=2, kl_coeff=0.0)
        .debugging(seed=0)
    )
    keys = ("policy_loss", "vf_loss", "total_loss", "entropy", "grad_norm", "episode_reward_mean")
    with two_iterations_then_a_restored_twin(cfg, keys, 4) as (r, algo, _):
        assert r["timesteps_total"] == 2 * 2048
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)


def test_sac_pendulum_smoke(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import SACConfig

    cfg = (
        SACConfig()
        .environment("Pendulum-v1")
        .training(
            lr=3e-4, train_batch_size=64, learning_starts=200,
            rollout_steps_per_iter=300, model_hiddens=(32, 32),
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        for _ in range(3):
            r = algo.step()
        assert np.isfinite(r["critic_loss"])
        assert np.isfinite(r["alpha"]) and r["alpha"] > 0
        a = algo.compute_single_action(np.zeros(3, np.float32))
        assert a.shape == (1,) and -2.0 <= float(a[0]) <= 2.0
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


def test_sac_discrete_smoke(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import SACConfig

    cfg = (
        SACConfig()
        .environment("CartPole-v1")
        .training(
            lr=3e-4, train_batch_size=64, learning_starts=200,
            rollout_steps_per_iter=300, model_hiddens=(32, 32), target_entropy=0.3,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        r = algo.step()
        assert np.isfinite(r["critic_loss"])
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
    finally:
        algo.cleanup()


def test_td3_pendulum_smoke(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import TD3Config

    cfg = (
        TD3Config()
        .environment("Pendulum-v1")
        .training(
            lr=1e-3, train_batch_size=64, learning_starts=200,
            rollout_steps_per_iter=300, model_hiddens=(32, 32),
        )
        .debugging(seed=0)
    )
    assert cfg.twin_q and cfg.policy_delay == 2 and cfg.smooth_target_policy
    algo = cfg.build()
    try:
        for _ in range(2):
            r = algo.step()
        assert np.isfinite(r["critic_loss"])
        a = algo.compute_single_action(np.zeros(3, np.float32))
        assert -2.0 <= float(a[0]) <= 2.0
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


def test_es_improves_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import ESConfig

    cfg = (
        ESConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2)
        .training(
            episodes_per_batch=16, stepsize=0.02, noise_stdev=0.05,
            episode_horizon=200, eval_episodes=3, model_hiddens=(16,),
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        rewards = []
        for _ in range(6):
            r = algo.step()
            if np.isfinite(r["episode_reward_mean"]):
                rewards.append(r["episode_reward_mean"])
        # Random CartPole is ~20; ES should clearly move the mean up.
        assert max(rewards) > 35, f"ES made no progress: {rewards}"
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
    finally:
        algo.cleanup()


def test_cql_offline_smoke(ray_cluster, tmp_path):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import gymnasium as gym

    from ray_tpu.rllib import CQLConfig
    from ray_tpu.rllib.offline import JsonWriter
    from ray_tpu.rllib.policy.sample_batch import (
        ACTIONS,
        DONES,
        NEXT_OBS,
        OBS,
        REWARDS,
        SampleBatch,
    )

    # Collect a small random-policy dataset on Pendulum.
    env = gym.make("Pendulum-v1")
    writer = JsonWriter(str(tmp_path / "cql_data"))
    rng = np.random.default_rng(0)
    obs, _ = env.reset(seed=0)
    rows = {k: [] for k in (OBS, ACTIONS, REWARDS, DONES, NEXT_OBS)}
    for _ in range(400):
        a = rng.uniform(-1, 1, size=(1,)).astype(np.float32)
        nobs, r, term, trunc, _ = env.step(a * 2.0)
        rows[OBS].append(np.asarray(obs, np.float32))
        rows[ACTIONS].append(a)
        rows[REWARDS].append(np.float32(r))
        rows[DONES].append(np.float32(term or trunc))
        rows[NEXT_OBS].append(np.asarray(nobs, np.float32))
        obs = nobs
        if term or trunc:
            obs, _ = env.reset()
    writer.write(SampleBatch({k: np.asarray(v) for k, v in rows.items()}))
    writer.close()
    env.close()

    cfg = (
        CQLConfig()
        .environment("Pendulum-v1")
        .offline_data(input_=str(tmp_path / "cql_data"))
        .training(train_batch_size=32, updates_per_iter=20, model_hiddens=(32, 32), cql_alpha=0.5)
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        r = algo.step()
        assert np.isfinite(r["bellman_loss"])
        # The conservative term is a logsumexp gap — must be finite, usually +.
        assert np.isfinite(r["cql_term"])
        a = algo.compute_single_action(np.zeros(3, np.float32))
        assert a.shape == (1,)
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
    finally:
        algo.cleanup()


def test_pg_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import PGConfig

    cfg = (
        PGConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=8)
        .training(lr=4e-3, train_batch_size=2000)
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(40):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 100:
                break
        assert best >= 100, f"PG failed to improve on CartPole (best={best})"
    finally:
        algo.cleanup()


def test_dt_imitates_expert_cartpole(ray_cluster, tmp_path):
    """Decision Transformer: offline sequence modeling on scripted-expert
    CartPole data; conditioned on the dataset's best return it should act
    near-expert (random play scores ~22)."""
    import gymnasium as gym
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import DTConfig
    from ray_tpu.rllib.offline import JsonWriter
    from ray_tpu.rllib.policy.sample_batch import (
        ACTIONS,
        DONES,
        NEXT_OBS,
        OBS,
        REWARDS,
        SampleBatch,
    )

    env = gym.make("CartPole-v1")
    writer = JsonWriter(str(tmp_path / "dt_data"))
    rows = {k: [] for k in (OBS, ACTIONS, REWARDS, DONES, NEXT_OBS)}
    for ep in range(25):
        obs, _ = env.reset(seed=ep)
        for _ in range(200):
            a = 1 if (obs[2] + 0.5 * obs[3]) > 0 else 0  # PD controller, ~200 reward
            nobs, r, term, trunc, _ = env.step(a)
            rows[OBS].append(np.asarray(obs, np.float32))
            rows[ACTIONS].append(np.int64(a))
            rows[REWARDS].append(np.float32(r))
            rows[DONES].append(np.float32(term or trunc))
            obs = nobs
            rows[NEXT_OBS].append(np.asarray(obs, np.float32))
            if term or trunc:
                break
        rows[DONES][-1] = np.float32(1.0)  # close the final episode
    writer.write(SampleBatch({k: np.asarray(v) for k, v in rows.items()}))
    writer.close()
    env.close()

    cfg = (
        DTConfig()
        .environment("CartPole-v1")
        .training(
            lr=1e-3,
            train_batch_size=64,
            context_length=20,
            updates_per_iter=150,
            eval_episodes=3,
            max_ep_len=200,
        )
        .debugging(seed=0)
        .offline_data(str(tmp_path / "dt_data"))
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(4):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 120:
                break
        assert best >= 120, f"DT failed to imitate the expert (best={best})"
    finally:
        algo.cleanup()
