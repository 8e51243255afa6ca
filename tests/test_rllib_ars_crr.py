"""ARS + CRR (VERDICT r2 Missing #1: RLlib algorithm breadth).

Learning-gated like the other algorithm tests:
- ARS improves CartPole purely by top-k filtered random search with the
  observation filter (reference rllib/algorithms/ars/).
- CRR recovers a good CartPole policy OFFLINE from mixed expert/random
  data — the advantage filter must reject the random fraction
  (reference rllib/algorithms/crr/).
"""

import numpy as np
import pytest

import gymnasium as gym

import ray_tpu


@pytest.fixture
def ray_cluster():
    import jax

    jax.config.update("jax_platforms", "cpu")
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


def test_ars_learns_cartpole(ray_cluster):
    from ray_tpu.rllib import ARSConfig

    cfg = (
        ARSConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2)
        .training(
            episodes_per_batch=16,
            num_top_directions=8,
            noise_stdev=0.05,
            stepsize=0.05,
            episode_horizon=500,
            eval_episodes=3,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(30):
            r = algo.step()
            reward = r.get("episode_reward_mean")
            if reward == reward:  # not NaN
                best = max(best, reward)
            if best >= 150:
                break
        assert best >= 150, f"ARS failed to learn CartPole (best={best})"
        assert algo.compute_single_action([0.0, 0.1, 0.0, -0.1]) in (0, 1)
    finally:
        algo.cleanup()


def _expert_action(obs) -> int:
    """Decent scripted CartPole controller (pole angle + velocity)."""
    return int(obs[2] + 0.3 * obs[3] > 0)


@pytest.mark.slow  # a learning curve: 3000 offline updates and five evaluation episodes, ~25 s; its quick case is the smoke test's "exp" case
def test_crr_learns_cartpole_offline(ray_cluster, tmp_path):
    from ray_tpu.rllib import CRRConfig
    from ray_tpu.rllib.offline import JsonWriter
    from ray_tpu.rllib.policy.sample_batch import (
        ACTIONS,
        DONES,
        NEXT_OBS,
        OBS,
        REWARDS,
        SampleBatch,
    )

    # Mixed dataset: 60% scripted expert, 40% random. Plain behavior
    # cloning of this data caps well below the expert; CRR's advantage
    # filter recovers the expert component.
    env = gym.make("CartPole-v1")
    writer = JsonWriter(str(tmp_path / "crr_data"))
    rng = np.random.default_rng(0)
    rows = {k: [] for k in (OBS, ACTIONS, REWARDS, DONES, NEXT_OBS)}
    obs, _ = env.reset(seed=0)
    for _ in range(6000):
        a = _expert_action(obs) if rng.random() < 0.6 else int(rng.integers(2))
        nobs, r, term, trunc, _ = env.step(a)
        rows[OBS].append(np.asarray(obs, np.float32))
        rows[ACTIONS].append(np.int64(a))
        rows[REWARDS].append(np.float32(r))
        rows[DONES].append(np.float32(term or trunc))
        rows[NEXT_OBS].append(np.asarray(nobs, np.float32))
        obs = nobs
        if term or trunc:
            obs, _ = env.reset()
    writer.write(SampleBatch({k: np.asarray(v) for k, v in rows.items()}))
    writer.close()

    cfg = (
        CRRConfig()
        .environment("CartPole-v1")
        .offline_data(input_=str(tmp_path / "crr_data"))
        .training(lr=1e-3, train_batch_size=256, updates_per_iter=300,
                  weight_type="exp", temperature=1.0)
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    try:
        for _ in range(10):
            r = algo.step()
        assert np.isfinite(r["total_loss"])
        # Evaluate the learned policy in the real env.
        rewards = []
        for ep in range(5):
            obs, _ = env.reset(seed=100 + ep)
            total = 0.0
            for _ in range(500):
                obs, rr, term, trunc, _ = env.step(algo.compute_single_action(obs))
                total += rr
                if term or trunc:
                    break
            rewards.append(total)
        mean_r = float(np.mean(rewards))
        assert mean_r >= 120, f"CRR failed to recover the expert (reward={mean_r})"
    finally:
        env.close()
        algo.cleanup()


@pytest.mark.parametrize("weight_type", ["binary", "exp"])
def test_crr_weights_smoke(ray_cluster, tmp_path, weight_type):
    """Two iterations over a small random dataset report finite losses and
    weights of their kind, and a second instance restored from the checkpoint
    takes the same actions; "exp" is the learning run's kind, whose stand-in in
    tier-1 this is."""
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import CRRConfig
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
    writer = JsonWriter(str(tmp_path / "crr_bin"))
    rng = np.random.default_rng(1)
    rows = {k: [] for k in (OBS, ACTIONS, REWARDS, DONES, NEXT_OBS)}
    obs, _ = env.reset(seed=1)
    for _ in range(1000):
        a = int(rng.integers(2))
        nobs, r, term, trunc, _ = env.step(a)
        rows[OBS].append(np.asarray(obs, np.float32))
        rows[ACTIONS].append(np.int64(a))
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
        CRRConfig()
        .environment("CartPole-v1")
        .offline_data(input_=str(tmp_path / "crr_bin"))
        .training(updates_per_iter=50, weight_type=weight_type)
        .debugging(seed=0)
    )
    with two_iterations_then_a_restored_twin(cfg, ("total_loss", "mean_weight"), 4) as (r, algo, algo2):
        if weight_type == "binary":
            assert 0.0 <= r["mean_weight"] <= 1.0  # binary weights are indicators
        assert algo2._timesteps_total == algo._timesteps_total
