"""ApexDQN (distributed prioritized replay) and QMIX (monotonic value
factorization) learning tests (reference: rllib/algorithms/{apex_dqn,qmix};
VERDICT r1 #9)."""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_apex_dqn_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import ApexDQNConfig

    cfg = (
        ApexDQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4)
        .training(
            lr=1e-3,
            train_batch_size=64,
            learning_starts=500,
            target_network_update_freq=50,
            num_replay_shards=2,
            rollout_fragment_length=25,
            train_rounds_per_iter=10,
            updates_per_round=8,
            weight_sync_period_updates=16,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(30):
            r = algo.step()
            best = max(best, r.get("episode_reward_mean") or 0.0)
            if best >= 100:
                break
        assert best >= 100, f"ApexDQN failed to improve on CartPole (best={best})"
        assert r["replay_size"] > 0
    finally:
        algo.cleanup()


class TwoStepGame:
    """Cooperative matrix game from the QMIX paper: agent 0's first action
    selects which payoff matrix the pair plays next step; the global optimum
    (8) needs coordinated (1, 1) in state 2, which VDN-style additive mixing
    cannot represent but monotonic mixing can."""

    possible_agents = ["a0", "a1"]

    def __init__(self, config=None):
        import gymnasium as gym

        self._obs_space = gym.spaces.Box(0.0, 1.0, (3,), np.float32)
        self._act_space = gym.spaces.Discrete(2)
        self._state = 0

    @property
    def observation_space(self):
        return self._obs_space

    @property
    def action_space(self):
        return self._act_space

    def _obs(self):
        o = np.zeros(3, np.float32)
        o[self._state] = 1.0
        return {a: o.copy() for a in self.possible_agents}

    def reset(self, *, seed=None):
        self._state = 0
        return self._obs(), {}

    def step(self, action_dict):
        if self._state == 0:
            self._state = 1 if action_dict["a0"] == 0 else 2
            return self._obs(), {a: 0.0 for a in self.possible_agents}, {"__all__": False}, {"__all__": False}, {}
        if self._state == 1:
            r = 7.0
        else:
            matrix = np.array([[0.0, 1.0], [1.0, 8.0]])
            r = float(matrix[action_dict["a0"], action_dict["a1"]])
        rewards = {a: r / 2 for a in self.possible_agents}
        return self._obs(), rewards, {"__all__": True}, {"__all__": False}, {}

    def close(self):
        pass


def _make_two_step(config):
    from ray_tpu.rllib.env.multi_agent_env import MultiAgentEnv

    class _Env(TwoStepGame, MultiAgentEnv):
        pass

    return _Env(config)


def test_qmix_learns_two_step_game():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import QMIXConfig

    cfg = (
        QMIXConfig()
        .environment(_make_two_step)
        .training(
            lr=3e-3,
            train_batch_size=64,
            learning_starts=128,
            target_network_update_freq=40,
            rollout_steps_per_iter=400,
            epsilon_timesteps=3000,
            final_epsilon=0.05,
            gamma=0.99,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = -1e9
    try:
        for _ in range(15):
            r = algo.step()
            if np.isfinite(r["episode_reward_mean"]):
                best = max(best, r["episode_reward_mean"])
            if best >= 7.5:
                break
        # Optimal coordinated play earns 8; the uncoordinated trap pays 7.
        assert best >= 7.5, f"QMIX failed to coordinate (best={best})"
        # Greedy joint policy picks the (1,*) branch then (1,1).
        obs, _ = _make_two_step({}).reset()
        acts = algo.compute_actions(obs)
        assert acts["a0"] == 1
    finally:
        algo.cleanup()
