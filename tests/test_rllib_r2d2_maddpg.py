"""R2D2 + MADDPG + ExternalEnv (VERDICT r3 item 6).

Learning-gated like the reference's tuned-example regression tests:
- R2D2 reaches reward >=100 on CartPole (recurrent replay + burn-in +
  h-rescaling; reference rllib/algorithms/r2d2/).
- MADDPG solves a cooperative 2-agent spread task that needs the
  centralized critic (reference rllib/algorithms/maddpg/).
- ExternalEnv drives a DQN purely from an inverted-control loop
  (reference rllib/env/external_env.py:23).
"""

import numpy as np
import pytest

import gymnasium as gym

from ray_tpu.rllib.env.multi_agent_env import MultiAgentEnv


class Spread1D(MultiAgentEnv):
    """Two agents on a line must cover goals at -0.5/+0.5 without
    colliding; the shared min-assignment reward makes it cooperative, so
    independent learners plateau but a centralized critic does not."""

    possible_agents = ["agent_0", "agent_1"]

    def __init__(self, config=None):
        self._obs_space = gym.spaces.Box(-2, 2, (4,), np.float32)
        self._act_space = gym.spaces.Box(-1, 1, (1,), np.float32)
        self.goals = np.array([-0.5, 0.5], np.float32)
        self.t = 0
        self._rng = np.random.default_rng(0)

    @property
    def observation_space(self):
        return self._obs_space

    @property
    def action_space(self):
        return self._act_space

    def reset(self, *, seed=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self.pos = self._rng.uniform(-1, 1, 2).astype(np.float32)
        self.t = 0
        return self._obs(), {}

    def _obs(self):
        return {
            "agent_0": np.array([self.pos[0], self.pos[1], *self.goals], np.float32),
            "agent_1": np.array([self.pos[1], self.pos[0], *self.goals], np.float32),
        }

    def step(self, actions):
        self.pos[0] = np.clip(self.pos[0] + 0.1 * float(actions["agent_0"][0]), -2, 2)
        self.pos[1] = np.clip(self.pos[1] + 0.1 * float(actions["agent_1"][0]), -2, 2)
        self.t += 1
        d1 = abs(self.pos[0] - self.goals[0]) + abs(self.pos[1] - self.goals[1])
        d2 = abs(self.pos[0] - self.goals[1]) + abs(self.pos[1] - self.goals[0])
        r = -min(d1, d2)
        if abs(self.pos[0] - self.pos[1]) < 0.1:
            r -= 1.0
        done = self.t >= 25
        return (
            self._obs(),
            {"agent_0": r / 2, "agent_1": r / 2},
            {"__all__": done},
            {"__all__": False},
            {},
        )


@pytest.mark.slow  # a learning curve: up to 30 iterations, ~20 s; its quick case is the round trip's recurrent case
def test_r2d2_learns_cartpole():
    from ray_tpu.rllib.algorithms.r2d2 import R2D2Config

    cfg = (
        R2D2Config()
        .environment("CartPole-v1")
        .rollouts(num_envs_per_worker=4)
        .training(
            lr=1e-3,
            rollout_steps_per_iter=1000,
            learning_starts=400,
            train_intensity=16,
            epsilon_timesteps=6000,
            target_network_update_freq=100,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    best = 0.0
    try:
        for _ in range(30):
            r = algo.step()
            best = max(best, r.get("episode_reward_mean") or 0.0)
            if best >= 100:
                break
        assert best >= 100, f"R2D2 failed to learn CartPole (best={best})"
        # Recurrent action API round-trips hidden state.
        a, h = algo.compute_single_action(
            [0.0, 0.1, 0.0, -0.1], state=np.zeros((1, cfg.hidden_size), np.float32)
        )
        assert a in (0, 1) and h.shape == (1, cfg.hidden_size)
    finally:
        algo.cleanup()


@pytest.mark.parametrize("recurrent", [False, True], ids=["from a zero state", "state in, state out"])
def test_r2d2_checkpoint_roundtrip(recurrent):
    """Two iterations report a finite loss once learning has started, and a
    second instance restored from the checkpoint holds the same weights and takes
    the same actions, also through the recurrent API that hands the hidden state
    on (the learning run's last assertion, whose stand-in in tier-1 this is)."""
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib.algorithms.r2d2 import R2D2Config

    cfg = (
        R2D2Config()
        .environment("CartPole-v1")
        .training(rollout_steps_per_iter=200, learning_starts=100, train_intensity=20)
        .debugging(seed=0)
    )

    def act(algo, obs):
        if not recurrent:
            return algo.compute_single_action(obs)
        a, h = algo.compute_single_action(obs, state=np.full((1, cfg.hidden_size), 0.5, np.float32))
        assert a in (0, 1) and h.shape == (1, cfg.hidden_size)
        return np.concatenate([[a], h.ravel()])

    with two_iterations_then_a_restored_twin(cfg, ("total_loss", "td_abs", "epsilon"), 4, act=act) as (_, algo, algo2):
        assert algo2._timesteps_total == algo._timesteps_total
        import jax

        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
            algo.params, algo2.params,
        )


def test_maddpg_learns_cooperative_spread():
    from ray_tpu.rllib.algorithms.maddpg import MADDPGConfig

    cfg = (
        MADDPGConfig()
        .environment(Spread1D)
        .training(
            rollout_steps_per_iter=500,
            learning_starts=500,
            train_batch_size=128,
            exploration_noise=0.3,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = -1e9
    try:
        for _ in range(24):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best > -6:
                break
        assert best > -8, f"MADDPG failed to learn (best={best})"
        # Decentralized execution API.
        acts = algo.compute_actions(algo.env._obs())
        assert set(acts) == {"agent_0", "agent_1"}
    finally:
        algo.cleanup()


def test_external_env_drives_dqn():
    """Inverted control: a user thread owns the CartPole loop and queries
    the algorithm; episodes flow into DQN replay and the policy improves."""
    from ray_tpu.rllib.algorithms.dqn import DQNConfig
    from ray_tpu.rllib.env.external_env import ExternalEnv, ExternalEnvRunner

    class CartPoleExternal(ExternalEnv):
        def __init__(self):
            env = gym.make("CartPole-v1")
            super().__init__(env.action_space, env.observation_space)
            self._env = env
            self._stop = False

        def run(self):
            while not self._stop:
                eid = self.start_episode()
                obs, _ = self._env.reset()
                done = False
                while not done:
                    action = self.get_action(eid, obs)
                    obs, reward, term, trunc, _ = self._env.step(int(action))
                    self.log_returns(eid, reward)
                    done = term or trunc
                self.end_episode(eid, obs)

    cfg = (
        DQNConfig()
        .environment("CartPole-v1")  # spaces probe only; rollouts come from the external env
        .training(
            lr=1e-3,
            learning_starts=500,
            epsilon_timesteps=4000,
            target_network_update_freq=100,
            rollout_steps_per_iter=0,  # no internal rollouts
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    ext = CartPoleExternal()
    runner = ExternalEnvRunner(ext, algo)
    best = 0.0
    try:
        # 60 rounds (early-exit at reward 100): under full-suite load on a
        # 1-core box the collector thread gets starved and 40 rounds was
        # marginal — passed standalone, flaked in-suite.
        for _ in range(60):
            runner.collect(min_steps=500, timeout=60)
            for _ in range(60):
                algo._train_once()
            window = algo._episode_reward_window[-20:]
            if window:
                best = max(best, float(np.mean(window)))
            if best >= 100:
                break
        assert best >= 100, f"ExternalEnv-driven DQN failed to learn (best={best})"
    finally:
        ext._stop = True
        algo.cleanup()
