"""Tests: CNN RLModules / ModelCatalog, MultiAgentEnv shared-policy path,
PolicyServer/Client external sims, rllib CLI.

Reference analogs: rllib/models/tests/test_models.py (vision nets),
rllib/env/tests/test_multi_agent_env.py, rllib/tests/test_external_env.py,
rllib/tests/test_rllib_train_and_evaluate.py.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


# ---------- CNN modules ----------

def test_cnn_module_forward_shapes():
    import gymnasium as gym
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ray_tpu.rllib.core import rl_module
    from ray_tpu.rllib.core.rl_module import RLModuleSpec

    obs_space = gym.spaces.Box(0, 1, (32, 32, 3), np.float32)
    act_space = gym.spaces.Discrete(4)
    spec = RLModuleSpec.from_spaces(obs_space, act_space, hiddens=(32,))
    assert spec.conv_filters, "3D obs should get a conv torso"
    params = rl_module.init_params(jax.random.PRNGKey(0), spec)
    assert "pi_conv" in params and "vf_conv" in params
    obs = jnp.zeros((5, 32, 32, 3))
    logits, value = rl_module.forward(params, obs, spec)
    assert logits.shape == (5, 4) and value.shape == (5,)
    # Flat input (as rollout batches carry it) reshapes internally.
    logits2, _ = rl_module.forward(params, obs.reshape(5, -1), spec)
    assert np.allclose(np.asarray(logits), np.asarray(logits2))


def test_model_catalog_picks_torso():
    import gymnasium as gym

    from ray_tpu.rllib.models import ModelCatalog

    flat = ModelCatalog.get_model_spec(
        gym.spaces.Box(-1, 1, (8,), np.float32), gym.spaces.Discrete(2)
    )
    assert not flat.conv_filters
    img = ModelCatalog.get_model_spec(
        gym.spaces.Box(0, 255, (84, 84, 4), np.uint8), gym.spaces.Discrete(6),
        {"conv_filters": None, "fcnet_hiddens": (256,)},
    )
    assert img.conv_filters == ((16, 8, 4), (32, 4, 2), (64, 3, 1))
    custom = ModelCatalog.get_model_spec(
        gym.spaces.Box(0, 1, (10, 10, 1), np.float32), gym.spaces.Discrete(2),
        {"conv_filters": [(8, 3, 1)]},
    )
    assert custom.conv_filters == ((8, 3, 1),)
    # Tiny spatial dims fall back to the flat MLP — no collapsing conv stack.
    tiny = ModelCatalog.get_model_spec(
        gym.spaces.Box(0, 1, (2, 2, 1), np.float32), gym.spaces.Discrete(2)
    )
    assert not tiny.conv_filters
    small = ModelCatalog.get_model_spec(
        gym.spaces.Box(0, 1, (4, 4, 1), np.float32), gym.spaces.Discrete(2)
    )
    assert small.conv_filters == ((16, 3, 1),)


def test_ppo_learns_tiny_vision_env(ray_cluster):
    """A trivially-learnable image env: the signal is which half of the image
    is bright; PPO with the conv torso must exceed random reward."""
    import gymnasium as gym
    import jax

    jax.config.update("jax_platforms", "cpu")

    class SideEnv(gym.Env):
        observation_space = gym.spaces.Box(0, 1, (10, 10, 1), np.float32)
        action_space = gym.spaces.Discrete(2)

        def __init__(self, config=None):
            self._rng = np.random.default_rng(0)
            self._t = 0

        def _obs(self):
            img = np.zeros((10, 10, 1), np.float32)
            self.side = int(self._rng.integers(0, 2))
            if self.side == 0:
                img[:, :5] = 1.0
            else:
                img[:, 5:] = 1.0
            return img

        def reset(self, *, seed=None, options=None):
            self._t = 0
            return self._obs(), {}

        def step(self, action):
            r = 1.0 if int(action) == self.side else 0.0
            self._t += 1
            return self._obs(), r, self._t >= 20, False, {}

    from ray_tpu.rllib import PPOConfig

    cfg = (
        PPOConfig()
        .environment(lambda config: SideEnv(config))
        .rollouts(num_rollout_workers=2, num_envs_per_worker=2)
        .training(lr=1e-3, train_batch_size=800, sgd_minibatch_size=128,
                  num_sgd_iter=6, model_hiddens=(32,),
                  model_conv_filters=[(8, 3, 2), (16, 3, 2)])
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(15):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 16:
                break
        # Random play scores ~10/20; a working conv torso approaches 20.
        assert best >= 16, f"vision PPO failed to learn (best={best})"
    finally:
        algo.cleanup()


# ---------- multi-agent ----------

def test_make_multi_agent_api():
    from ray_tpu.rllib.env import make_multi_agent

    cls = make_multi_agent("CartPole-v1", num_agents=3)
    env = cls({})
    obs, _ = env.reset(seed=0)
    assert set(obs) == {"agent_0", "agent_1", "agent_2"}
    actions = {a: env.action_space.sample() for a in env.possible_agents}
    obs, rewards, terms, truncs, _ = env.step(actions)
    assert set(rewards) == set(actions)
    assert terms["__all__"] is False
    env.close()


def test_multi_agent_vector_env_slots():
    from ray_tpu.rllib.env import make_multi_agent, make_vector_env

    cls = make_multi_agent("CartPole-v1", num_agents=2)
    venv = make_vector_env(lambda config: cls(config), 2, {}, 0, seed=0)
    assert venv.num_envs == 4  # 2 envs x 2 agents
    obs = venv.current_obs()
    assert obs.shape == (4, 4)
    for _ in range(30):
        _, rewards, dones, infos = venv.step(np.zeros(4, np.int64))
    # Always-push CartPole ends episodes; per-slot boundaries recorded.
    r, lens = venv.pop_episode_stats()
    assert len(r) > 0
    venv.close()


def test_ppo_learns_multi_agent_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import PPOConfig
    from ray_tpu.rllib.env import make_multi_agent

    ma_cls = make_multi_agent("CartPole-v1", num_agents=2)
    cfg = (
        PPOConfig()
        .environment(lambda config: ma_cls(config))
        .rollouts(num_rollout_workers=2, num_envs_per_worker=2)
        .training(lr=3e-4, train_batch_size=2048, sgd_minibatch_size=256,
                  num_sgd_iter=8, entropy_coeff=0.01)
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(20):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 120:
                break
        assert best >= 120, f"shared-policy multi-agent PPO failed (best={best})"
    finally:
        algo.cleanup()


# ---------- external env / policy server ----------

def test_policy_server_roundtrip():
    from ray_tpu.rllib.env import PolicyClient, PolicyServerInput

    def compute_action(obs, explore):
        return int(obs.sum() > 0)

    server = PolicyServerInput(compute_action)
    try:
        client = PolicyClient(server.address)
        eid = client.start_episode()
        for t in range(5):
            obs = np.ones(4) * (1 if t % 2 == 0 else -1)
            a = client.get_action(eid, obs)
            assert a == (1 if t % 2 == 0 else 0)
            client.log_returns(eid, 0.5)
        rows = client.end_episode(eid)
        assert rows == 5
        batch = server.next_batch()
        assert batch.count == 5
        assert batch["rewards"].sum() == pytest.approx(2.5)
        assert batch["dones"][-1] == 1.0
        # Several shaping rewards per step accumulate onto that step.
        eid = client.start_episode()
        client.get_action(eid, np.ones(4))
        client.log_returns(eid, 1.0)
        client.log_returns(eid, 0.25)
        assert client.end_episode(eid) == 1
        b2 = server.next_batch()
        assert b2["rewards"][0] == pytest.approx(1.25)
        # Unknown episode -> server-side error surfaced client-side.
        with pytest.raises(Exception):
            client.get_action("nope", np.zeros(4))
    finally:
        server.shutdown()


def test_rllib_cli_train(ray_cluster, capsys):
    from ray_tpu.rllib.train import main

    rc = main([
        "train", "--run", "PPO", "--env", "CartPole-v1",
        "--stop-iters", "2",
        "--config", '{"num_rollout_workers": 1, "train_batch_size": 400, "num_envs_per_worker": 2}',
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "iter 1" in out and "reward=" in out
