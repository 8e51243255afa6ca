"""DreamerV3 — model-based RL (reference: rllib/algorithms/dreamerv3/).

World model (RSSM, categorical latents) + actor-critic trained purely in
imagination. The learning test uses a 1-D target-reaching task: a correct
world model makes it solvable in a handful of iterations, while a broken
reward/dynamics head leaves the actor at random-policy level.
"""

import numpy as np
import pytest

import gymnasium as gym


class Reach1D(gym.Env):
    """Move to the target: obs [pos, target], action in [-1, 1],
    pos += 0.2 * a, reward -|pos - target|, 20-step episodes.
    Random policy averages about -18 per episode; a good policy -5."""

    observation_space = gym.spaces.Box(-2, 2, (2,), np.float32)
    action_space = gym.spaces.Box(-1, 1, (1,), np.float32)

    def __init__(self, config=None):
        self._rng = np.random.default_rng(0)

    def reset(self, *, seed=None, options=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self.pos = float(self._rng.uniform(-1, 1))
        self.target = float(self._rng.uniform(-1, 1))
        self.t = 0
        return np.array([self.pos, self.target], np.float32), {}

    def step(self, a):
        self.pos = float(np.clip(self.pos + 0.2 * float(np.asarray(a).ravel()[0]), -2, 2))
        self.t += 1
        r = -abs(self.pos - self.target)
        return np.array([self.pos, self.target], np.float32), r, False, self.t >= 20, {}


@pytest.mark.slow  # a learning curve: up to 25 iterations, ~40 s; its quick case is the smoke test's Reach1D case
def test_dreamerv3_learns_reach1d():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import DreamerV3Config

    cfg = (
        DreamerV3Config()
        .environment(Reach1D)
        .training(
            learning_starts=300, rollout_steps_per_iter=400, train_intensity=10,
            batch_size=8, batch_length=12, deter_size=64, model_hiddens=(64,),
            latent_groups=4, latent_classes=8, imagine_horizon=10,
            entropy_coeff=1e-3,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = -1e9
    try:
        for _ in range(25):
            r = algo.step()
            m = r.get("episode_reward_mean")
            if m is not None and np.isfinite(m):
                best = max(best, m)
            if best > -8:
                break
        # Random policy sits near -18; the world-model-driven actor must
        # clearly beat it.
        assert best > -8, f"DreamerV3 failed to learn Reach1D (best={best})"
        assert np.isfinite(r["model_loss"])
    finally:
        algo.cleanup()


@pytest.mark.parametrize("env, obs_dim, bound", [("Pendulum-v1", 3, 2.0), (Reach1D, 2, 1.0)], ids=["pendulum", "reach1d"])
def test_dreamerv3_smoke_and_checkpoint(env, obs_dim, bound):
    """Two iterations report finite losses, and a second instance restored from
    the checkpoint takes the same actions: on Pendulum, and on the learning
    run's own task, whose stand-in in tier-1 this is."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import DreamerV3Config

    cfg = (
        DreamerV3Config()
        .environment(env)
        .training(
            learning_starts=200, rollout_steps_per_iter=250, train_intensity=25,
            batch_size=4, batch_length=12, deter_size=64, model_hiddens=(64,),
            latent_groups=4, latent_classes=8, imagine_horizon=8,
        )
        .debugging(seed=0)
    )
    def act(algo, obs):  # the posterior's latent is SAMPLED, from a key no checkpoint holds: the same key for both
        algo._key = jax.random.PRNGKey(0)
        return algo.compute_single_action(obs)

    keys = ("model_loss", "recon_loss", "reward_loss", "actor_loss", "critic_loss")
    with two_iterations_then_a_restored_twin(cfg, keys, obs_dim, act=act) as (_, algo, twin):
        a = algo.compute_single_action(np.zeros(obs_dim, np.float32))
        assert a.shape == (1,) and -bound <= float(a[0]) <= bound
        np.testing.assert_allclose(
            np.asarray(twin.params["reward"][0]["w"]), np.asarray(algo.params["reward"][0]["w"])
        )


def test_dreamerv3_discrete_smoke():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import DreamerV3Config

    cfg = (
        DreamerV3Config()
        .environment("CartPole-v1")
        .training(
            learning_starts=200, rollout_steps_per_iter=250, train_intensity=25,
            batch_size=4, batch_length=12, deter_size=64, model_hiddens=(64,),
            latent_groups=4, latent_classes=8, imagine_horizon=8,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        for _ in range(2):
            r = algo.step()
        assert np.isfinite(r["model_loss"]) and np.isfinite(r["actor_loss"])
        assert algo.compute_single_action(np.zeros(4, np.float32)) in (0, 1)
    finally:
        algo.cleanup()


def test_dreamerv3_evaluation():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import DreamerV3Config

    cfg = (
        DreamerV3Config()
        .environment(Reach1D)
        .training(
            learning_starts=100, rollout_steps_per_iter=150, train_intensity=50,
            batch_size=4, batch_length=12, deter_size=64, model_hiddens=(64,),
            latent_groups=4, latent_classes=8, imagine_horizon=8,
        )
        .evaluation(evaluation_interval=1, evaluation_duration=2)
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        r = algo.train()
        ev = r["evaluation"]
        assert ev["episodes_this_iter"] == 2
        assert np.isfinite(ev["episode_reward_mean"])
        # Eval must not corrupt the training rollout's live RSSM carry.
        r2 = algo.train()
        assert np.isfinite(r2["model_loss"])
    finally:
        algo.cleanup()
