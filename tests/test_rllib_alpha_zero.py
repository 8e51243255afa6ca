"""AlphaZero (single-player MCTS) on state-cloneable CartPole.

Learning-gated (reference: rllib/algorithms/alpha_zero/ CartPole example):
self-play must improve substantially, and MCTS-planned evaluation must
reach near the horizon cap.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def ray_cluster():
    import jax

    jax.config.update("jax_platforms", "cpu")
    ray_tpu.init(num_cpus=2, object_store_memory=96 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow  # a learning curve: 22 iterations of self-play, ~2 minutes; its quick case is the round trip below
def test_alpha_zero_learns_cartpole(ray_cluster):
    from ray_tpu.rllib import AlphaZeroConfig

    cfg = (
        AlphaZeroConfig()
        .environment("CartPole-v1")
        .training(
            num_sims=25,
            episodes_per_iter=3,
            updates_per_iter=30,
            horizon=200,
            lr=5e-3,
            temperature_timesteps=1500,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    best = 0.0
    try:
        for _ in range(22):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 120:
                break
        assert best >= 100, f"AlphaZero self-play failed to improve (best={best})"

        # Planning-mode evaluation: MCTS + learned net should max out (or
        # nearly max out) the horizon.
        totals = []
        for ep in range(2):
            obs, _ = algo.env.reset(seed=900 + ep)
            total, done = 0.0, False
            while not done:
                a = algo.compute_single_action(obs, use_mcts=True)
                obs, rr, term, trunc, _ = algo.env.step(a)
                total += rr
                done = term or trunc
            totals.append(total)
        assert np.mean(totals) >= 150, f"MCTS evaluation weak: {totals}"
    finally:
        algo.cleanup()


@pytest.mark.parametrize("use_mcts", [False, True], ids=["the net's own action", "the search's action"])
def test_alpha_zero_checkpoint_roundtrip(ray_cluster, use_mcts):
    """The learning run's stand-in in tier-1: two iterations of self-play report
    finite numbers, and a second instance restored from the checkpoint holds the
    same weights and picks the same actions, by the net alone or by the search."""
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import AlphaZeroConfig

    cfg = (
        AlphaZeroConfig()
        .environment("CartPole-v1")
        .training(num_sims=8, episodes_per_iter=1, updates_per_iter=3, horizon=50)
        .debugging(seed=0)
    )

    def act(algo, obs):
        if use_mcts:  # the search steps the env from its state: the same start for both
            algo.env.reset(seed=7)
        return algo.compute_single_action(obs, use_mcts=use_mcts)

    keys = ("pi_loss", "v_loss", "total_loss", "episode_reward_mean")
    with two_iterations_then_a_restored_twin(cfg, keys, 4, act=act) as (_, algo, algo2):
        assert algo2._timesteps_total == algo._timesteps_total
        import jax

        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
            algo.params, algo2.params,
        )


def test_state_clone_wrapper_restores_exactly(ray_cluster):
    import gymnasium as gym

    from ray_tpu.rllib.algorithms.alpha_zero import StateCloneWrapper

    env = StateCloneWrapper(gym.make("CartPole-v1"), horizon=100)
    obs, _ = env.reset(seed=3)
    state = env.get_state()
    o1, *_ = env.step(0)
    env.set_state(state)
    o2, *_ = env.step(0)
    np.testing.assert_allclose(o1, o2)
    env.set_state(state)
    o3, *_ = env.step(1)
    assert not np.allclose(o1, o3)
    env.close()
