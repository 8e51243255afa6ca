"""SlateQ on the synthetic RecSim-style slate environment.

Learning-gated: the decomposed slate Q must clearly beat the random-slate
baseline (~17.6 mean session reward on this env/seed family) within test
time (reference: rllib/algorithms/slateq/ + RecSim interest evolution)."""

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


def test_slateq_learns_interest_evolution(ray_cluster):
    from ray_tpu.rllib import SlateQConfig
    from ray_tpu.rllib.env.recsys import SlateRecEnv

    cfg = (
        SlateQConfig()
        .environment(SlateRecEnv)
        .training(
            rollout_steps_per_iter=400,
            learning_starts=400,
            train_intensity=2,
            epsilon_timesteps=4000,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = -1e9
    try:
        for _ in range(25):
            r = algo.step()
            erm = r.get("episode_reward_mean")
            if erm == erm:  # not NaN
                best = max(best, erm)
            if best >= 24:
                break
        # Random slates score ~17.6 on this env; the decomposition must
        # push well past it.
        assert best >= 24, f"SlateQ failed to beat random slates (best={best})"
        # Greedy slate API: K distinct candidate indices.
        obs, _ = algo.env.reset(seed=7)
        slate = algo.compute_single_action(obs)
        assert len(set(int(i) for i in slate)) == algo.K
        assert all(0 <= int(i) < algo.C for i in slate)
    finally:
        algo.cleanup()


def test_slateq_checkpoint_roundtrip(ray_cluster):
    from ray_tpu.rllib import SlateQConfig
    from ray_tpu.rllib.env.recsys import SlateRecEnv

    cfg = (
        SlateQConfig()
        .environment(SlateRecEnv)
        .training(rollout_steps_per_iter=100, learning_starts=50, train_intensity=4)
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.step()
    ckpt = algo.save_checkpoint()
    algo2 = cfg.build()
    algo2.load_checkpoint(ckpt)
    assert algo2._timesteps_total == algo._timesteps_total
    import jax

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        algo.params, algo2.params,
    )
    algo.cleanup()
    algo2.cleanup()
