"""Legacy Policy facade tests.

The reference's legacy policy layer (rllib/policy/policy.py:175) is the API
external-serving and offline-eval code builds against: compute_single_action /
compute_actions / compute_log_likelihoods / postprocess_trajectory /
get-set_weights / export-from_checkpoint. Here Policy is a thin view over the
new-stack RLModule pure functions — these tests pin the surface and its
consistency with the underlying module math.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.policy import Policy, SampleBatch
from ray_tpu.rllib.policy.sample_batch import (
    ADVANTAGES,
    DONES,
    REWARDS,
    VALUE_TARGETS,
    VF_PREDS,
)


@pytest.fixture(scope="module")
def spaces():
    import gymnasium as gym

    obs = gym.spaces.Box(low=-1.0, high=1.0, shape=(4,), dtype=np.float32)
    act = gym.spaces.Discrete(3)
    return obs, act


@pytest.fixture(scope="module")
def cont_spaces():
    import gymnasium as gym

    obs = gym.spaces.Box(low=-1.0, high=1.0, shape=(6,), dtype=np.float32)
    act = gym.spaces.Box(low=-2.0, high=2.0, shape=(2,), dtype=np.float32)
    return obs, act


def test_compute_actions_shapes_and_fetches(spaces):
    policy = Policy.from_spaces(*spaces)
    obs = np.random.default_rng(0).normal(size=(16, 4)).astype(np.float32)
    actions, state, info = policy.compute_actions(obs)
    assert actions.shape == (16,)
    assert state == []
    assert info["action_logp"].shape == (16,)
    assert info["vf_preds"].shape == (16,)
    assert np.all(actions >= 0) and np.all(actions < 3)


def test_single_action_greedy_deterministic_exploring_varies(spaces):
    policy = Policy.from_spaces(*spaces)
    obs = np.ones(4, np.float32)
    greedy = {policy.compute_single_action(obs, explore=False)[0] for _ in range(5)}
    assert len(greedy) == 1  # argmax: same every call
    explored = {policy.compute_single_action(obs, explore=True)[0] for _ in range(30)}
    assert len(explored) > 1  # fresh rng fold per call


def test_log_likelihoods_match_action_fetches(spaces):
    """logp returned by compute_actions must equal compute_log_likelihoods
    re-evaluated on the same (obs, action) pairs — one set of numerics."""
    policy = Policy.from_spaces(*spaces)
    obs = np.random.default_rng(1).normal(size=(32, 4)).astype(np.float32)
    actions, _, info = policy.compute_actions(obs, explore=True)
    logp = policy.compute_log_likelihoods(actions, obs)
    np.testing.assert_allclose(logp, info["action_logp"], rtol=1e-5, atol=1e-5)


def test_continuous_actions_and_logp(cont_spaces):
    policy = Policy.from_spaces(*cont_spaces)
    obs = np.random.default_rng(2).normal(size=(8, 6)).astype(np.float32)
    actions, _, info = policy.compute_actions(obs, explore=True)
    assert actions.shape == (8, 2)
    logp = policy.compute_log_likelihoods(actions, obs)
    np.testing.assert_allclose(logp, info["action_logp"], rtol=1e-4, atol=1e-4)
    a, _, one_info = policy.compute_single_action(obs[0], explore=False)
    assert a.shape == (2,)
    assert np.isfinite(one_info["vf_preds"])


def test_postprocess_trajectory_gae(spaces):
    policy = Policy.from_spaces(*spaces)
    rng = np.random.default_rng(3)
    n = 40
    batch = SampleBatch({
        REWARDS: rng.normal(size=n).astype(np.float32),
        DONES: (rng.random(n) < 0.1).astype(np.float32),
        VF_PREDS: rng.normal(size=n).astype(np.float32),
    })
    out = policy.postprocess_trajectory(batch, last_value=0.5)
    assert np.isfinite(out[ADVANTAGES]).all()
    np.testing.assert_allclose(
        out[VALUE_TARGETS], out[ADVANTAGES] + out[VF_PREDS], rtol=1e-5
    )


def test_weights_roundtrip_and_checkpoint(tmp_path, spaces):
    import jax

    policy = Policy.from_spaces(*spaces)
    obs = np.random.default_rng(4).normal(size=(4, 4)).astype(np.float32)
    ref_actions, _, ref_info = policy.compute_actions(obs, explore=False)

    # set_weights: a perturbed copy must change outputs; restoring the
    # originals must restore them.
    orig = policy.get_weights()
    bumped = jax.tree_util.tree_map(lambda x: x + 0.5, orig)
    policy.set_weights(bumped)
    _, _, bump_info = policy.compute_actions(obs, explore=False)
    assert not np.allclose(bump_info["vf_preds"], ref_info["vf_preds"])
    policy.set_weights(orig)

    path = str(tmp_path / "ckpt")
    policy.export_checkpoint(path)
    restored = Policy.from_checkpoint(path)
    got_actions, _, got_info = restored.compute_actions(obs, explore=False)
    np.testing.assert_array_equal(got_actions, ref_actions)
    np.testing.assert_allclose(got_info["vf_preds"], ref_info["vf_preds"], rtol=1e-6)


def test_algorithm_get_policy_end_to_end():
    """algo.get_policy() must hand back a Policy whose greedy actions match
    Algorithm.compute_single_action (the serving path equals the training
    snapshot)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import PPOConfig

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        cfg = (
            PPOConfig()
            .environment("CartPole-v1")
            .rollouts(num_rollout_workers=1, num_envs_per_worker=2)
            .training(train_batch_size=400, num_sgd_iter=2)
            .debugging(seed=0)
        )
        algo = cfg.build()
        try:
            algo.step()
            policy = algo.get_policy()
            for obs in (np.zeros(4, np.float32), np.ones(4, np.float32)):
                a_algo = algo.compute_single_action(obs, explore=False)
                a_pol, _, _ = policy.compute_single_action(obs, explore=False)
                assert a_algo == a_pol
            # gamma/lambda flow into postprocessing config
            assert policy.config["gamma"] == pytest.approx(cfg.gamma)
        finally:
            algo.cleanup()
    finally:
        ray_tpu.shutdown()


def test_policy_applies_observation_filter(tmp_path, spaces):
    """A policy trained behind a MeanStdFilter must apply the SAME filter at
    inference (and carry it through checkpoints) — raw observations fed to
    the network would be distribution-shifted garbage."""
    import jax

    from ray_tpu.rllib.connectors import MeanStdFilter

    f = MeanStdFilter()
    rng = np.random.default_rng(5)
    f(rng.normal(loc=100.0, scale=3.0, size=(256, 4)))  # accumulate stats

    policy = Policy.from_spaces(*spaces)
    obs = rng.normal(loc=100.0, scale=3.0, size=(8, 4)).astype(np.float32)

    _, _, raw_info = policy.compute_actions(obs, explore=False)
    policy._obs_filter_state = f.get_state()
    _, _, filt_info = policy.compute_actions(obs, explore=False)
    # filtered obs are ~N(0,1) around the running mean; values must differ
    assert not np.allclose(filt_info["vf_preds"], raw_info["vf_preds"])
    # equivalent to filtering by hand
    byhand = np.asarray(f.transform(obs), np.float32)
    _, _, ref_info = Policy(policy.spec, policy.params).compute_actions(byhand, explore=False)
    np.testing.assert_allclose(filt_info["vf_preds"], ref_info["vf_preds"], rtol=1e-5)

    path = str(tmp_path / "fckpt")
    policy.export_checkpoint(path)
    restored = Policy.from_checkpoint(path)
    _, _, rest_info = restored.compute_actions(obs, explore=False)
    np.testing.assert_allclose(rest_info["vf_preds"], filt_info["vf_preds"], rtol=1e-6)
