"""ray_tpu.rllib tests.

Modeled on the reference's rllib test strategy (per-algorithm learning tests
against CartPole with a reward stop criterion — rllib/tuned_examples/ppo/
cartpole-ppo.yaml reward 150; unit tests for SampleBatch/GAE/buffers)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.policy.sample_batch import (
    ADVANTAGES,
    DONES,
    REWARDS,
    VALUE_TARGETS,
    VF_PREDS,
    SampleBatch,
    compute_gae,
)
from ray_tpu.rllib.utils.replay_buffers import PrioritizedReplayBuffer, ReplayBuffer


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_sample_batch_basics():
    b = SampleBatch({"a": np.arange(10), "b": np.arange(10) * 2.0})
    assert b.count == 10
    cat = SampleBatch.concat_samples([b, b])
    assert cat.count == 20
    sh = b.shuffle(seed=0)
    assert sorted(sh["a"]) == list(range(10))
    mbs = list(cat.minibatches(8, seed=1))
    assert all(mb.count == 8 for mb in mbs)


def test_gae_matches_reference_impl():
    rng = np.random.default_rng(0)
    n = 50
    batch = SampleBatch({
        REWARDS: rng.normal(size=n).astype(np.float32),
        DONES: (rng.random(n) < 0.1).astype(np.float32),
        VF_PREDS: rng.normal(size=n).astype(np.float32),
    })
    last_v = 0.3
    gamma, lam = 0.95, 0.9
    out = compute_gae(SampleBatch(dict(batch)), last_v, gamma, lam)
    # brute-force forward recomputation
    rewards, dones, values = batch[REWARDS], batch[DONES], batch[VF_PREDS]
    vals_ext = np.append(values, last_v)
    adv = np.zeros(n)
    for t in range(n):
        acc, coef = 0.0, 1.0
        for k in range(t, n):
            nonterm = 1.0 - dones[k]
            delta = rewards[k] + gamma * vals_ext[k + 1] * nonterm - values[k]
            acc += coef * delta
            if dones[k]:
                break
            coef *= gamma * lam
        adv[t] = acc
    np.testing.assert_allclose(out[ADVANTAGES], adv, atol=1e-4)
    np.testing.assert_allclose(out[VALUE_TARGETS], adv + values, atol=1e-4)


def test_replay_buffer_ring():
    buf = ReplayBuffer(capacity=100, seed=0)
    for i in range(5):
        buf.add(SampleBatch({"x": np.full(30, i)}))
    assert len(buf) == 100
    s = buf.sample(64)
    assert s.count == 64
    assert set(np.unique(s["x"])).issubset({1, 2, 3, 4})  # 0s evicted


def test_prioritized_replay_updates():
    buf = PrioritizedReplayBuffer(capacity=64, alpha=1.0, seed=0)
    buf.add(SampleBatch({"x": np.arange(64, dtype=np.float32)}))
    s = buf.sample(16)
    assert "weights" in s
    buf.update_priorities(np.ones(16) * 5.0)
    s2 = buf.sample(32)
    assert s2.count == 32


def test_vector_env_autoreset():
    from ray_tpu.rllib.env.vector_env import VectorEnv

    env = VectorEnv("CartPole-v1", 3, seed=0)
    total_done = 0
    for _ in range(300):
        _, _, dones, _ = env.step(np.zeros(3, dtype=np.int64))
        total_done += dones.sum()
    assert total_done > 0
    rewards, lens = env.pop_episode_stats()
    assert len(rewards) == total_done
    assert all(l > 0 for l in lens)
    env.close()


def test_ppo_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import PPOConfig

    cfg = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4)
        .training(lr=3e-4, train_batch_size=2048, sgd_minibatch_size=256, num_sgd_iter=8, entropy_coeff=0.01)
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
        assert best >= 120, f"PPO failed to learn CartPole (best={best})"
        a = algo.compute_single_action(np.zeros(4, np.float32))
        assert a in (0, 1)
    finally:
        algo.cleanup()


def test_ppo_checkpoint_restore(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import PPOConfig

    cfg = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=1, num_envs_per_worker=2)
        .training(train_batch_size=256, sgd_minibatch_size=64, num_sgd_iter=2)
    )
    algo = cfg.build()
    algo.step()
    ckpt = algo.save_checkpoint()
    w_before = algo.get_policy_weights()
    algo.step()  # weights move on
    algo.load_checkpoint(ckpt)
    w_after = algo.get_policy_weights()
    flat_b = np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(w_before)])
    flat_a = np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(w_after)])
    np.testing.assert_allclose(flat_b, flat_a)
    algo.cleanup()


def test_dqn_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import DQNConfig

    cfg = (
        DQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_envs_per_worker=4)
        .training(
            lr=1e-3,
            train_batch_size=64,
            learning_starts=500,
            target_network_update_freq=100,
            epsilon_timesteps=4000,
            rollout_steps_per_iter=500,
            train_intensity=2,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(20):
            r = algo.step()
            best = max(best, r["episode_reward_mean"])
            if best >= 100:
                break
        assert best >= 100, f"DQN failed to learn CartPole (best={best})"
    finally:
        algo.cleanup()


def test_ppo_under_tune(ray_cluster):
    """Algorithms are Tune Trainables (reference: Algorithm extends
    Trainable; tune.Tuner(PPO) runs a sweep)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu import tune
    from ray_tpu.rllib import PPO

    tuner = tune.Tuner(
        PPO,
        param_space={
            "env": "CartPole-v1",
            "num_rollout_workers": 1,
            "num_envs_per_worker": 2,
            "train_batch_size": 256,
            "sgd_minibatch_size": 64,
            "num_sgd_iter": 2,
            "lr": tune.grid_search([3e-4, 1e-3]),
        },
        tune_config=tune.TuneConfig(metric="episode_reward_mean", mode="max"),
        run_config=tune.RunConfig(stop={"training_iteration": 2}),
    )
    results = tuner.fit()
    assert len(results) == 2
    assert results.get_best_result() is not None


def test_rollout_worker_fault_tolerance(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib.core.rl_module import RLModuleSpec
    from ray_tpu.rllib.evaluation.rollout_worker import WorkerSet

    import gymnasium as gym

    probe = gym.make("CartPole-v1")
    spec = RLModuleSpec.from_spaces(probe.observation_space, probe.action_space)
    probe.close()
    ws = WorkerSet("CartPole-v1", spec, num_workers=2, num_envs_per_worker=1)
    from ray_tpu.rllib.core.learner import Learner
    from ray_tpu.rllib.algorithms.ppo.ppo import ppo_loss

    learner = Learner(spec, ppo_loss)
    ws.sync_weights(learner.get_weights())
    batches = ws.sample(16)
    assert len(batches) == 2
    # Kill one worker's actor (kill lands asynchronously); keep sampling —
    # the round where the death lands must still succeed with the survivor,
    # and after a respawn + weight sync the set must be back to full size.
    import time

    ray_tpu.kill(ws._workers[0])
    saw_degraded = False
    for _ in range(20):
        batches = ws.sample(8)
        assert len(batches) >= 1
        if len(batches) < 2:
            saw_degraded = True
            break
        time.sleep(0.2)
    assert saw_degraded, "kill never landed"
    ws.sync_weights(learner.get_weights())
    batches = ws.sample(8)
    assert len(batches) == 2
    ws.stop()


def test_offline_json_roundtrip(tmp_path):
    """JsonWriter/JsonReader roundtrip + return-to-go targets."""
    from ray_tpu.rllib.offline import JsonReader, JsonWriter
    from ray_tpu.rllib.policy.sample_batch import VALUE_TARGETS

    w = JsonWriter(str(tmp_path))
    w.write(
        SampleBatch(
            {
                "obs": np.arange(8, dtype=np.float32).reshape(4, 2),
                "actions": np.array([0, 1, 0, 1]),
                "rewards": np.array([1.0, 1.0, 1.0, 1.0], np.float32),
                "dones": np.array([False, True, False, True]),
            }
        )
    )
    w.close()
    r = JsonReader(str(tmp_path), gamma=0.5)
    b = r.next()
    assert len(b) == 4
    # episode 1: returns [1 + .5, 1]; episode 2 same
    assert np.allclose(b[VALUE_TARGETS], [1.5, 1.0, 1.5, 1.0])
    mini = r.next(2)
    assert len(mini) == 2


def test_bc_imitates_expert(ray_cluster, tmp_path):
    """BC learns an obs->action rule from offline data (reference:
    rllib/algorithms/bc tests): expert picks action = 1 iff obs[0] > 0."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import BCConfig
    from ray_tpu.rllib.offline import JsonWriter

    rng = np.random.default_rng(0)
    obs = rng.uniform(-1, 1, size=(2000, 4)).astype(np.float32)
    actions = (obs[:, 0] > 0).astype(np.int64)
    w = JsonWriter(str(tmp_path))
    w.write(
        SampleBatch(
            {
                "obs": obs,
                "actions": actions,
                "rewards": np.ones(2000, np.float32),
                "dones": (np.arange(2000) % 100 == 99),
            }
        )
    )
    w.close()

    cfg = (
        BCConfig()
        .environment("CartPole-v1")  # spaces only; no rollouts
        .rollouts(num_rollout_workers=0)
        .training(lr=5e-3, train_batch_size=512)
        .debugging(seed=0)
    )
    cfg.offline_data(input_=str(tmp_path))
    algo = cfg.build()
    try:
        first = None
        for _ in range(60):
            r = algo.step()
            if first is None:
                first = r["bc_logp"]
        assert r["bc_logp"] > first, (first, r["bc_logp"])
        # The learned policy reproduces the expert rule.
        correct = 0
        probe = rng.uniform(-1, 1, size=(50, 4)).astype(np.float32)
        for o in probe:
            a = algo.compute_single_action(o)
            correct += int(a == int(o[0] > 0))
        assert correct >= 45, f"BC policy only matched {correct}/50 expert actions"
    finally:
        algo.cleanup()


def test_marwil_prefers_high_return_actions(ray_cluster, tmp_path):
    """MARWIL upweights trajectories with higher return-to-go: with mixed
    expert/anti-expert data where the expert earns more reward, beta>0 must
    recover the expert rule."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import MARWILConfig
    from ray_tpu.rllib.offline import JsonWriter

    rng = np.random.default_rng(1)
    n = 3000
    obs = rng.uniform(-1, 1, size=(n, 4)).astype(np.float32)
    expert_a = (obs[:, 0] > 0).astype(np.int64)
    # half the data follows the expert (reward 1), half does the opposite (reward 0)
    follow = rng.uniform(size=n) < 0.5
    actions = np.where(follow, expert_a, 1 - expert_a)
    rewards = np.where(follow, 1.0, 0.0).astype(np.float32)
    dones = np.ones(n, bool)  # 1-step episodes: return == immediate reward
    w = JsonWriter(str(tmp_path))
    w.write(SampleBatch({"obs": obs, "actions": actions, "rewards": rewards, "dones": dones}))
    w.close()

    cfg = (
        MARWILConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0)
        .training(lr=5e-3, train_batch_size=1024, beta=2.0)
        .debugging(seed=0)
    )
    cfg.offline_data(input_=str(tmp_path))
    algo = cfg.build()
    try:
        for _ in range(80):
            algo.step()
        probe = rng.uniform(-1, 1, size=(50, 4)).astype(np.float32)
        correct = sum(
            int(algo.compute_single_action(o) == int(o[0] > 0)) for o in probe
        )
        assert correct >= 40, f"MARWIL matched expert on only {correct}/50"
    finally:
        algo.cleanup()


def test_impala_learns_cartpole(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import IMPALAConfig

    cfg = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=4)
        .training(
            lr=1e-3,
            train_batch_size=2048,
            entropy_coeff=0.01,
            num_sgd_iter=2,
            broadcast_interval=1,
        )
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
        assert best >= 100, f"IMPALA failed to learn CartPole (best={best})"
    finally:
        algo.cleanup()


def test_connectors_mean_std_filter():
    """MeanStdFilter: running normalization + Chan merge across workers
    (reference: rllib/utils/filter.py + connector pipelines)."""
    from ray_tpu.rllib.connectors import (
        ClipActions,
        ConnectorPipeline,
        FlattenObservations,
        MeanStdFilter,
    )

    rng = np.random.default_rng(0)
    data = rng.normal(loc=5.0, scale=3.0, size=(500, 4))
    f = MeanStdFilter()
    out = f(data)
    assert abs(float(np.mean(out))) < 0.2
    assert abs(float(np.std(out)) - 1.0) < 0.2
    # transform() does not update stats
    st = f.get_state()
    f.transform(rng.normal(size=(100, 4)))
    assert f.get_state()["count"] == st["count"]
    # Chan merge of two shards == one filter over all data
    f1, f2, fall = MeanStdFilter(), MeanStdFilter(), MeanStdFilter()
    a, b = data[:200], data[200:]
    f1(a)
    f2(b)
    fall(data)
    merged = MeanStdFilter()
    merged.merge_states([f1.get_state(), f2.get_state()])
    np.testing.assert_allclose(merged.get_state()["mean"], fall.get_state()["mean"], rtol=1e-9)
    np.testing.assert_allclose(merged.get_state()["m2"], fall.get_state()["m2"], rtol=1e-9)
    # pipeline composes
    pipe = ConnectorPipeline([FlattenObservations(), MeanStdFilter()])
    assert pipe(rng.normal(size=(10, 2, 2))).shape == (10, 4)
    clip = ClipActions(low=-1.0, high=1.0)
    assert np.all(np.abs(clip(np.array([-5.0, 0.2, 9.0]))) <= 1.0)


def test_ppo_with_observation_filter(ray_cluster):
    """End-to-end: PPO with MeanStdFilter connectors still learns and the
    filter stats synchronize across workers."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import PPOConfig

    cfg = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, num_envs_per_worker=2, observation_filter="MeanStdFilter")
        .training(lr=3e-4, train_batch_size=1024, sgd_minibatch_size=128, num_sgd_iter=4)
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        r = None
        for _ in range(4):
            r = algo.step()
        assert np.isfinite(r["policy_loss"])
        # Both workers hold identical (merged) filter stats after sync.
        states = [
            ray_tpu.get(w.get_filter_state.remote()) for w in algo.workers._workers
        ]
        assert states[0]["count"] == states[1]["count"] > 0
        np.testing.assert_allclose(states[0]["mean"], states[1]["mean"])
        # Delta-sync accounting: the merged count equals real samples seen
        # (full-state re-merging would compound ~2x per iteration).
        total_sampled = 4 * 1024  # iterations * train_batch_size
        assert states[0]["count"] <= total_sampled * 1.2, states[0]["count"]
    finally:
        algo.cleanup()
