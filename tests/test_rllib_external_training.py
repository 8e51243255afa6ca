"""Serving-abstraction integration: external simulators TRAIN live algorithms.

The reference's cartpole_server/client pattern (rllib/env/policy_server_input
as config.input_; examples/serving/): an external process owns the env loop,
gets actions over HTTP from the algorithm's policy, and the completed
episodes feed the algorithm's training. Two paths covered:

- MARWIL via ExternalInputReader (PolicyServerInput as config.input_ — the
  reference's exact wiring for offline-capable algorithms), and
- DQN via replay-buffer ingestion (external SampleBatches share the buffer
  schema with on-policy rollouts).
"""

import threading

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.env import PolicyClient, PolicyServerInput


@pytest.fixture(scope="module")
def ray_cluster():
    import jax

    jax.config.update("jax_platforms", "cpu")
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _drive_external_episodes(address, n_episodes, policy=None, max_steps=40):
    """External-sim loop: gymnasium CartPole stepped CLIENT-side, actions
    from the server (or a local scripted policy logged via log_action)."""
    import gymnasium as gym

    client = PolicyClient(address)
    returns = []
    env = gym.make("CartPole-v1")
    for _ in range(n_episodes):
        obs, _ = env.reset(seed=int(np.random.default_rng().integers(1 << 30)))
        eid = client.start_episode()
        total, steps = 0.0, 0
        while True:
            if policy is None:
                action = client.get_action(eid, obs.astype(np.float32))
            else:
                action = policy(obs)
                client.log_action(eid, obs.astype(np.float32), action)
            obs, r, term, trunc, _ = env.step(int(action))
            client.log_returns(eid, float(r))
            total += float(r)
            steps += 1
            if term or trunc or steps >= max_steps:
                client.end_episode(eid, obs.astype(np.float32))
                break
        returns.append(total)
    return returns


def test_marwil_trains_from_external_clients(ray_cluster):
    """PolicyServerInput as config.input_: client-side expert episodes flow
    through ExternalInputReader into MARWIL updates (the reference's
    input-reader wiring for external experiences)."""
    from ray_tpu.rllib import MARWILConfig

    server = PolicyServerInput(compute_action=lambda obs, explore: 0)
    try:
        expert = lambda obs: int(obs[2] > 0)  # push toward the pole's lean
        _drive_external_episodes(server.address, n_episodes=6, policy=expert)

        cfg = (
            MARWILConfig()
            .environment("CartPole-v1")
            .rollouts(num_rollout_workers=0)
            .training(lr=5e-3, train_batch_size=128, beta=1.0)
            .debugging(seed=0)
        )
        cfg.offline_data(input_=server)
        algo = cfg.build()
        try:
            m = algo.step()
            assert np.isfinite(m.get("loss", m.get("total_loss", np.nan))), m
            # More external episodes mid-training fold into the window.
            _drive_external_episodes(server.address, n_episodes=2, policy=expert)
            m2 = algo.step()
            assert np.isfinite(m2.get("loss", m2.get("total_loss", np.nan))), m2
            assert algo._timesteps_total > 0
        finally:
            algo.cleanup()
    finally:
        server.shutdown()


def test_input_reader_kwargs_reach_the_reader(ray_cluster):
    """config.offline_data(input_reader_kwargs=...) tunes the external
    reader (slow-simulator timeout etc.) without bypassing the input_ seam."""
    from ray_tpu.rllib import MARWILConfig

    server = PolicyServerInput(compute_action=lambda obs, explore: 0)
    try:
        _drive_external_episodes(server.address, 1, policy=lambda o: 0, max_steps=5)
        cfg = MARWILConfig().environment("CartPole-v1").rollouts(num_rollout_workers=0)
        cfg.offline_data(
            input_=server,
            input_reader_kwargs={"timeout_s": 5.0, "min_episodes": 1, "window_rows": 256},
        )
        algo = cfg.build()
        try:
            assert algo.reader._timeout == 5.0
            assert algo.reader._window.capacity == 256
        finally:
            algo.cleanup()
    finally:
        server.shutdown()


def test_dqn_serves_actions_and_trains_on_external_episodes(ray_cluster):
    """The live algorithm's policy answers client get_action; its replay
    buffer ingests the collected external episodes and a gradient step
    runs on them."""
    from ray_tpu.rllib import DQNConfig

    cfg = (
        DQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0)
        .training(learning_starts=0, train_batch_size=32)
        .debugging(seed=0)
    )
    algo = cfg.build()
    server = PolicyServerInput(
        compute_action=lambda obs, explore: int(
            algo.compute_single_action(np.asarray(obs, np.float32))
        )
    )
    try:
        returns = _drive_external_episodes(server.address, n_episodes=4)
        assert len(returns) == 4 and all(r > 0 for r in returns)
        batch = server.next_batch(min_episodes=4)
        assert batch is not None and len(batch) == int(sum(returns))
        algo.buffer.add(batch)
        algo._timesteps_total += len(batch)
        metrics = algo._train_once()
        loss = next(v for k, v in metrics.items() if "loss" in k.lower())
        assert np.isfinite(loss), metrics
    finally:
        server.shutdown()
        algo.cleanup()


def test_concurrent_external_clients(ray_cluster):
    """Multiple client sims against one server: episode isolation holds.
    Every client stamps its thread id into all its observations AND
    actions, so cross-episode contamination (one client's rows landing in
    another's episode) is directly detectable — not just contiguity."""
    server = PolicyServerInput(compute_action=lambda obs, explore: 1)
    steps_per_ep, eps_per_client, n_clients = 7, 3, 3

    def drive(tid):
        client = PolicyClient(server.address)
        for ep in range(eps_per_client):
            eid = client.start_episode()
            for step in range(steps_per_ep):
                obs = np.array([tid, ep, step, 0], np.float32)
                client.log_action(eid, obs, int(tid))
                client.log_returns(eid, float(tid))
            client.end_episode(eid, np.array([tid, ep, steps_per_ep, 0], np.float32))

    try:
        threads = [threading.Thread(target=drive, args=(t,)) for t in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        batch = server.next_batch(min_episodes=n_clients * eps_per_client)
        assert batch is not None
        assert len(batch) == n_clients * eps_per_client * steps_per_ep
        eps = np.asarray(batch["eps_id"])
        obs = np.asarray(batch["obs"])
        acts = np.asarray(batch["actions"])
        rews = np.asarray(batch["rewards"])
        dones = np.asarray(batch["dones"])
        assert len(set(eps.tolist())) == n_clients * eps_per_client
        # Episodes must be CONTIGUOUS runs each ending in done=1 —
        # _add_return_targets's single backward scan (resetting on dones)
        # depends on this batch layout.
        changes = np.flatnonzero(np.diff(eps) != 0)
        assert len(set(eps.tolist())) == len(changes) + 1
        for boundary in changes:
            assert dones[boundary] == 1.0
        assert dones[-1] == 1.0
        for e in set(eps.tolist()):
            rows = eps == e
            tids = obs[rows][:, 0]
            # All rows of one episode belong to exactly one client...
            assert len(set(tids.tolist())) == 1, f"episode {e} mixes clients"
            tid = tids[0]
            # ...and carry that client's actions/rewards/step sequence.
            assert (acts[rows] == tid).all()
            assert (rews[rows] == tid).all()
            assert obs[rows][:, 2].tolist() == list(range(steps_per_ep))
    finally:
        server.shutdown()
