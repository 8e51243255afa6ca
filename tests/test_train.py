"""Ray-Train-equivalent tests: the BASELINE minimum slice (JaxTrainer MNIST
MLP, 1 CPU worker) and multi-worker data-parallel training with gradient
allreduce through the collective plane."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.air import session
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import CheckpointConfig, RunConfig, ScalingConfig
from ray_tpu.train.jax import JaxTrainer


def _synthetic_mnist(n=256, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 784)).astype(np.float32)
    w_true = rng.standard_normal((784, 10)).astype(np.float32)
    y = (x @ w_true).argmax(-1).astype(np.int32)
    return x, y


def mnist_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.mlp import init_mlp, mlp_loss

    x, y = _synthetic_mnist()
    params = init_mlp(jax.random.PRNGKey(0), (784, 64, 10))
    opt = optax.adam(config.get("lr", 1e-2))
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, acc), grads = jax.value_and_grad(mlp_loss, has_aux=True)(params, batch)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    for epoch in range(config.get("epochs", 5)):
        params, opt_state, loss, acc = step(params, opt_state, batch)
        session.report(
            {"epoch": epoch, "loss": float(loss), "acc": float(acc)},
            checkpoint=Checkpoint.from_dict({"epoch": epoch}) if epoch % 2 == 0 else None,
        )


def test_jax_trainer_minimum_slice(ray_start_regular):
    """BASELINE config #1: JaxTrainer MNIST MLP, 1 CPU worker, end-to-end."""
    trainer = JaxTrainer(
        mnist_loop,
        train_loop_config={"epochs": 6, "lr": 1e-2},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            storage_path="/tmp/rtpu_train_test",
            checkpoint_config=CheckpointConfig(num_to_keep=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["epoch"] == 5
    assert result.metrics["loss"] < 2.0
    assert result.checkpoint is not None
    assert result.checkpoint.to_dict()["epoch"] == 4


def dp_loop(config):
    """2-worker data-parallel loop: grads allreduced over the XLA world."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.air import session
    from ray_tpu.util import collective as col

    rank = session.get_world_rank()
    world = session.get_world_size()
    # Per-rank shard of a quadratic problem: minimise sum over all shards.
    w = jnp.zeros((4,))
    targets = jnp.full((4,), float(rank + 1))

    def loss_fn(w):
        return jnp.sum((w - targets) ** 2)

    for step_i in range(10):
        g = jax.grad(loss_fn)(w)
        g_sum = jnp.asarray(col.allreduce(g, group_name="train"))
        w = w - 0.1 * (g_sum / world)
        session.report({"step": step_i, "w0": float(w[0]), "rank": rank})


def test_jax_trainer_multi_worker_dp(ray_start_regular):
    trainer = JaxTrainer(
        dp_loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path="/tmp/rtpu_train_test"),
    )
    result = trainer.fit()
    assert result.error is None
    # Optimum of the summed objective: mean of targets = (1+2)/2 = 1.5.
    assert abs(result.metrics["w0"] - 1.5) < 0.2


def test_trainer_failure_restart(ray_start_regular):
    """Worker failure restarts the whole gang from the last checkpoint
    (reference: BackendExecutor failure path + FailureConfig)."""
    import os

    marker = f"/tmp/rtpu_train_fail_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    def flaky_loop(config):
        import os as _os
        import time as _time

        from ray_tpu.air import session

        ckpt = session.get_checkpoint()
        start = ckpt.to_dict()["epoch"] + 1 if ckpt else 0
        for epoch in range(start, 4):
            if epoch == 2 and not _os.path.exists(config["marker"]):
                with open(config["marker"], "w") as f:
                    f.write("1")
                _os._exit(1)
            session.report(
                {"epoch": epoch, "resumed": start > 0},
                checkpoint=Checkpoint.from_dict({"epoch": epoch}),
            )
            _time.sleep(0.3)  # let the driver poll before a crash (like a real step)

    from ray_tpu.air.config import FailureConfig

    trainer = JaxTrainer(
        flaky_loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            storage_path="/tmp/rtpu_train_test",
            failure_config=FailureConfig(max_failures=2),
        ),
    )
    result = trainer.fit()
    assert result.metrics["epoch"] == 3
    assert result.metrics["resumed"] is True
    os.unlink(marker)


def test_sklearn_trainer(ray_start_regular):
    """SklearnTrainer fits remotely on a Dataset and checkpoints the
    estimator (reference: train/sklearn/sklearn_trainer.py)."""
    from sklearn.linear_model import LogisticRegression

    from ray_tpu import data as rdata
    from ray_tpu.train import SklearnTrainer

    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    rows = [{"a": X[i, 0], "b": X[i, 1], "c": X[i, 2], "label": int(y[i])} for i in range(200)]
    train_ds = rdata.from_items(rows[:150])
    valid_ds = rdata.from_items(rows[150:])
    trainer = SklearnTrainer(
        estimator=LogisticRegression(),
        label_column="label",
        datasets={"train": train_ds, "valid": valid_ds},
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["train_score"] > 0.85
    assert result.metrics["valid_score"] > 0.75
    est = result.checkpoint.to_dict()["estimator"]
    pred = est.predict(X[:5])
    assert pred.shape == (5,)


def test_gbdt_trainers_gated():
    from ray_tpu.train import LightGBMTrainer, XGBoostTrainer

    with pytest.raises(ImportError, match="xgboost"):
        XGBoostTrainer(datasets={})
    with pytest.raises(ImportError, match="lightgbm"):
        LightGBMTrainer(datasets={})


@pytest.mark.parametrize("num_workers", [1, 2], ids=["one_worker", "a_gang_of_two"])
def test_a_reports_metrics_carry_the_stamps_of_the_trainers_start(ray_start_regular, num_workers):
    """ISSUE 52: ``_trainer_start`` is filled into every report on its way to
    ``on_report``, four CLOCK_MONOTONIC stamps in the order taken, and the
    user's own keys are as the loop reported them."""
    import time

    from ray_tpu.train._internal.backend_executor import TRAINER_START

    def loop(config):
        for step in range(3):
            session.report({"step": step, "loss": 1.0 / (step + 1), "rank": session.get_world_rank()})

    t0 = time.monotonic_ns()
    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=num_workers),
        run_config=RunConfig(storage_path="/tmp/rtpu_train_start_test"),
    ).fit()
    t1 = time.monotonic_ns()
    assert TRAINER_START == "_trainer_start"
    start = result.metrics.pop(TRAINER_START)
    assert result.metrics == {"step": 2, "loss": 1.0 / 3, "rank": 0}
    assert list(start) == ["t_fit_ns", "t_worker_ns", "t_mesh_ns", "t_loop_ns"]
    order = list(start.values())
    assert all(isinstance(t, int) for t in order) and order == sorted(order)
    assert t0 <= order[0] and order[-1] <= t1
    # every report of the run, not the last alone
    frame = result.metrics_dataframe
    assert list(frame["step"]) == [0, 1, 2] and all(s == start for s in frame[TRAINER_START])
