"""Data-parallel LM training with JaxTrainer.

The flagship path: driver builds a trainer; each worker claims its chips,
joins the collective mesh, and runs the jitted train step (fused LM loss,
Pallas flash attention on TPU). Scale with ScalingConfig(num_workers=N,
use_tpu=True) — the same script drives 1 chip or a pod slice.

Run: python examples/train_transformer.py [steps]
"""

import sys


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.air import session
    from ray_tpu.models.transformer import TransformerConfig, init_params, make_train_step

    cfg = TransformerConfig(
        vocab_size=1024, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=256, max_seq_len=128,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
        remat=False,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0, cfg.vocab_size)
    for i in range(config.get("steps", 5)):
        params, opt_state, loss = step(params, opt_state, {"tokens": tokens})
        session.report({"step": i, "loss": float(loss)})


def main(steps: int = 5):
    import ray_tpu
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    ray_tpu.init(num_cpus=2)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": steps},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path="/tmp/rtpu_example_train"),
    )
    result = trainer.fit()
    print("final loss:", result.metrics.get("loss"))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 5)
