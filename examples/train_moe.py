"""Training a mixture of experts with JaxTrainer: one chip's share of an
expert-parallel job.

Dropless routed experts (softmax top-2 of 8, no router bias) under a pattern of
window layers and full layers (their rotary stretched by YaRN past 64
positions), this worker holding experts 0-3 of each layer's 8
(``expert_share``): what the other half would add to a layer is left out, as
on one chip of two. Every few steps the loop reports what its routing
looks like (``train_loop_utils.moe_reporter``: the balance term, the share of
the assignments held here, the fullest held expert over the mean) beside the loss.

Run: python examples/train_moe.py [steps]
"""

import sys
import tempfile


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.air import session
    from ray_tpu.models.transformer import TransformerConfig, init_params, make_train_step
    from ray_tpu.train.jax.train_loop_utils import moe_reporter, prepare_batch

    cfg = TransformerConfig(
        vocab_size=1024, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, max_seq_len=128,
        sliding_window=32, layer_kinds=("window", "window", "window", "full"), qk_norm=True,
        num_experts=8, experts_per_token=2, d_expert=64, router_score="softmax", router_bias=False,
        rope_scaling=dict(factor=4, original_max_position_embeddings=64, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=0),
        expert_share=(0, 2), balance_loss_coef=0.001,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    stats = moe_reporter(cfg)
    batch = prepare_batch({"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0, cfg.vocab_size)})
    every, steps = config.get("stats_every", 2), config.get("steps", 6)
    for i in range(steps):
        routed = stats(params, batch) if i % every == 0 or i == steps - 1 else {}
        params, opt_state, loss = step(params, opt_state, batch)
        session.report({"step": i, "loss": float(loss), **routed})


def main(steps: int = 6):
    import ray_tpu
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    ray_tpu.init(num_cpus=2)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": steps},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=tempfile.mkdtemp(prefix="rtpu_example_train_moe_")),  # under TMPDIR, this run's own
    )
    result = trainer.fit()
    print("final loss:", result.metrics.get("loss"), "held share:", result.metrics.get("moe/held_share"))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 6)
