"""Per-worker loop helpers (analog of train/torch/train_loop_utils.py's
prepare_model/prepare_data_loader — but TPU-native: "preparing" data means
placing host numpy shards onto the mesh as sharded jax.Arrays)."""

from __future__ import annotations


def shard_batch(batch: dict, mesh, axis: str = "dp"):
    """Host batch dict -> jax.Arrays sharded over the mesh's data axes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = [a for a in (axis, "fsdp") if mesh.shape.get(a, 1) > 1] or [axis]
    spec = P(tuple(axes))

    def place(x):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return {k: place(v) for k, v in batch.items()}


def prepare_batch(batch: dict, mesh=None):
    """device_put a host batch; sharded if a mesh is available."""
    import jax

    if mesh is None:
        return {k: jax.device_put(v) for k, v in batch.items()}
    return shard_batch(batch, mesh)


def moe_reporter(cfg, mesh=None):
    """What a loop over a model with dropless routed experts reports of them:
    ``stats = moe_reporter(cfg)`` once, then every N steps
    ``session.report({"step": i, "loss": ..., **stats(params, batch)})``. One
    forward pass's worth on the batch (``models/transformer.moe_stats``, jitted
    here once), as plain numbers: ``moe/balance`` (1.0 where routing is
    uniform), ``moe/held_share`` (of the assignments, those sent to the experts
    this program holds), ``moe/fullest_over_mean`` (the fullest held expert's
    rows over the held experts' mean, the worst layer's) and ``moe/assignments``
    (a list a layer of the assignments sent to each of ALL the experts).
    OBSERVABILITY.md, "train: routed experts"."""
    import jax

    from ray_tpu.models.transformer import moe_stats

    compiled = jax.jit(lambda params, batch: moe_stats(params, batch, cfg, mesh=mesh))

    def stats(params, batch) -> dict:
        out = jax.device_get(compiled(params, batch))
        return {
            "moe/balance": float(out["balance"]),
            "moe/held_share": float(out["held_share"]),
            "moe/fullest_over_mean": float(out["fullest_over_mean"]),
            "moe/assignments": out["assignments"].tolist(),
        }

    return stats
