"""ops/losses.fused_lm_loss numerics vs the materialized log-softmax path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.transformer import TransformerConfig, init_params, loss_fn
from ray_tpu.ops.losses import fused_lm_loss


def _naive(x, head, targets):
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0].mean()


@pytest.mark.parametrize("chunk", [64, 128, 1000])  # 1000: non-dividing -> _pick_chunk
def test_fused_matches_naive_forward_and_grad(chunk):
    key = jax.random.PRNGKey(0)
    N, D, V = 256, 64, 512
    x = jax.random.normal(key, (N, D), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(1), (D, V), jnp.float32) * 0.1
    targets = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V)

    f_fused = lambda x, h: fused_lm_loss(x, h, targets, chunk_size=chunk)
    f_naive = lambda x, h: _naive(x, h, targets)

    lf = f_fused(x, head)
    ln = f_naive(x, head)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)

    gf = jax.grad(f_fused, argnums=(0, 1))(x, head)
    gn = jax.grad(f_naive, argnums=(0, 1))(x, head)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gn[0]), rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gn[1]), rtol=2e-4, atol=2e-6)


def test_fused_bf16_inputs_finite_and_close():
    N, D, V = 128, 32, 256
    x = (jax.random.normal(jax.random.PRNGKey(0), (N, D)) * 2).astype(jnp.bfloat16)
    head = (jax.random.normal(jax.random.PRNGKey(1), (D, V)) * 0.2).astype(jnp.bfloat16)
    targets = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V)
    loss = fused_lm_loss(x, head, targets)
    naive = _naive(x.astype(jnp.float32), head.astype(jnp.float32), targets)
    assert jnp.isfinite(loss)
    np.testing.assert_allclose(float(loss), float(naive), rtol=3e-2)


def test_model_loss_fused_matches_unfused():
    cfg_base = dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=32, dtype=jnp.float32, remat=False,
    )
    cfg_f = TransformerConfig(**cfg_base, fused_loss=True)
    cfg_u = TransformerConfig(**cfg_base, fused_loss=False)
    params = init_params(jax.random.PRNGKey(0), cfg_f)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 128)
    batch = {"tokens": tokens}
    lf = loss_fn(params, batch, cfg_f)
    lu = loss_fn(params, batch, cfg_u)
    np.testing.assert_allclose(float(lf), float(lu), rtol=1e-5)
    gf = jax.grad(lambda p: loss_fn(p, batch, cfg_f))(params)
    gu = jax.grad(lambda p: loss_fn(p, batch, cfg_u))(params)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6)


def test_fused_under_jit_and_mesh():
    """Compiles under jit with a tp-sharded head (sharding propagation must
    handle the chunked scan; 8-device CPU mesh from conftest)."""
    import numpy as _np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs multi-device CPU mesh")
    mesh = Mesh(_np.array(devs[:2]), ("tp",))
    N, D, V = 128, 32, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(1), (D, V), jnp.float32) * 0.1
    targets = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V)
    head = jax.device_put(head, NamedSharding(mesh, P(None, "tp")))
    loss = jax.jit(lambda x, h: fused_lm_loss(x, h, targets))(x, head)
    naive = _naive(x, jax.device_put(head, NamedSharding(mesh, P(None, None))), targets)
    np.testing.assert_allclose(float(loss), float(naive), rtol=1e-5)


_TOY = dict(
    vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=1024, dtype=jnp.float32, remat=False,
)


def _mesh(**axes):
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    n = int(np.prod(list(axes.values())))
    return create_mesh(MeshConfig(**axes), devices=jax.devices()[:n])


def _placed(params, tokens, cfg, mesh):
    from jax.sharding import NamedSharding

    from ray_tpu.models.transformer import param_logical_axes
    from ray_tpu.parallel.mesh import logical_to_spec, shard_by_logical_axes

    spec = NamedSharding(mesh, logical_to_spec(("batch", None)))
    return shard_by_logical_axes(params, param_logical_axes(cfg), mesh), {"tokens": jax.device_put(tokens, spec)}


@pytest.mark.parametrize(
    "axes, rows, seq",
    [
        (dict(dp=4), 4, 1024),  # one row a device, two chunks of 512
        (dict(dp=4), 8, 1024),  # two rows a device
        (dict(dp=2, fsdp=2), 4, 1024),  # the head enters gathered over fsdp
        (dict(dp=2, sp=2), 4, 1024),  # a split of the sequence is as good as one of the batch
        (dict(dp=2, pp=2), 4, 64),  # an axis no token rule names: its members repeat the loss
        (dict(dp=2, tp=2), 4, 64),  # a split vocabulary keeps the partitioner's path
    ],
    ids=["dp4", "dp4-2rows", "dp2xfsdp2", "dp2xsp2", "dp2xpp2", "dp2xtp2"],
)
def test_loss_and_grads_under_a_mesh_match_no_mesh(axes, rows, seq):
    """Each device scans its own rows inside a shard_map (PR 36): the same
    loss and the same gradient on every leaf as the unsharded program."""
    cfg = TransformerConfig(**_TOY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, seq + 1), 0, cfg.vocab_size)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg)))(params, {"tokens": tokens})

    mesh = _mesh(**axes)
    step = jax.jit(jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg, mesh)))
    got_loss, got = step(*_placed(params, tokens, cfg, mesh))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5 * scale, err_msg=jax.tree_util.keystr(path)
        )
    sharded = "shard_map" in str(jax.make_jaxpr(lambda h, t: fused_lm_loss(
        h, params["lm_head"], t, mesh=mesh))(jnp.zeros((rows, seq, cfg.d_model)), tokens[:, 1:]))
    assert sharded == (axes.get("tp", 1) == 1)


def test_a_mesh_of_one_device_is_the_program_without_a_mesh():
    """`train2.dense-4k` runs under the trainer's mesh of one chip: not a
    character of its lowered step may depend on that mesh."""
    cfg = TransformerConfig(**{**_TOY, "dtype": jnp.bfloat16, "remat": True})
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 1025), jnp.int32)}

    def lowered(mesh):
        return jax.jit(jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg, mesh))).lower(params, batch).as_text()

    assert lowered(_mesh(dp=1)) == lowered(None)


def test_rows_that_do_not_divide_keep_the_partitioners_path():
    mesh = _mesh(dp=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 32, 16), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(1), (16, 64), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (6, 32), 0, 64)
    np.testing.assert_allclose(
        float(jax.jit(lambda x, h: fused_lm_loss(x, h, targets, mesh=mesh))(x, head)),
        float(_naive(x, head, targets)), rtol=1e-5,
    )


def test_sliding_window_train_step_runs_and_differs():
    """Training path with sliding_window: loss_fn is finite, grads flow,
    and the window genuinely changes the loss vs full attention."""
    from ray_tpu.models.transformer import TransformerConfig, init_params, loss_fn

    base = dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=64, dtype=jnp.float32, remat=False,
    )
    cfg_w = TransformerConfig(**base, sliding_window=8)
    cfg_f = TransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(0), cfg_w)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 128)}
    lw = loss_fn(params, batch, cfg_w)
    lf = loss_fn(params, batch, cfg_f)
    assert jnp.isfinite(lw) and jnp.isfinite(lf)
    assert abs(float(lw) - float(lf)) > 1e-6, "window had no effect on loss"
    grads = jax.grad(lambda p: loss_fn(p, batch, cfg_w))(params)
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
