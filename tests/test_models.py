"""Model zoo smoke + sharded-train-step tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.mlp import init_mlp, mlp_forward, mlp_loss
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    make_train_step,
    num_params,
    param_logical_axes,
)
from ray_tpu.parallel.mesh import MeshConfig, create_mesh, logical_to_spec


def tiny_cfg(**kw):
    defaults = dict(
        vocab_size=128,
        d_model=32,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        max_seq_len=64,
        dtype=jnp.float32,
        remat=False,
    )
    defaults.update(kw)
    return TransformerConfig(**defaults)


def test_mlp_forward_and_loss():
    params = init_mlp(jax.random.PRNGKey(0), (16, 8, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    y = jnp.zeros((8,), jnp.int32)
    loss, acc = mlp_loss(params, {"x": x, "y": y})
    assert np.isfinite(float(loss))


def test_transformer_forward_shapes():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_transformer_gqa_and_moe():
    cfg = tiny_cfg(num_experts=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_transformer_loss_decreases():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_transformer_sharded_train_step():
    """Full train step jitted over a dp×tp mesh with logical-axis shardings —
    the single-host version of what __graft_entry__.dryrun_multichip does."""
    from jax.sharding import NamedSharding

    cfg = tiny_cfg()
    mesh = create_mesh(MeshConfig(dp=2, tp=2, fsdp=2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    axes = param_logical_axes(cfg)

    def spec_for(path, leaf):
        node = axes
        for p in path:
            node = node[p.key]
        return logical_to_spec(node)

    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.device_put(leaf, NamedSharding(mesh, spec_for(path, leaf))),
        params,
    )
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab_size)
    batch = {"tokens": jax.device_put(tokens, NamedSharding(mesh, logical_to_spec(("batch", None))))}
    params, opt_state, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))


def test_resnet_forward():
    from ray_tpu.models.resnet import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.float32, axis_name=None)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


def test_vit_forward():
    from ray_tpu.models.vit import ViT_Tiny

    model = ViT_Tiny(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)
    assert out.shape == (2, 10)


@pytest.mark.parametrize("kv_heads", [4, 2])  # 2: the ring's chunks pair head with head, so K and V are repeated for it
def test_transformer_ring_attention_path(kv_heads):
    """attn_impl='ring' over an sp mesh matches the dense path."""
    cfg = tiny_cfg(n_kv_heads=kv_heads)
    mesh = create_mesh(MeshConfig(sp=4, dp=2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    dense, _ = forward(params, tokens, cfg)
    ring, _ = forward(params, tokens, cfg, mesh=mesh, attn_impl="ring")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-4)


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_a_mesh_that_splits_the_heads_further_than_the_kv_heads_go_round(kv_heads):
    """Under a mesh the attention core runs in a ``shard_map`` over the batch and the heads. A device's query heads
    must find their KV heads on it: 8 heads over ``tp`` = 4 with 4 KV heads split as they are, with 2 or 1 K and V
    are repeated up to 4, the least width the mesh divides, and no further. The logits and every gradient are the
    unsharded model's."""
    from functools import partial

    cfg = tiny_cfg(n_heads=8, n_kv_heads=kv_heads)
    mesh = create_mesh(MeshConfig(tp=4, dp=2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size)
    want, _ = forward(params, tokens[:, :-1], cfg)
    got, _ = forward(params, tokens[:, :-1], cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    want_grads = jax.grad(partial(loss_fn, cfg=cfg))(params, {"tokens": tokens})
    got_grads = jax.grad(partial(loss_fn, cfg=cfg, mesh=mesh))(params, {"tokens": tokens})
    gaps = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)), got_grads, want_grads)
    assert max(jax.tree.leaves(gaps)) <= 1e-4, gaps


_YARN = (("factor", 4.0), ("original_max_position_embeddings", 64.0), ("beta_fast", 32.0), ("beta_slow", 1.0), ("mscale", 1.0), ("mscale_all_dim", 0.0))


@pytest.mark.parametrize("scaling", [(), _YARN], ids=["plain", "yarn"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_training_blocks_rotary_is_the_serving_one(dtype, scaling):
    """``_rope_rotate`` over the training block's ``[B, H, T, Dh]`` (a head's halves swapped by a signed permutation,
    tables at the whole width, a cotangent rule of its own) against ``_rope_apply`` over ``[B, T, H, Dh]``, which
    serving runs and autodiff differentiates: the same values and the same cotangent, bit for bit (each output is
    the same two float32 products and their sum, rounded once), with plain and with YaRN's tables; and the tables,
    functions of the positions alone, get no gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import _rope_apply, _rope_rotate, _rope_tables, _whole_width

    B, H, T, Dh = 2, 3, 256, 128
    x, dy = (jax.random.normal(jax.random.PRNGKey(seed), (B, H, T, Dh), jnp.float32).astype(dtype) for seed in (0, 1))
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)) + jnp.array([[0], [1000]])
    cos, sin = _rope_tables(positions, Dh, 10000.0, scaling)
    swap = lambda a: a.transpose(0, 2, 1, 3)
    want, vjp_want = jax.vjp(lambda a: swap(_rope_apply(swap(a), cos, sin)), x)
    got, vjp_got = jax.vjp(_rope_rotate, x, *_whole_width(cos, sin))
    assert got.dtype == want.dtype == x.dtype and float(jnp.abs(want.astype(jnp.float32) - x.astype(jnp.float32)).max()) > 0.5
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))
    dx, dcos, dsin = vjp_got(dy)
    np.testing.assert_array_equal(np.asarray(dx.astype(jnp.float32)), np.asarray(vjp_want(dy)[0].astype(jnp.float32)))
    assert not dcos.any() and not dsin.any()


def _flash_tokens_major(q, k, v, **kw):
    """``flash_attention`` over the reference's ``[B, T, H, D]``: the call itself takes and gives heads before tokens."""
    from ray_tpu.ops.attention import flash_attention

    swap = lambda x: x.transpose(0, 2, 1, 3)
    return swap(flash_attention(swap(q), swap(k), swap(v), **kw))


def test_pallas_flash_attention_matches_xla_fwd_bwd():
    """The pallas kernel (interpret mode on CPU) must match the XLA
    reference in BOTH forward and gradients — the training loss
    differentiates through flash_attention on TPU, so a missing/wrong VJP
    would crash or corrupt every TPU train step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import _xla_attention

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 256, 2, 32
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32) for _ in range(3)
    )
    for causal in (False, True):
        ref = _xla_attention(q, k, v, causal, 0.125)
        out = _flash_tokens_major(
            q, k, v, causal=causal, sm_scale=0.125,
            force_pallas=True, interpret=True, block_q=128, block_k=128,
        )
        assert float(jnp.abs(out - ref).max()) < 1e-5

        def loss_p(q, k, v, _c=causal):
            return (_flash_tokens_major(q, k, v, causal=_c, sm_scale=0.125,
                                    force_pallas=True, interpret=True) ** 2).sum()

        def loss_x(q, k, v, _c=causal):
            return (_xla_attention(q, k, v, _c, 0.125) ** 2).sum()

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert rel < 1e-4, f"causal={causal} grad mismatch {rel}"


def test_flash_attention_odd_lengths_fall_back():
    """Non-tileable sequence lengths must route to the XLA path (a clamped
    tail block would double-count rows)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import _xla_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 100, 2, 16)), jnp.float32)
    out = _flash_tokens_major(q, q, q, causal=True, force_pallas=True, interpret=True)
    ref = _xla_attention(q, q, q, True, 0.25)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_flash_attention_cross_length_causal_alignment():
    """Tq != Tk causal: both paths must use the same (bottom-right) mask
    alignment — query row i sees keys 0..i+(Tk-Tq), the kv-cache decode
    convention."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import _xla_attention

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    ref = _xla_attention(q, k, v, True, 0.125)
    out = _flash_tokens_major(q, k, v, causal=True, sm_scale=0.125,
                          force_pallas=True, interpret=True, block_q=64, block_k=64)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_pallas_backward_kernels_vs_oracle():
    """The Pallas dkv/dq backward kernels (transposed-score orientation,
    causal/window loop pruning) must match the XLA attention's autodiff
    exactly — including the Tq != Tk bottom-right alignment and the
    sliding-window mask, at block sizes that exercise multi-block loops."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention
    from ray_tpu.ops.attention import _xla_attention

    # the call's block_q = block_k = 64 are all three kernels' (``_fit_block`` raises them to a lane tile, 128)
    assert attention._edges("dkv", jnp.zeros((1, 1, 512, 32)), jnp.zeros((1, 1, 512, 32)), 0, 64, 64) == (128, 128)

    rng = np.random.default_rng(7)
    cases = [
        # (Tq, Tk, causal, window) — 512-length at block 128 gives 4 blocks
        # per axis, so the causal/window loop pruning runs multi-iteration
        # spans (qb_start/qb_end interior values), not just 0..1.
        (512, 512, True, 0),
        (256, 256, False, 0),
        (256, 512, True, 0),    # decode-style cross-length alignment
        (512, 512, True, 192),  # sliding window, multi-block pruning
    ]
    for Tq, Tk, causal, window in cases:
        q = jnp.asarray(rng.standard_normal((2, Tq, 2, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, Tk, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, Tk, 2, 32)), jnp.float32)

        def loss_p(q, k, v, _c=causal, _w=window):
            return (_flash_tokens_major(q, k, v, causal=_c, sm_scale=0.2, window=_w,
                                    force_pallas=True, interpret=True,
                                    block_q=64, block_k=64) ** 2).sum()

        def loss_x(q, k, v, _c=causal, _w=window):
            return (_xla_attention(q, k, v, _c, 0.2, window=_w) ** 2).sum()

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gp, gx):
            rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert rel < 1e-4, f"T={Tq}/{Tk} causal={causal} w={window} d{name}: {rel}"


# What a layer under ``cfg.remat`` keeps (PR 51): the attention core's inputs and the flash kernel's results. The
# toys: the dense GQA block, the ``mellum`` block (norms of queries and keys, a pattern of window and full layers,
# dropless routed experts) two periods deep, and the Switch layer.
_REMAT_TOYS = {
    "dense": dict(),
    "qk_norm, a pattern, routed experts": dict(
        n_layers=4, layer_kinds=("window", "full") * 2, sliding_window=32, qk_norm=True, head_dim=16, rope_scaling=(("factor", 4.0), ("original_max_position_embeddings", 16.0), ("beta_fast", 32.0), ("beta_slow", 1.0), ("mscale", 1.0), ("mscale_all_dim", 0.0)),
        num_experts=8, experts_per_token=2, d_expert=16, router_score="softmax", router_bias=False,
    ),
    "switch": dict(num_experts=4),
}


def _scanned_layers(cfg):
    """Layers in the body of ``_run_layers``' scan: a period of the pattern, or one."""
    from ray_tpu.models.transformer import _period

    return _period(cfg.layer_kinds) if cfg.layer_kinds else 1


@pytest.mark.parametrize("toy", _REMAT_TOYS)
def test_a_layer_under_remat_gives_the_loss_and_the_gradients_of_a_layer_that_keeps_everything(toy, monkeypatch):
    """The flash kernels run (interpreted) in both programs; under remat the
    backward pass reads q, k, v, the kernel's output and its log-sum-exp where
    the forward pass left them and recomputes the rest of the layer: the same
    float32 operations on the same values, so the loss and every leaf's gradient
    are the ones ``remat=False`` gives."""
    from functools import partial

    from ray_tpu.models import transformer
    from ray_tpu.ops import attention

    monkeypatch.setattr(transformer, "flash_attention", partial(attention.flash_attention, interpret=True))
    tokens = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 128)}
    got = {}
    for remat in (True, False):
        cfg = tiny_cfg(remat=remat, **_REMAT_TOYS[toy])
        params = init_params(jax.random.PRNGKey(0), cfg)
        loss_and_grads = jax.value_and_grad(partial(loss_fn, cfg=cfg))
        jaxpr = str(jax.make_jaxpr(loss_and_grads)(params, tokens))
        # a layer of the scan's body: the forward kernel once and the two backward kernels, whatever it keeps
        assert jaxpr.count("pallas_call[") == 3 * _scanned_layers(cfg) and ("remat" in jaxpr) == remat
        got[remat] = jax.jit(loss_and_grads)(params, tokens)
    (loss, grads), (want_loss, want_grads) = got[True], got[False]
    assert abs(float(loss) - float(want_loss)) <= 1e-6
    gaps = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)), grads, want_grads)
    assert max(jax.tree.leaves(gaps)) <= 1e-6, gaps


_LOWERED_TOYS = {
    "dense": dict(),
    "dense, a dp mesh of four": dict(),
    "a pattern of two kinds": dict(layer_kinds=("window", "full") * 2, n_layers=4, sliding_window=128, qk_norm=True),
}


@pytest.mark.parametrize("toy", _LOWERED_TOYS)
def test_the_backward_pass_of_a_layer_under_remat_runs_no_flash_forward_kernel(toy, monkeypatch):
    """The mechanism's counter: the train step lowered for the TPU from here
    holds ONE ``_flash_kernel`` call a layer of the scan's body (the forward
    pass's; the backward scan has the two backward kernels only), under a
    ``shard_map`` too, where a bare ``jax.checkpoint`` (the parent's, here the
    policy taken away) has two."""
    from jax.sharding import NamedSharding

    from ray_tpu.models import transformer
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import single_axis_mesh

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = tiny_cfg(vocab_size=512, d_model=256, n_heads=2, n_kv_heads=1, d_ff=256, max_seq_len=256, dtype=jnp.bfloat16, remat=True, **_LOWERED_TOYS[toy])
    mesh = single_axis_mesh("dp", devices=jax.devices()[:4]) if "mesh" in toy else None
    opt = optax.adamw(1e-4)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((4, 257), jnp.int32, sharding=NamedSharding(mesh, logical_to_spec(("batch", None))) if mesh else None)

    def kernels():
        step = jax.jit(make_train_step(cfg, opt, mesh=mesh))
        text = step.trace(params, jax.eval_shape(opt.init, params), {"tokens": tokens}).lower(lowering_platforms=("tpu",)).as_text()
        return tuple(text.count(f'kernel_name = "{name}"') for name in ("_flash_kernel", "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel"))

    body = _scanned_layers(cfg)
    assert kernels() == (body, body, body)
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT", None)
    assert kernels() == (2 * body, body, body)
