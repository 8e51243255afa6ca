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


def test_transformer_ring_attention_path():
    """attn_impl='ring' over an sp mesh matches the dense path."""
    cfg = tiny_cfg(n_kv_heads=4)
    mesh = create_mesh(MeshConfig(sp=4, dp=2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    dense, _ = forward(params, tokens, cfg)
    ring, _ = forward(params, tokens, cfg, mesh=mesh, attn_impl="ring")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-4)


def test_pallas_flash_attention_matches_xla_fwd_bwd():
    """The pallas kernel (interpret mode on CPU) must match the XLA
    reference in BOTH forward and gradients — the training loss
    differentiates through flash_attention on TPU, so a missing/wrong VJP
    would crash or corrupt every TPU train step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import _xla_attention, flash_attention

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 256, 2, 32
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32) for _ in range(3)
    )
    for causal in (False, True):
        ref = _xla_attention(q, k, v, causal, 0.125)
        out = flash_attention(
            q, k, v, causal=causal, sm_scale=0.125,
            force_pallas=True, interpret=True, block_q=128, block_k=128,
        )
        assert float(jnp.abs(out - ref).max()) < 1e-5

        def loss_p(q, k, v, _c=causal):
            return (flash_attention(q, k, v, causal=_c, sm_scale=0.125,
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

    from ray_tpu.ops.attention import _xla_attention, flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 100, 2, 16)), jnp.float32)
    out = flash_attention(q, q, q, causal=True, force_pallas=True, interpret=True)
    ref = _xla_attention(q, q, q, True, 0.25)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_flash_attention_cross_length_causal_alignment():
    """Tq != Tk causal: both paths must use the same (bottom-right) mask
    alignment — query row i sees keys 0..i+(Tk-Tq), the kv-cache decode
    convention."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import _xla_attention, flash_attention

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    ref = _xla_attention(q, k, v, True, 0.125)
    out = flash_attention(q, k, v, causal=True, sm_scale=0.125,
                          force_pallas=True, interpret=True, block_q=64, block_k=64)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_pallas_backward_kernels_vs_oracle(monkeypatch):
    """The Pallas dkv/dq backward kernels (transposed-score orientation,
    causal/window loop pruning) must match the XLA attention's autodiff
    exactly — including the Tq != Tk bottom-right alignment and the
    sliding-window mask, at block sizes that exercise multi-block loops."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention
    from ray_tpu.ops.attention import _xla_attention, flash_attention

    monkeypatch.setattr(attention, "_BWD_BLOCK", 128)

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
            return (flash_attention(q, k, v, causal=_c, sm_scale=0.2, window=_w,
                                    force_pallas=True, interpret=True,
                                    block_q=64, block_k=64) ** 2).sum()

        def loss_x(q, k, v, _c=causal, _w=window):
            return (_xla_attention(q, k, v, _c, 0.2, window=_w) ** 2).sum()

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gp, gx):
            rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert rel < 1e-4, f"T={Tq}/{Tk} causal={causal} w={window} d{name}: {rel}"
