"""Parallelism-strategy correctness tests on the virtual 8-device CPU mesh
(SURVEY.md §5.7: these strategies are absent in the reference and built
natively here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import _xla_attention, flash_attention
from ray_tpu.parallel.mesh import MeshConfig, create_mesh, logical_to_spec
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.ulysses import ulysses_attention


@pytest.fixture(scope="module")
def qkv():
    key = jax.random.PRNGKey(0)
    B, T, H, D = 2, 64, 4, 16
    return [jax.random.normal(k, (B, T, H, D), jnp.float32) for k in jax.random.split(key, 3)]


def test_mesh_resolve():
    cfg = MeshConfig(dp=2, tp=-1)
    sizes = cfg.resolve(8)
    assert sizes["dp"] == 2 and sizes["tp"] == 4


def test_create_mesh_axes():
    mesh = create_mesh(MeshConfig(dp=2, sp=2, tp=2))
    assert mesh.shape["dp"] == 2
    assert mesh.shape["sp"] == 2
    assert mesh.shape["tp"] == 2
    assert mesh.shape["pp"] == 1


def test_logical_to_spec():
    spec = logical_to_spec(("batch", "seq", "embed"))
    assert spec[0] == ("dp", "fsdp")
    assert spec[1] == "sp"


def test_flash_attention_matches_reference(qkv):
    q, k, v = qkv
    ref = _xla_attention(q, k, v, True, q.shape[-1] ** -0.5)
    swap = lambda x: x.transpose(0, 2, 1, 3)  # the call takes and gives heads before tokens
    out = swap(flash_attention(swap(q), swap(k), swap(v), causal=True, interpret=True, block_q=32, block_k=32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(qkv, causal):
    q, k, v = qkv
    mesh = create_mesh(MeshConfig(sp=4, dp=2))
    ref = _xla_attention(q, k, v, causal, q.shape[-1] ** -0.5)
    out = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_exact(qkv, causal):
    q, k, v = qkv
    mesh = create_mesh(MeshConfig(sp=4, dp=2))
    ref = _xla_attention(q, k, v, causal, q.shape[-1] ** -0.5)
    out = ulysses_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pipeline_matches_sequential():
    from ray_tpu.parallel.pipeline import pipeline_apply

    mesh = create_mesh(MeshConfig(pp=4, dp=2))
    n_stages, d = 4, 8
    key = jax.random.PRNGKey(1)
    ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(2), (8, d))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    # Sequential reference.
    ref = x
    for i in range(n_stages):
        ref = stage_fn(ws[i], ref)
    out = pipeline_apply(stage_fn, ws, x, mesh, num_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_microbatches_exceed_stages():
    """The GPipe schedule's bubble arithmetic (T = M + S - 1 steps) at
    M > S — more microbatches than stages, the regime that actually shrinks
    the bubble — was previously only exercised at M == S."""
    from ray_tpu.parallel.pipeline import pipeline_apply

    mesh = create_mesh(MeshConfig(pp=4, dp=2))
    n_stages, d = 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(3), (n_stages, d, d)) * 0.3

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    for M in (8, 16):
        x = jax.random.normal(jax.random.PRNGKey(M), (M * 2, d))
        ref = x
        for i in range(n_stages):
            ref = stage_fn(ws[i], ref)
        out = pipeline_apply(stage_fn, ws, x, mesh, num_microbatches=M)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_non_divisible_batch_asserts():
    """A batch that doesn't divide into num_microbatches fails loudly at
    the assertion, not with a silent reshape error downstream."""
    from ray_tpu.parallel.pipeline import pipeline_apply

    mesh = create_mesh(MeshConfig(pp=4, dp=2))
    ws = jax.random.normal(jax.random.PRNGKey(4), (4, 8, 8))
    x = jax.random.normal(jax.random.PRNGKey(5), (10, 8))  # 10 % 4 != 0

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    with pytest.raises(AssertionError, match="not divisible"):
        pipeline_apply(stage_fn, ws, x, mesh, num_microbatches=4)


def test_moe_layer_shapes_and_balance():
    from ray_tpu.parallel.moe import init_moe_params, moe_layer

    key = jax.random.PRNGKey(0)
    params = init_moe_params(key, d_model=16, d_ff=32, num_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    out, aux = moe_layer(params, x, capacity_factor=2.0)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    # With generous capacity, most tokens should be routed (non-zero output).
    assert float(jnp.abs(out).mean()) > 0


def test_moe_expert_parallel_sharding():
    """The MoE layer jits under a sharded-experts constraint (ep axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.moe import init_moe_params, moe_layer

    mesh = create_mesh(MeshConfig(ep=4, dp=2))
    params = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    params = jax.tree.map(
        lambda p: jax.device_put(p, NamedSharding(mesh, P("ep"))) if p.shape[0] == 4 and p.ndim == 3 else jax.device_put(p, NamedSharding(mesh, P())),
        params,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    out, aux = jax.jit(lambda p, x: moe_layer(p, x, capacity_factor=2.0))(params, x)
    assert out.shape == x.shape


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match_reference(causal):
    """Ring-level custom VJP: grads of the two-ring-pass implementation match
    plain attention's autodiff (both impls; pallas runs in interpret mode)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.ops.attention import _xla_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("sp",))
    B, T, H, D = 1, 512, 2, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (B, T, H, D))
    k = jax.random.normal(k2, (B, T, H, D))
    v = jax.random.normal(k3, (B, T, H, D))
    sc = 1.0 / np.sqrt(D)

    ref = jax.grad(lambda q, k, v: _xla_attention(q, k, v, causal, sc).sum(), argnums=(0, 1, 2))(q, k, v)
    for impl, interp in (("xla", False), ("pallas", True)):
        got = jax.grad(
            lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=causal, impl=impl, interpret=interp
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3)
