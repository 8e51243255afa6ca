"""Mosaic lowering gate for the Pallas kernels.

Round-1 lesson (VERDICT.md Weak #1): every kernel test ran interpret=True on
CPU, so the suite stayed green while the TPU lowering was broken (the LSE
BlockSpec violated the (8, 128) tile constraint and the train step crashed
on hardware). This test compiles the kernels for the real TPU backend — no
interpret — so a Mosaic lowering regression fails CI whenever a TPU is
reachable.

The suite-wide conftest pins this process to CPU before jax import, so the
probe runs in a subprocess with the CPU pins stripped; it skips (not passes)
when no TPU backend comes up — which is every run off the chip. It runs for
real only through the chip tool:
    chiprun -- python -m pytest tests/test_tpu_lowering.py -q
"""

import os
import subprocess
import sys

import pytest

_PROBE = r"""
import sys
import jax
if jax.default_backend() != "tpu":
    print("NO_TPU_BACKEND:" + jax.default_backend())
    sys.exit(42)
import jax.numpy as jnp
from ray_tpu.ops.attention import flash_attention

# (B, T, H, D, causal, window): the original gate, then chip_smoke.py's shapes
# (Mistral-7B heads at T=4096, full causal and the 4096 sliding window).
CASES = [
    (2, 512, 4, 128, False, 0),
    (2, 512, 4, 128, True, 0),
    (1, 4096, 32, 128, True, 0),
    (1, 4096, 32, 128, True, 4096),
]
for B, T, H, D, causal, window in CASES:
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, force_pallas=True)
    jax.jit(attn).lower(q, q, q).compile()
    jax.jit(jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )).lower(q, q, q).compile()
print("LOWERED_OK")
"""


def _run_tpu_probe(probe_src: str):
    """Run a probe in a subprocess with the suite's CPU pins stripped;
    returns the CompletedProcess, or None if no TPU backend came up."""
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "RAY_TPU_NUM_TPUS", "XLA_FLAGS"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, "-c", probe_src],
        env=env,
        capture_output=True,
        text=True,
        timeout=580,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if proc.returncode == 42:
        pytest.skip(
            f"no TPU backend in subprocess ({proc.stdout.strip()}): this gate only "
            "runs through the chip tool (chiprun -- python -m pytest tests/test_tpu_lowering.py)"
        )
    return proc


def _assert_lowered(proc):
    assert proc.returncode == 0, f"TPU lowering failed:\n{proc.stdout}\n{proc.stderr[-4000:]}"
    assert "LOWERED_OK" in proc.stdout


def test_flash_attention_lowers_on_tpu():
    _assert_lowered(_run_tpu_probe(_PROBE))


_RING_PROBE = r"""
import sys
import jax
if jax.default_backend() != "tpu":
    print("NO_TPU_BACKEND:" + jax.default_backend())
    sys.exit(42)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh
from ray_tpu.parallel.ring_attention import ring_attention

mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
x = jax.ShapeDtypeStruct((2, 1024, 4, 128), jnp.bfloat16)
fwd = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True, impl="pallas"))
fwd.lower(x, x, x).compile()
bwd = jax.jit(jax.grad(
    lambda q, k, v: ring_attention(q, k, v, mesh, causal=True, impl="pallas").astype(jnp.float32).sum(),
    argnums=(0, 1, 2)))
bwd.lower(x, x, x).compile()
print("LOWERED_OK")
"""


def test_ring_attention_pallas_lowers_on_tpu():
    _assert_lowered(_run_tpu_probe(_RING_PROBE))


def test_sharded_train_step_lowers_for_tpu_from_cpu():
    """Runs everywhere: lowering FOR the TPU needs no TPU. Under a mesh the
    train step must reach the three Mosaic kernels through a shard_map — the
    compiler refuses to partition them itself (found on the four-chip host,
    PR 21: "Mosaic kernels cannot be automatically partitioned")."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.models.transformer import (
        TransformerConfig, init_params, make_train_step, param_logical_axes,
    )
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import (
        logical_to_spec, shard_by_logical_axes, single_axis_mesh,
    )

    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=256,
        max_seq_len=256, sliding_window=256, dtype=jnp.bfloat16, remat=True,
    )
    mesh = single_axis_mesh("dp", devices=jax.devices()[:4])
    params = shard_by_logical_axes(
        init_params(jax.random.PRNGKey(0), cfg), param_logical_axes(cfg), mesh
    )
    opt = optax.adamw(1e-4)
    batch = {
        "tokens": jax.device_put(
            jnp.zeros((4, 257), jnp.int32),
            NamedSharding(mesh, logical_to_spec(("batch", None))),
        )
    }
    with mock.patch.object(attention, "_on_tpu", lambda: True):
        lowered = (
            jax.jit(make_train_step(cfg, opt, mesh=mesh))
            .trace(params, opt.init(params), batch)
            .lower(lowering_platforms=("tpu",))
        )
    text = lowered.as_text()
    for kernel in ("_flash_kernel", "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel"):
        assert f'kernel_name = "{kernel}"' in text
