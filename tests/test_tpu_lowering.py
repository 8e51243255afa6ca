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
# (Mistral-7B heads at T=4096, full causal and the 4096 sliding window), then the
# 8192-token cell's two kinds of layer (PR 60: each kernel at the blocks
# ``attention._blocks`` gives that mask).
CASES = [
    (2, 512, 4, 128, False, 0),
    (2, 512, 4, 128, True, 0),
    (1, 4096, 32, 128, True, 0),
    (1, 4096, 32, 128, True, 4096),
    (1, 8192, 8, 128, True, 1024),
    (1, 8192, 8, 128, True, 0),
]
for B, T, H, D, causal, window in CASES:
    q = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
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


def _compiled_dp4_train_step(cfg, devices, rows_a_device, flash_kernels):
    """``make_train_step`` compiled for a ``dp`` mesh of four ``devices``, which
    may be described and not attached (every argument is a shape): parameters
    and optimizer state replicated, the batch split. ``flash_kernels`` takes
    the attention's TPU path, whatever backend this process has."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.models.transformer import init_params, make_train_step
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import logical_to_spec, single_axis_mesh

    mesh = single_axis_mesh("dp", devices=devices)
    opt = optax.adamw(1e-4)

    def placed(tree, axes=()):
        sharding = NamedSharding(mesh, logical_to_spec(axes))
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    params = placed(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    opt_state = placed(jax.eval_shape(opt.init, params))
    tokens = jax.ShapeDtypeStruct((4 * rows_a_device, cfg.max_seq_len + 1), jnp.int32)
    with mock.patch.object(attention, "_on_tpu", lambda: flash_kernels):
        return (
            jax.jit(make_train_step(cfg, opt, mesh=mesh), donate_argnums=(0, 1))
            .lower(params, opt_state, {"tokens": placed(tokens, ("batch", None))})
            .compile()
        )


def _collectives(compiled):
    """Result shapes of a compiled module's collectives, by operation, but for
    those whose every group is one device: a shard_map sums its inputs'
    cotangents over the mesh axes its specs do not name, here all of size 1."""
    import re

    found = {"all-gather": [], "all-reduce": []}
    line = r"= (\(.*?\)|\S+) (all-gather|all-reduce)(?:-start)?\(.*?replica_groups=(\{\{.*?\}\}|\[\d+,(\d+)\])"
    for shapes, op, groups, iota_size in re.findall(line, compiled.as_text()):
        if iota_size == "1" or (not iota_size and not re.search(r"\d,\d", groups)):
            continue
        found[op] += re.findall(r"\w+\[[\d,]*\]", shapes)
    return found


def _assert_the_loss_crosses_no_rows(compiled, cfg):
    """Parameters replicated, batch split: there is nothing to gather, and what
    is reduced is gradients and the loss. Left to the partitioner (until PR 36)
    the fused loss's scans all-gathered the global batch's chunked hidden
    states ``[N // chunk, chunk, D]`` and targets, forward and backward, and
    every device computed the head for every row."""
    found = _collectives(compiled)
    assert found["all-gather"] == [], found
    assert found["all-reduce"], found
    chunks = [s for s in found["all-reduce"] if s.endswith(f",512,{cfg.d_model}]")]
    assert chunks == [], chunks


@pytest.mark.parametrize("rows_a_device", [1, 2])
def test_the_dp4_train_step_gathers_nothing(rows_a_device):
    """Runs everywhere, compiled for four of the suite's CPU devices at toy
    widths, two or four chunks of 512 tokens a device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=256,
        max_seq_len=1024, sliding_window=1024, dtype=jnp.bfloat16, remat=True,
    )
    compiled = _compiled_dp4_train_step(cfg, jax.devices()[:4], rows_a_device, flash_kernels=False)
    _assert_the_loss_crosses_no_rows(compiled, cfg)


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of a v5e host, described and not attached: the TPU's
    compiler compiles for them here (the on-chip-measurement guide, section 2)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_host):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


def test_the_dp4_cell_train_step_gathers_nothing_on_the_v5e_host(v5e_host):
    """``train2.dp4-4k``'s step at the cell's widths, compiled for the four
    chips of a v5e host (PR 36): each chip runs one chip's loss, 8 chunks of 512
    of its own sequence, and the step still fits beside what is resident."""
    import jax.numpy as jnp

    from benchmarks.harness import registry
    from ray_tpu.models.transformer import TransformerConfig

    cell = registry.load_cell(registry.load_manifest(), "train2.dp4-4k")
    dep = cell["config"]["deployment"]
    model = registry.load_architecture(cell, "config").model_config(
        cell["config"], cell["traffic"]["seq_len"], dep["param_dtype"]
    )
    for key in ("dtype", "param_dtype"):
        model[key] = jnp.dtype(model[key]).type
    cfg = TransformerConfig(**model, remat=dep["remat"], fused_loss=dep["fused_loss"])
    compiled = _compiled_dp4_train_step(
        cfg, v5e_host, cell["traffic"]["batch_per_chip"], flash_kernels=True
    )
    _assert_the_loss_crosses_no_rows(compiled, cfg)
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernels, not the attention's fallback
    stats = compiled.memory_analysis()
    in_use = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 14.274 GB since PR 53 (no transposed copies of q, k, v and the output, no k and v at H heads; a projection is
    # ``h @ w`` with only its RESULT heads-major, so that its weight's gradient leaves the matmul in the leaf's
    # [D, H * Dh]: over ``w`` seen as [D, H, Dh] the compiler relaid the gradients of wq, wk and wv in float32 under
    # this mesh before it stacked them, 67 + 2 x 17 MB of temporaries more, 14.442 GB); 14.347 since PR 51 (each of
    # the two layers keeps its q, k, v, attention output and log-sum-exp, 84 MB a layer; 14.242 until then, as before
    # PR 36): 8.38 resident + 5.89 of temporaries
    assert in_use < 14.4e9, in_use


@pytest.mark.parametrize("reads", ["kernel", "view"])
def test_the_latent_decode_step_copies_neither_the_pool_nor_the_expert_stacks(one_v5e_chip, reads, monkeypatch):
    """GLM-4.7-Flash's decode program at the benchmark's widths, compiled for
    the v5e (PR 32). Two things the compiler did to its first versions, each
    worth milliseconds a step and invisible on the CPU: with a 576-wide row the
    pool got a layout of its own and was copied in and out of the layer scan
    (the row is padded to 640 for that); and the scan's slice of a layer's
    [64, 2048, 1536] expert matrices was materialised before the grouped
    matmul (the stacks stay whole for that, a layer is a group offset).

    ``kernel`` (PR 44): the program a TPU backend gets, at the whole table's
    width, the pool read in place by ``ops/latent_attention.py`` (here the
    backend is the CPU's, so the test says what the predicate would see there);
    ``view``: the program of every other backend, at the 1024-token rung."""
    import importlib
    import re

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import registry
    from ray_tpu.models.generate import MOE_CHOICE, MOE_COUNTS, init_moe_choice, init_moe_counts, init_paged_cache
    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm.engine import _ROW_TABLE, _compiled_fns

    cell = registry.load_cell(registry.load_manifest(), "glm8.rollout-long")
    engine = cell["config"]["deployment"]["engine"]
    model = registry.load_architecture(cell, "config").model_config(
        cell["config"], engine["max_model_len"], "bfloat16"
    )
    model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    cfg = TransformerConfig(**model)
    width = 64
    if reads == "kernel":
        for module in ("ray_tpu.ops.attention", "ray_tpu.ops.latent_attention"):  # the predicate's, and the kernel's "compiled, not interpreted"
            monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
        monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
        width = -(-engine["max_model_len"] // engine["block_size"])

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e_chip), tree)

    def pool():
        blocks = engine["num_blocks"], engine["block_size"]
        return {**init_paged_cache(cfg, *blocks), MOE_COUNTS: init_moe_counts(cfg), MOE_CHOICE: init_moe_choice(cfg, *blocks)}

    params = described(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    rows = jax.ShapeDtypeStruct((engine["num_slots"], _ROW_TABLE + width), jnp.int32, sharding=one_v5e_chip)
    ids = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=one_v5e_chip)
    compiled = _compiled_fns(cfg)[0].lower(params, rows, described(jax.eval_shape(pool)), ids).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text  # the grouped matmul is the TPU's own, not a dense fallback
    # The kernel's one result is the array the weighted sum over the view returned
    # (the benchmark's ``trace_ops.latent_attention`` finds either by that shape), and no view is gathered.
    assert bool(re.search(r"= bf16\[32,20,1,640\]\S* custom-call\(.*tpu_custom_call", text)) == (reads == "kernel")
    assert bool(re.search(r"= bf16\[\d+,16,640\]\S* fusion\(", text)) == (reads == "view")
    pool_shape = re.escape("bf16[8,8193,16,640]")
    assert not re.search(rf"= {pool_shape}\S* copy\(", text)
    assert not re.search(r"= bf16\[64,(2048,1536|1536,2048)\]\S* fusion\(", text)
    assert not re.search(r"= s32\[7,8193,16\]\S* copy\(", text)  # nor the words of the experts taken
    # 32 rows at the 1024-token rung: the view (42 MB) and little else, not a pool (1.34 GB) or an expert matrix (0.4 GB);
    # through the kernel not even the view
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < (100e6 if reads == "kernel" else 150e6), stats.temp_size_in_bytes
    assert stats.alias_size_in_bytes >= 8 * 8193 * 16 * 640 * 2 + 7 * 8193 * 16 * 4  # the pool is updated in place


def test_the_latent_prefill_chunk_reads_the_pool_in_place_on_a_tpu(one_v5e_chip, monkeypatch):
    """GLM-4.7-Flash's prefill chunk as a TPU backend gets it (PR 48), at the
    benchmark's widths, compiled for the v5e: 20 heads x 32 queries a tile of
    ``ops/latent_attention.py``'s chunk kernel, its one result ``[1, 20, 512,
    640]``; no view of the 256-block table is gathered, the pool is updated in
    place and never copied, and the float32 scores over 4096 keys (0.17 GB a
    layer) are not among the temporaries."""
    import importlib
    import re

    import jax

    # the predicate's, and the kernels' "compiled, not interpreted" (since PR 49 the chunk's experts run one too)
    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.latent_attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
    _, prefill, args = _cell_programs("glm8.rollout-long")
    described = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e_chip), args(None))
    compiled = prefill.lower(*described).compile()
    text = compiled.as_text()
    assert re.search(r"= bf16\[1,20,512,640\]\S* custom-call\(.*tpu_custom_call", text)
    assert not re.search(r"= bf16\[\d+,16,640\]\S* fusion\(", text)
    assert not re.search(rf"= {re.escape('bf16[8,8193,16,640]')}\S* copy\(", text)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 8 * 8193 * 16 * 640 * 2 + 7 * 8193 * 16 * 4
    assert stats.temp_size_in_bytes < 150e6, stats.temp_size_in_bytes
    # and its experts' 2048 assignments the grouped kernel, over the whole stacks (the next test holds Xing's to more)
    assert "ragged-dot" not in text and len(re.findall(r"%gmm\S* = bf16\[2048,(1536|2048)\]\S* custom-call\(", text)) == 3


def test_the_prefill_chunks_experts_run_the_grouped_kernel_over_the_whole_stacks_on_a_tpu(one_v5e_chip, monkeypatch):
    """Xing4.0's prefill chunk as a TPU backend gets it (PR 49), at the
    benchmark's widths, compiled for the v5e: 2048 assignments over 64 experts
    are 32 rows a group, so the three matmuls of an expert layer run
    ``ops/grouped_matmul.py``'s kernel (``moe.experts_run``), three custom calls
    in the layer scan whose results are ``[2048, 1024]`` twice and ``[2048,
    3584]``, each over a WHOLE ``[5 * 64, in, out]`` stack as it lies: the
    layer is in the group sizes. No ``ragged_dot`` is left in the program, no
    stack and no layer's slice of one is copied or materialised, and the
    temporaries hold nothing of that size."""
    import importlib
    import re

    import jax

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.latent_attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
    _, prefill, args = _cell_programs("xing6.longdoc-12k")
    described = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e_chip), args(None))
    compiled = prefill.lower(*described).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    for rows_out, stack in (("2048,1024", "320,3584,1024"), ("2048,3584", "320,1024,3584")):
        calls = re.findall(rf"%gmm\S* = bf16\[{rows_out}\]\S* custom-call\(.*tpu_custom_call.*operand_layout_constraints=\{{(.*?\}})\}}, ", text)
        assert len(calls) == (2 if rows_out == "2048,1024" else 1), (rows_out, len(calls))
        assert all(f"bf16[{stack}]" in operands for operands in calls), calls  # the whole stack is the operand, as it lies
    for leaf in ("bf16[5,64,3584,1024]", "bf16[5,64,1024,3584]", "bf16[320,3584,1024]", "bf16[320,1024,3584]"):
        assert not re.search(rf"= {re.escape(leaf)}\S* (copy|fusion)\(", text), leaf
    assert not re.search(r"= bf16\[64,(3584,1024|1024,3584)\]\S* (copy|fusion)\(", text)  # nor a layer's experts
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 0.3e9, stats.temp_size_in_bytes  # a layer's one matrix of 64 experts is 0.47 GB


def test_a_scanned_slice_of_an_expert_stack_is_copied_and_a_whole_stack_is_not(one_v5e_chip):
    """Why ``generate._cached_layers`` keeps the routed experts' ``[L, E, in, out]``
    stacks out of the leaves its layer scan slices, and ``moe.routed_experts``
    takes a layer index (PR 32): the TPU's compiler materialises a scan's slice
    of a stack before ``ragged_dot`` reads it, a layer's experts copied every
    layer of every step, and reads a whole stack in place. The day the first
    half fails, the ``held`` leaves and ``layer=`` can go."""
    import re

    import jax
    import jax.numpy as jnp
    from jax import lax

    L, E, D, N = 3, 64, 1024, 128

    def sliced(x, stack, sizes):
        return lax.scan(lambda x, w: (x + lax.ragged_dot(x, w, sizes), None), x, stack)[0]

    def whole(x, stack, sizes):
        flat = stack.reshape(L * E, D, D)

        def layer(x, l):
            groups = lax.dynamic_update_slice(jnp.zeros((L * E,), jnp.int32), sizes, (l * E,))
            return x + lax.ragged_dot(x, flat, groups), None

        return lax.scan(layer, x, jnp.arange(L))[0]

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    args = described((N, D), jnp.bfloat16), described((L, E, D, D), jnp.bfloat16), described((E,), jnp.int32)
    one_layer = E * D * D * 2
    copied = {}
    for fn in (sliced, whole):
        compiled = jax.jit(fn).lower(*args).compile()
        copied[fn] = (
            bool(re.search(rf"= bf16\[{E},{D},{D}\]\S* fusion\(", compiled.as_text())),
            compiled.memory_analysis().temp_size_in_bytes >= one_layer,
        )
    assert copied[sliced] == (True, True) and copied[whole] == (False, False)


# The attention cores of the benchmark's training cells: (batch a chip, H, KV, T, window) and the (block_q, block_k)
# ``attention._blocks`` gives the forward, the dK/dV and the dQ kernel there (PR 60: measured on the v5e, PERF.md
# section 6; a change of the function's answer for a cell is a change of this table, on purpose).
_FLASH_CELLS = {
    "mellum4.moe-8k, a window layer": ((2, 32, 4, 8192, 1024), {"fwd": (512, 512), "dkv": (512, 512), "dq": (512, 512)}),
    "mellum4.moe-8k, the full layer": ((2, 32, 4, 8192, 0), {"fwd": (256, 1024), "dkv": (1024, 1024), "dq": (512, 1024)}),
    "train2, a window that reaches every key": ((1, 32, 8, 4096, 4096), {"fwd": (512, 512), "dkv": (512, 512), "dq": (512, 1024)}),
}


@pytest.mark.parametrize("which", sorted(_FLASH_CELLS))
def test_the_flash_kernels_compile_for_the_v5e_at_the_blocks_the_mask_gives(which, one_v5e_chip, monkeypatch):
    """``flash_attention`` and its gradient at a cell's shapes, each kernel at the block edges ``_blocks`` derives
    from the mask, compiled by the TPU's compiler for the described chip: the resident operands and the tiles fit
    what ``_vmem`` asks for (a shape that does not is refused here, as the forward at 8192 tokens was before PR 50
    raised its limit), and the lowered text names each of the three kernels once."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    (B, H, KV, T, window), want = _FLASH_CELLS[which]
    seen = 0 if window >= T else window
    assert {kernel: attention._blocks(kernel, T, T, 128, seen) for kernel in want} == want
    q = jax.ShapeDtypeStruct((B, H, T, 128), jnp.bfloat16, sharding=one_v5e_chip)
    kv = jax.ShapeDtypeStruct((B, KV, T, 128), jnp.bfloat16, sharding=one_v5e_chip)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # the backward rule asks it too
    attn = lambda q, k, v: attention.flash_attention(q, k, v, causal=True, window=window)
    lowered = jax.jit(jax.value_and_grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))).lower(q, kv, kv)
    calls = _flash_calls(lowered.as_text())
    assert {name: len(of) for name, of in calls.items()} == dict.fromkeys(("_flash_kernel", "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel"), 1)
    lowered.compile()


def _lowered_train_step_for_the_v5e(cfg, batch, one_chip, monkeypatch):
    """``make_train_step`` (AdamW, donated) with the flash kernels, lowered for the described chip."""
    import importlib

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import init_params, make_train_step

    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = on(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    opt = optax.adamw(1e-4)
    tokens = jax.ShapeDtypeStruct((batch, cfg.max_seq_len + 1), jnp.int32, sharding=one_chip)
    return jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1)).lower(params, on(jax.eval_shape(opt.init, params)), {"tokens": tokens})


def _train_step_for_the_v5e(cfg, batch, one_chip, monkeypatch):
    """The same, (lowered, compiled)."""
    lowered = _lowered_train_step_for_the_v5e(cfg, batch, one_chip, monkeypatch)
    return lowered, lowered.compile()


def _flash_calls(lowered_text: str) -> dict:
    """kernel name -> the calls' (operand types, result types) as the lowered text writes them."""
    import re

    calls = {}
    for line in lowered_text.splitlines():
        if "stablehlo.custom_call @tpu_custom_call" in line:
            name = re.search(r'kernel_name = "(\w+)"', line).group(1)
            operands, results = re.search(r"\} : \((.*?)\) -> (.*)$", line).groups()
            calls.setdefault(name, []).append((re.findall(r"tensor<([^>]+)>", operands), re.findall(r"tensor<([^>]+)>", results)))
    return calls


def _moved_whole(compiled_text: str, shapes: set) -> list:
    """The compiled program's ``copy`` and ``transpose`` instructions (outside fusions: each runs, reads its operand
    and writes it again) over a tensor whose axes, ones aside and in any order, are one of ``shapes``."""
    import re

    wanted = {tuple(sorted(d for d in shape if d != 1)) for shape in shapes}
    found, inside_fusion = [], False
    for line in compiled_text.splitlines():
        if not line.startswith(" "):
            inside_fusion = line.startswith("%fused_computation") or line.startswith("fused_computation")
            continue
        m = None if inside_fusion else re.match(r"\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* (copy|transpose)\(", line)
        if m and tuple(sorted(int(d) for d in m.group(1).split(",") if d and d != "1")) in wanted:
            found.append(line.strip()[:240])
    return found


def _mellum_shaped(H, KV, T, Dh, **more):
    """A toy of Mellum's shape: a pattern of window and full layers, norms of q and k, YaRN, softmax top-2-of-8 experts."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=1024, d_model=384, n_layers=4, n_heads=H, n_kv_heads=KV, head_dim=Dh, d_ff=256, max_seq_len=T, sliding_window=256,
        layer_kinds=("window", "full") * 2, qk_norm=True, dtype=jnp.bfloat16, remat=True, fused_loss=True,
        rope_scaling=(("factor", 4.0), ("original_max_position_embeddings", 128.0), ("beta_fast", 32.0), ("beta_slow", 1.0), ("mscale", 1.0), ("mscale_all_dim", 0.0)),
        num_experts=8, experts_per_token=2, d_expert=128, router_score="softmax", router_bias=False, **more,
    )


# What ``_attention_block`` hands the three flash kernels (PR 53), at Mistral-7B's widths (``train2``'s configuration
# at its 4096 tokens; TWO rows, so that a q-sized tensor is no matrix's size: wo is [32, 128, 4096] too) and at a
# toy of Mellum's shape (a pattern of window and full layers, norms of q and k, YaRN, 8 query heads a KV head).
_HEADS_MAJOR_STEPS = {
    "train2": dict(batch=2, H=32, KV=8, T=4096, layers=1),
    "mellum-shaped": dict(batch=2, H=8, KV=1, T=512, layers=2),
}


@pytest.mark.parametrize("which", sorted(_HEADS_MAJOR_STEPS))
def test_the_train_step_hands_the_flash_kernels_q_k_v_where_the_layer_holds_them(which, one_v5e_chip, monkeypatch):
    """The counter that says PR 53's change engaged. In the step lowered for a TPU every call of the three flash
    kernels takes q, the output, its cotangent and dq as ``[B, H, T, Dh]`` and k and v as ``[B, KV, T, Dh]``, the
    layout the projections' matmuls write and ``wo``'s reads; nothing broadcasts k or v to H heads, and a query
    head's dk and dv are summed over its group by ONE reduction a tensor. The lowered text still holds a
    projection's own transpose of its matmul's result, which the compiler folds into the matmul; the COMPILED step
    is what must hold no ``copy`` and no ``transpose`` of a q-sized or a k-sized tensor in the forward pass, and in
    the backward pass none but the cotangents of q, k and v as their projections' weight gradients read them (tokens
    on the lanes: the price of a gradient that leaves its matmul in the leaf's ``[D, H * Dh]``; at most three a
    layer. The parent's holds ten a layer at Mellum's widths: q into ``[B*H, T, Dh]``, the output back, and q, k, v,
    the output and its cotangent again in the backward pass, 134 MB each; and a float32 relayout of the output for
    ``delta``, 268 MB)."""
    import jax.numpy as jnp

    from benchmarks.harness import registry
    from ray_tpu.models.transformer import TransformerConfig

    want = _HEADS_MAJOR_STEPS[which]
    B, H, KV, T, Dh = want["batch"], want["H"], want["KV"], want["T"], 128
    if which == "train2":
        cell = registry.load_cell(registry.load_manifest(), "train2.dense-4k")
        model = registry.load_architecture(cell, "config").model_config(cell["config"], T, "float32")
        for key in ("dtype", "param_dtype"):
            model[key] = jnp.dtype(model[key]).type
        cfg = TransformerConfig(**model, remat=True, fused_loss=True)
    else:
        cfg = _mellum_shaped(H, KV, T, Dh)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (H, KV, Dh)
    lowered, compiled = _train_step_for_the_v5e(cfg, B, one_v5e_chip, monkeypatch)
    text = lowered.as_text()
    q, kv, row = f"{B}x{H}x{T}x{Dh}xbf16", f"{B}x{KV}x{T}x{Dh}xbf16", f"{B}x{H}x1x{T}xf32"
    calls = _flash_calls(text)
    assert {name: len(of) for name, of in calls.items()} == dict.fromkeys(("_flash_kernel", "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel"), want["layers"])
    for operands, results in calls["_flash_kernel"]:
        assert operands == [q, kv, kv] and results == [q, row]  # the log-sum-exp leaves the kernel lane-major, no lane replicated
    for operands, results in calls["_flash_bwd_dkv_kernel"]:
        assert operands == [q, q, kv, kv, row, row] and results == [q, q]  # a query head's dk and dv each
    for operands, results in calls["_flash_bwd_dq_kernel"]:
        assert operands == [q, q, kv, kv, row, row] and results == [q]
    # k and v are never seen at H heads (``jnp.repeat`` is a broadcast into [.., KV, rep, ..]); the only tensors of
    # that shape are dk and dv on their way into the one sum over a group's query heads
    grouped = [line for line in text.splitlines() if f"-> tensor<{B}x{KV}x{H // KV}x{T}x{Dh}x" in line]
    assert "stablehlo.broadcast_in_dim" in text and f"{B}x{T}x{KV}x{H // KV}x{Dh}x" not in text
    assert len(grouped) == 4 * want["layers"] and not any("broadcast" in line for line in grouped)  # a reshape and a convert each
    moved = _moved_whole(compiled.as_text(), {(B, H, T, Dh), (B, KV, T, Dh), (B * H, T, Dh), (B * KV, T, Dh)})
    assert len(moved) <= 3 * want["layers"] and all("/transpose(jvp())/" in line for line in moved), moved


def _cell_config(cell_name: str):
    """(the ``TransformerConfig`` of a serving cell of the benchmark at its
    published widths, its engine's settings)."""
    import jax.numpy as jnp

    from benchmarks.harness import registry
    from ray_tpu.models.transformer import TransformerConfig

    cell = registry.load_cell(registry.load_manifest(), cell_name)
    engine = cell["config"]["deployment"]["engine"]
    model = registry.load_architecture(cell, "config").model_config(
        cell["config"], engine["max_model_len"], "bfloat16"
    )
    model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    return TransformerConfig(**model), engine


def _cell_programs(cell_name: str):
    """(decode, prefill, their arguments as shapes) of a serving cell of the
    benchmark at its published widths: ``args(width)`` for the decode program
    at a rung, ``args(None)`` for the prefill chunk."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import (
        MOE_CHOICE, MOE_COUNTS, init_moe_choice, init_moe_counts, init_paged_cache, ring_blocks, state_kind,
    )
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm.engine import _ROW_TABLE, _STATE_COLS, _compiled_fns

    cfg, engine = _cell_config(cell_name)
    slots, chunk, bs = engine["num_slots"], engine.get("prefill_chunk", 32), engine["block_size"]
    linear = state_kind(cfg) is not None  # a state group a slot, two columns of a program row for it, and no ring
    ring = ring_blocks(cfg.sliding_window, chunk, bs) if "window" in cfg.layer_kinds else 0
    lead = ring + (_STATE_COLS if linear else 0)

    def pool():
        blocks = engine["num_blocks"], bs
        leaves = init_paged_cache(
            cfg, *blocks, window_blocks=slots * ring + 1 if ring else 0, state_slots=slots if linear else 0
        )
        if cfg.routed_experts:
            leaves.update({MOE_COUNTS: init_moe_counts(cfg), MOE_CHOICE: init_moe_choice(cfg, *blocks)})
        return leaves

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    n_max = -(-engine["max_model_len"] // bs)

    def args(width, with_chunk=False):
        if width is None:
            return params, ints(1, chunk), jax.eval_shape(pool), ints(1, _ROW_TABLE + lead + n_max)
        step = params, ints(slots, _ROW_TABLE + lead + width), jax.eval_shape(pool), ints(slots)
        return (*step, ints(1, chunk), ints(1, _ROW_TABLE + lead + width)) if with_chunk else step

    return (*_compiled_fns(cfg, ring)[:2], args)


# sha1 of the StableHLO text of the decode program (at the 64-block rung) and of
# the prefill chunk, lowered for the TPU at the benchmark's widths, as PR 34's
# tree gives them (computed from a copy of that commit, PR 35).
_PROGRAMS_OF_PR_34 = {
    "serve16.chat-open": ("80f55ef50a78c761acd079a445c783f338c74f07", "12bea586c90179b8fe8ea8526dc82e3f9d61ecfa"),
    # Since PR 47 the two configurations whose experts run grouped as PR 47's tree gives them: ``routed_experts``
    # selects zeros into the rows that are no token, where a weight of 0 stood alone (0 x what the grouped kernel left
    # there: NaN at Xing4.0's widths). One select, one convert and the broadcasts of its mask more in each program,
    # 1585 -> 1591 and 1620 -> 1626 operations (GLM), 3922 -> 3934 and 3994 -> 4006 (Trinity), nothing else moved
    # (computed beside a copy of PR 46's commit). They were ("cba2cf83...", "70387e17...") and ("297552fc...", "8fcb0907...").
    "glm8.rollout-long": ("f9f997ae62241335aa2cd39ed7f12aca83698444", "931d4f5514a91ffbdd18dc838e069855b4c12855"),
    # The configuration with a layer pattern, as PR 38's tree gives it (computed from a copy of that commit, PR 40).
    "trinity5.rollout-longctx": ("95e06a237e124b8f5f0ac86c299afb760b83d974", "82c28c7927a264027a126ccbe518ca1398785739"),
    # The configuration with linear-attention layers, as PR 42's tree gives it (computed from a copy of that commit, PR 43).
    "olmo16.longdoc-8k": ("de78f60b73b654d4eadbb63811fa2ff4b68953d9", "0fda9676a9cf4a725d3b4724104b7306aaa6c29a"),
    # The configuration of single-mixer blocks, as PR 44's tree gives it (computed from a copy of that commit, PR 45).
    "nemo14.chat-churn": ("9c5e797dc713f995bdbfed486a2c0bd65e5c5c2b", "6523618bc968aac74fe8215876f17a2e71f772e0"),
}


@pytest.mark.parametrize("cell_name", sorted(_PROGRAMS_OF_PR_34))
def test_a_configuration_without_a_layer_pattern_keeps_the_programs_it_had(cell_name):
    """Runs everywhere: lowering FOR the TPU needs no TPU. PR 35 gave the cached
    layer a second kind, the pool a second group and the program rows a ring; a
    configuration without ``layer_kinds`` (Mistral's three cells, GLM's one)
    must get none of it: operation for operation the programs PR 34 built. PR 40
    gave the cached layer ``parts`` and the engine a step that carries a chunk:
    the two programs every configuration had, Trinity's too, are still the
    parent's operation for operation. PR 43 gave the cached layer blocks of a
    single mixer, a third stack by kind and the experts a held share: Olmo-Hybrid's
    two programs, scanned by kind as the new ones are, joined the table. PR 45
    gave a pool of one group of key and value leaves a kernel where a TPU
    decodes: here (a CPU backend: the view) Mistral's programs are still PR
    34's, and the pattern pools', Nemotron's joined to them, whatever the
    backend (tests/test_latent_paged_kernel.py). A PR
    that changes them on purpose computes the new digests and says what moved."""
    import hashlib

    decode, prefill, args = _cell_programs(cell_name)
    texts = (
        decode.trace(*args(64)).lower(lowering_platforms=("tpu",)).as_text(),
        prefill.trace(*args(None)).lower(lowering_platforms=("tpu",)).as_text(),
    )
    assert tuple(hashlib.sha1(t.encode()).hexdigest() for t in texts) == _PROGRAMS_OF_PR_34[cell_name]


@pytest.mark.parametrize("cell_name", sorted(_PROGRAMS_OF_PR_34))
def test_the_table_of_kinds_names_every_stack_and_every_group_of_the_pool(cell_name):
    """``generate._layer_plan`` against what it is read in place of, at each
    serving architecture's published widths (shapes only): its stacks are
    ``transformer._layer_stacks``' keys, its groups' leaves are
    ``init_paged_cache``'s, every kind of ``layer_kinds`` has a row, and its
    segments cover the layers once, in order."""
    import importlib

    import jax

    from ray_tpu.models.transformer import _layer_stacks

    generate = importlib.import_module("ray_tpu.models.generate")
    cfg, _ = _cell_config(cell_name)
    plan = generate._layer_plan(cfg)
    assert {row.stack for segment in plan for row in segment.rows.values()} == set(_layer_stacks(cfg))
    first = 0
    for segment in plan:
        assert segment.first == first and segment.kinds == cfg.layer_kinds[first : first + segment.depth]
        assert set(segment.rows) == (set(segment.kinds) or {None})
        first += segment.depth
    assert first == cfg.n_layers
    kinds = generate._kinds(cfg)
    assert set(kinds) == (set(cfg.layer_kinds) or {None})
    pool = jax.eval_shape(lambda: generate.init_paged_cache(cfg, 9, 16, window_blocks=5, state_slots=3))
    leaves = {name: row for row in kinds.values() if row.reach for name in generate._group_rows(cfg, row)}
    assert set(leaves) == set(pool)
    held = {"table": (9, 16), "ring": (5, 16), "state": (3,)}
    for name, row in leaves.items():
        assert pool[name].shape[: 1 + len(held[row.reach])] == (row.layers, *held[row.reach]), name
    assert generate.pool_reach(cfg) == {row.reach for row in leaves.values()}


def _without_locations(text: str) -> str:
    """A lowered program's text with each Mosaic kernel's body, which the text
    carries as bytecode WITH the file and line of every frame that traced it
    (this checkout's path, ``generate.py``'s line numbers), replaced by the
    digest of that body printed without them."""
    import base64
    import hashlib
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def digest(body):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            asm = ir.Module.parse(base64.b64decode(body.group(1))).operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22' + hashlib.sha1(asm.encode()).hexdigest() + '\\22'

    text, found = re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', digest, text)
    assert found, "no kernel in this program"
    return text


# The same of the programs a TPU backend gets where a kernel reads the pool in
# place, which the table above cannot hold (a CPU backend lowers the view):
# Mistral-16's decode step and its step with a chunk at the whole table (160
# blocks), GLM's decode step at its whole table (256), as PR 45's tree gives
# them (computed from a copy of that commit, PR 46).
_KERNEL_PROGRAMS_OF_PR_45 = {
    ("serve16.chat-open", "step"): "300a106ea60589510bbff1569b8ba27f96bd2eb2",
    ("serve16.chat-open", "step_with_chunk"): "1454ae8052439a18d8c24bbd91f3bd33418535d1",
    ("glm8.rollout-long", "step"): "7f052d4f03462a7ada2a691555e5dd8d0e262440",  # since PR 47's select in ``routed_experts`` (above); was "a0e0493a..."
}


@pytest.mark.parametrize("cell_name, program", sorted(_KERNEL_PROGRAMS_OF_PR_45))
def test_the_programs_that_read_a_pool_in_place_are_the_ones_pr_45_built(cell_name, program, monkeypatch):
    """Lowered for the TPU from here, as ``_PROGRAMS_OF_PR_34``'s are, with the
    predicate told what it would see there."""
    import hashlib
    import importlib

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.paged_attention", "ray_tpu.ops.latent_attention"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    engine = importlib.import_module("ray_tpu.serve.llm.engine")
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    _, _, args = _cell_programs(cell_name)
    (fns,) = engine._JIT_CACHE.values()
    settings = _cell_config(cell_name)[1]
    n_max = -(-settings["max_model_len"] // settings["block_size"])
    with_chunk = program == "step_with_chunk"
    text = fns[2 if with_chunk else 0].trace(*args(n_max, with_chunk=with_chunk)).lower(lowering_platforms=("tpu",)).as_text()
    assert hashlib.sha1(_without_locations(text).encode()).hexdigest() == _KERNEL_PROGRAMS_OF_PR_45[cell_name, program]


@pytest.mark.parametrize("program", ["step", "step_with_chunk"])
def test_the_kv_decode_programs_read_the_pool_in_place_on_a_tpu(one_v5e_chip, program, monkeypatch):
    """Mistral-16's two decode programs as a TPU backend gets them (PR 45), at
    the benchmark's widths and the whole table (160 blocks), compiled for the
    v5e: ``ops/paged_attention.py``'s kernel reads the decode rows' keys and
    values where they lie (one custom call, its result ``[slots, KV, group,
    Dh]``), no ``[rows, 16, 8, 128]`` view is gathered for them (the step with
    a chunk gathers its chunk's one table and nothing else), the pool is
    aliased and never copied, and the temporaries stay megabytes. The step with
    a chunk (PR 40) runs the d_ff matmuls once over 16 + 32 = 48 rows, none at
    16 or at 32. In a trace either is found as a decode step."""
    import importlib
    import re

    import jax

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.paged_attention"):  # the predicate's, and the kernel's "compiled, not interpreted"
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    engine = importlib.import_module("ray_tpu.serve.llm.engine")
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    _, _, args = _cell_programs("serve16.chat-open")
    (fns,) = engine._JIT_CACHE.values()
    with_chunk = program == "step_with_chunk"
    described = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e_chip), args(160, with_chunk=with_chunk)
    )
    compiled = fns[2 if with_chunk else 0].lower(*described).compile()
    text = compiled.as_text()
    assert re.search(r"HloModule (\S+?),", text).group(1).startswith("jit__lambda")
    assert re.search(r"= bf16\[16,8,4,128\]\S* custom-call\(.*tpu_custom_call", text)
    gathered = set(re.findall(r"= bf16\[(\d+),16,8,128\]\S* fusion\(", text))
    assert gathered == ({"160"} if with_chunk else set()), gathered
    assert ("bf16[48,14336]" in text) == with_chunk and ("bf16[16,14336]" in text) != with_chunk and "bf16[32,14336]" not in text
    assert not re.search(rf"= {re.escape('bf16[16,2561,16,8,128]')}\S* copy\(", text)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 2 * 16 * 2561 * 16 * 8 * 128 * 2  # 2.69 GB updated in place
    assert stats.temp_size_in_bytes < (60e6 if with_chunk else 10e6), stats.temp_size_in_bytes


def test_the_pattern_decode_step_gathers_rings_and_copies_neither_pools_nor_experts(one_v5e_chip):
    """Trinity-Mini's decode program at the benchmark's widths and the cell's
    rung (8192 tokens), compiled for the v5e (PR 35): a window layer's view is
    its ring (161 blocks a row), never the rung's 512; both groups of the pool
    are updated in place; the layers are scanned by period with their weights
    indexed inside the body, which must not copy a layer's expert matrices."""
    import re

    import jax

    decode, _, args = _cell_programs("trinity5.rollout-longctx")
    described = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e_chip), args(512))
    compiled = decode.lower(*described).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    gathered = re.findall(r"= bf16\[(\d+),16,4,128\]\S* fusion\(", text)
    # keys and values: four window layers through 32 rings of 161 blocks, one full layer through 32 tables of 512
    assert gathered.count("5152") == 8 and gathered.count("16384") == 2, gathered
    # whatever else has a block's shape is a pool leaf's own scatter, in place: no wider view of either group
    assert set(gathered) <= {"5152", "16384", "18433", "5153"}, gathered
    for pool_shape in ("bf16[1,18433,16,4,128]", "bf16[4,5153,16,4,128]", "s32[2,4,18433,16]", "s32[8,18433,16]"):
        assert not re.search(rf"= {re.escape(pool_shape)}\S* copy\(", text), pool_shape
    assert not re.search(r"= bf16\[128,(2048,1024|1024,2048)\]\S* (fusion|copy)\(", text)
    stats = compiled.memory_analysis()
    pools = 2 * (18433 + 4 * 5153) * 16 * 4 * 128 * 2 + 2 * 4 * 18433 * 16 * 4
    assert stats.alias_size_in_bytes >= pools  # 1.29 GB updated in place
    # the full layer's view of 32 x 8192 tokens (0.27 GB, keys then values) and little else
    assert stats.temp_size_in_bytes < 450e6, stats.temp_size_in_bytes


def test_the_linear_pattern_programs_copy_neither_pool_nor_state_nor_a_periods_weights(one_v5e_chip):
    """Olmo-Hybrid's decode program at the benchmark's widths and the cell's
    rung (8192 tokens) and its prefill chunk, compiled for the v5e (PR 41). What
    each assertion has seen fail on the way: with 30 cached heads a leaf the
    compiler lays out two ways round and copies the whole pool between them,
    2 GB a copy, and the step does not fit (``generate._cache_heads`` pads to
    32); with a period's layers handed to the outer scan as xs, every matrix of
    three linear layers is copied out before the inner scan reads it (254 MB a
    matrix a period); one query row a head makes the compiler write the whole
    view out in float32 (1 GB for keys, 1 GB for values, a full layer)."""
    import re

    import jax

    decode, prefill, args = _cell_programs("olmo16.longdoc-8k")
    describe = lambda a: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip), a)  # noqa: E731
    pool_bytes = 2 * 4 * 4097 * 16 * 32 * 128 * 2 + 12 * 8 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    for program, given in ((decode, args(512)), (prefill, args(None))):
        compiled = program.lower(*describe(given)).compile()
        text = compiled.as_text()
        for leaf in ("bf16[4,4097,16,32,128]", "f32[12,8,30,96,192]"):
            assert leaf in text and not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), leaf
        assert "bf16[4,4097,16,30,128]" not in text  # no leaf with a head axis that does not fill its tiles
        assert not re.search(r"= bf16\[3,(3840|11008|5760),(11520|11008|3840|5760)\]\S* (fusion|copy)\(", text)  # a period's matrices
        assert not re.search(r"= f32\[(4096,16|8,8192),32,128\]\S* (fusion|copy|convert)\(", text)  # the view in float32
        stats = compiled.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes  # 4.6 GB updated in place
        # a full layer's view of 8 x 8192 tokens (0.54 GB, keys then values) or a chunk's scores (0.54 GB), and little else
        assert stats.temp_size_in_bytes < 0.8e9, stats.temp_size_in_bytes
        assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 15.75 * 2**30


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_single_mixer_programs_copy_neither_pool_nor_state_nor_the_expert_stacks(one_v5e_chip, backend, monkeypatch):
    """Nemotron-3-Nano's decode program at the benchmark's widths and the widest
    rung (2048 tokens) and its prefill chunk, compiled for the v5e (PR 43). The
    experts' widths (2688, 1856) are none that ``jax.lax.ragged_dot``'s kernel
    tiles (``moe.grouped_matmul_tiles``; on the chip it took 7.5 ms a matmul
    where the bytes take 0.8): every held expert runs over every row as batched
    matmuls, which read a block's two [64, ...] stacks where they lie. What
    has been seen to fail on the way: held as a whole stack for a grouped
    matmul, [6, 64, 2688, 1856], whose minor axis fills no lanes, is laid out on
    the device with the model width minor-most and was copied, 3.8 GB, into the
    kernel's layout in every step. The cached head axis of 2 (tiled (2, 128),
    nothing padded), the float32 state and the words of the experts taken are
    updated in place.

    ``tpu`` (PR 49): the programs a TPU backend gets. The decode step is the
    same; the chunk's 3072 assignments (24 rows a group) run
    ``ops/grouped_matmul.py``'s kernel over the stacks held WHOLE, six calls of
    two in the scan over the periods: the up projection's stack goes in
    transposed, which is how it lies (a bitcast), the down projection's as it
    is declared, and neither is copied."""
    import importlib
    import re

    import jax

    if backend == "tpu":
        for module in ("ray_tpu.ops.attention", "ray_tpu.ops.grouped_matmul"):
            monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
        monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
    decode, prefill, args = _cell_programs("nemo14.chat-churn")
    describe = lambda a: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip), a)  # noqa: E731
    pool_bytes = 2 * 2 * 8193 * 16 * 2 * 128 * 2 + 6 * 64 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) + 2 * 6 * 8193 * 16 * 4
    for program, given in ((decode, args(128)), (prefill, args(None))):
        compiled = program.lower(*describe(given)).compile()
        text = compiled.as_text()
        assert "ragged-dot" not in text  # ``ragged_dot``'s kernel tiles neither width
        kernel_calls = re.findall(r"%gmm\S* = bf16\[3072,(?:1856|2688)\]\S* custom-call\(.*operand_layout_constraints=\{(.*?\})\}, ", text)
        assert len(kernel_calls) == (6 if backend == "tpu" and program is prefill else 0)
        assert all("bf16[384,1856,2688]{2,1,0}" in operands for operands in kernel_calls)  # either stack, as it lies
        for leaf in ("bf16[2,8193,16,2,128]", "f32[6,64,64,64,128]", "s32[2,6,8193,16]", "bf16[6,64,2688,1856]", "bf16[6,64,1856,2688]"):
            assert leaf in text and not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), leaf
        stats = compiled.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes  # 1.09 GB updated in place
        # a chunk's 0.17 GB, a step's 0.04: no block's experts (0.64 GB a stack's slice) are written anywhere
        assert stats.temp_size_in_bytes < 0.3e9, stats.temp_size_in_bytes
        assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 11e9  # 10.27 GB of arguments: 61 % of the chip's 16.9


def test_the_hyper_connection_programs_keep_the_stream_on_the_lanes_and_copy_no_pool(one_v5e_chip, monkeypatch):
    """Xing4.0's decode program (the pool read in place, the whole table: the one
    a TPU backend gets) and its prefill chunk at the benchmark's widths,
    compiled for the v5e (PR 47). The residual path is four streams: held
    ``[B, q, 4 * 3584]`` it tiles (8, 128)(2, 1) with nothing padded; with the 4
    on an axis of its own it would sit on the sublanes and pad to 16. The 24
    coefficients a token stay vectors over the tokens (no ``[.., 4, 4]`` array,
    each 4 x 4 a padded tile), and a sub-layer's mixing and joining are some
    twenty small operations, not the forty normalisations one by one. The latent
    pool and the words of the experts taken are updated in place. Since PR 48
    the chunk reads the pool in place too (one kernel under ``jit`` for the dense
    layer and the scanned ones, its one result the ``bf16[1,32,512,640]`` that
    the benchmark's ``trace_ops.latent_prefill`` names): no 768-block view is
    gathered and the float32 scores over it (0.8 GB, until then the chunk's
    largest temporary) are gone."""
    import importlib
    import re

    import jax

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.latent_attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
    decode, prefill, args = _cell_programs("xing6.longdoc-12k")
    describe = lambda a: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip), a)  # noqa: E731
    pool_bytes = 6 * 6145 * 16 * 640 * 2 + 5 * 6145 * 16 * 4
    for program, given, rows, temp in ((decode, args(768), "8,1", 0.1e9), (prefill, args(None), "1,512", 0.3e9)):
        compiled = program.lower(*describe(given)).compile()
        text = compiled.as_text()
        # 3584 and 1024 are widths ``ragged_dot``'s kernel tiles: the step's 32 assignments run it; the chunk's 2048
        # run the kernel whose row tile fits a group (PR 49; the test above this file's slice test holds its operands)
        assert ("ragged-dot" in text) == (rows == "8,1") and bool(re.search(r"%gmm\S* = bf16\[2048,", text)) == (rows == "1,512")
        walked = "8,32,1,640" if rows == "8,1" else "1,32,512,640"  # the walk over the pool, a row's query or a chunk's tiles
        assert re.search(rf"= bf16\[{walked}\]\S* custom-call\(.*tpu_custom_call", text)
        assert not re.search(r"= bf16\[\d+,16,640\]\S* fusion\(", text)  # and no view of a table is gathered
        for leaf in ("bf16[6,6145,16,640]", "s32[5,6145,16]"):
            assert leaf in text and not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), leaf
        assert re.search(rf"bf16\[{rows},14336\]\{{[0-9,]*:T\(8,128\)\(2,1\)", text)  # the stream, tiled whole
        assert not re.search(r"f32\[[0-9,]*,4,4\]", text)
        fusions = len(re.findall(r"^\s+(?:ROOT )?%\S+ = \S+ fusion\(", text, flags=re.M))
        assert fusions < 260, fusions  # 160 and 178 as built (PR 47); 12 sub-layers of ~20 small operations among them
        assert not re.search(r"= bf16\[64,(3584,1024|1024,3584)\]\S* fusion\(", text)  # no layer's experts materialised
        stats = compiled.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes and stats.temp_size_in_bytes < temp, stats.temp_size_in_bytes
        assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 11.5e9  # 10.35 GB of arguments


def test_the_conv_pattern_programs_cache_two_heads_a_row_and_copy_no_pool(one_v5e_chip, monkeypatch):
    """LFM2-24B-A2B's decode program at 128 rows and the widest rung (2048 tokens)
    and its prefill chunk at the benchmark's widths, as a TPU backend gets them,
    compiled for the v5e (PR 54). What has been seen to fail on the way: cached a
    row a head, ``[2, 16385, 16, 8, 64]``, a leaf whose minor axis fills half the
    lanes is laid out one way round for the gather and another for the scatter
    and the whole pool is copied between them, six copies of 1.07 GB a step and
    4.4 GB of temporaries (``generate._heads_paired`` caches two heads a row of
    128 lanes, nothing padded: a token is 4,096 B over the two attention
    layers). The step's 512 assignments are 8 rows a group: it runs
    ``ops/grouped_matmul.py``'s kernel over the two stacks of experts held
    WHOLE (six calls: three in the scan over a period's conv layers, three for
    its attention layer), as the chunk's 2048 do, and no stack is copied. The
    K/V pool and the words of the experts taken are updated in place; the
    carried rows, 7.3 MB, are relaid on the way in and out (two rows a slot tile
    (2, 128) as an argument and (8, 128) inside)."""
    import importlib
    import re

    import jax

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
    decode, prefill, args = _cell_programs("lfm9.rollout-wide")
    describe = lambda a: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip), a)  # noqa: E731
    pool_bytes = 2 * 2 * 16385 * 16 * 4 * 128 * 2 + 7 * 128 * 2 * 2048 * 2 + 8 * 16385 * 16 * 4
    for program, given, rows, temp in ((decode, args(128), 512, 0.35e9), (prefill, args(None), 2048, 0.2e9)):
        compiled = program.lower(*describe(given)).compile()
        text = compiled.as_text()
        assert "ragged-dot" not in text and len(re.findall(rf"%gmm\S* = bf16\[{rows},(?:1536|2048)\]\S* custom-call\(", text)) == 6
        for leaf in ("bf16[2,16385,16,4,128]", "s32[8,16385,16]", "bf16[6,64,2048,1536]", "bf16[2,64,1536,2048]"):
            assert leaf in text and not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), leaf
        assert "bf16[2,16385,16,8,64]" not in text  # no leaf whose minor axis fills half the lanes
        assert not re.search(r"= bf16\[64,(2048,1536|1536,2048)\]\S* (fusion|copy)\(", text)  # no layer's experts materialised
        stats = compiled.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes  # 1.09 GB updated in place
        # a step: one attention layer's view of 128 x 2048 tokens at a time (0.27 GB, keys then values); a chunk 0.15 GB
        assert stats.temp_size_in_bytes < temp, stats.temp_size_in_bytes
        assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 12.0e9  # 11.45 GB of arguments: 68 % of the chip's 16.9


def test_the_block_pass_and_its_chunk_copy_no_pool_and_run_the_grouped_kernel(one_v5e_chip, monkeypatch):
    """SDAR-30B-A3B-Chat's pass over 128 blocks of 4 positions at the widest rung
    (2048 tokens) and its prefill chunk at the benchmark's widths, as a TPU
    backend gets them, compiled for the v5e (PR 56) before any chip run. The
    pool ``[6, 16385, 16, 4, 128]`` a leaf (a KV head fills the 128 lanes: no
    pairing, no padding) and the two words a token a layer of the experts taken
    are updated in place, never copied; both programs' 4,096 assignments are 32
    rows a group and run ``ops/grouped_matmul.py``'s kernel over the stacks of
    experts held WHOLE (three calls in the layer scan), at the new width 768;
    the pass keeps the gathered view (``kernel_reads`` at q = 4: no kernel is
    written for it) and ends in the head over ``[512, 151936]``. A pass's
    temporaries: a layer's views of keys and of values at the rung (2 x 0.27 GB)
    and the float32 logits with what the draw holds beside them: 0.78 GB as
    built; the chunk's 0.14 GB."""
    import dataclasses
    import importlib
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import MOE_CHOICE, MOE_COUNTS, init_moe_choice, init_moe_counts, init_paged_cache, kernel_reads
    from ray_tpu.models.transformer import init_params

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    engine_module = importlib.import_module("ray_tpu.serve.llm.engine")
    monkeypatch.setattr(engine_module, "_JIT_CACHE", {})
    cfg, engine = _cell_config("sdar6.rollout-block")
    slots, chunk, bs, B = engine["num_slots"], engine["prefill_chunk"], engine["block_size"], cfg.block_diffusion
    assert not kernel_reads(cfg, paged=True, q=B) and kernel_reads(dataclasses.replace(cfg, block_diffusion=0), paged=True, q=1)

    def pool():
        leaves = init_paged_cache(cfg, engine["num_blocks"], bs)
        leaves.update({MOE_COUNTS: init_moe_counts(cfg), MOE_CHOICE: init_moe_choice(cfg, engine["num_blocks"], bs)})
        return leaves

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    describe = lambda a: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip), a)  # noqa: E731
    table = engine_module._ROW_TABLE
    block_pass = engine_module._block_pass_fn(cfg)
    prefill = engine_module._compiled_fns(cfg, 0)[1]
    pool_bytes = 2 * 6 * 16385 * 16 * 4 * 128 * 2 + 2 * 6 * 16385 * 16 * 4
    for program, given, temp in (
        (block_pass, (params, ints(slots, table + B + 128), jax.eval_shape(pool), ints(slots, B)), 0.9e9),
        (prefill, (params, ints(1, chunk), jax.eval_shape(pool), ints(1, table + 128)), 0.2e9),
    ):
        compiled = program.lower(*describe(given)).compile()
        text = compiled.as_text()
        assert "ragged-dot" not in text and len(re.findall(r"%gmm\S* = bf16\[4096,(?:768|2048)\]\S* custom-call\(", text)) == 3
        for leaf in ("bf16[6,16385,16,4,128]", "s32[2,6,16385,16]", "bf16[6,128,2048,768]", "bf16[6,128,768,2048]"):
            assert leaf in text and not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), leaf
        assert not re.search(r"= bf16\[128,(2048,768|768,2048)\]\S* (fusion|copy)\(", text)  # no layer's experts materialised
        stats = compiled.memory_analysis()
        assert stats.alias_size_in_bytes >= pool_bytes  # 3.23 GB updated in place
        assert stats.temp_size_in_bytes < temp, stats.temp_size_in_bytes
        assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 13.0e9  # 11.96 GB of arguments: the chip reports 16.9


def test_the_double_layers_programs_walk_two_cached_layers_a_layer_and_copy_neither_pool_nor_experts(one_v5e_chip, monkeypatch):
    """LongCat-Flash-Omni's decode program (128 rows, the whole table: the one a
    TPU backend gets) and its prefill chunk at the benchmark's widths, compiled
    for the v5e (PR 61). A double layer is ONE scan body that walks TWO layers of
    the latent pool in place (64 heads over 640-wide rows, a shape neither
    kernel of ``ops/latent_attention.py`` had run) and runs the branch's grouped
    matmuls over the WHOLE stacks of the 16 held experts: the step's 1,536
    picks ``ragged_dot``, the chunk's 6,144 the kernel whose row tile fits. The
    pool (8 cached layers), the words of the picks and no layer's experts are
    copied; the arguments are the 13.1 GB a chip holds."""
    import importlib
    import re

    import jax

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.latent_attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    monkeypatch.setattr(importlib.import_module("ray_tpu.serve.llm.engine"), "_JIT_CACHE", {})
    decode, prefill, args = _cell_programs("longcat4.rollout-wide")
    describe = lambda a: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip), a)  # noqa: E731
    pool_bytes = 8 * 16385 * 16 * 640 * 2 + 4 * 4 * 16385 * 16 * 4
    for program, given, step in ((decode, args(128), True), (prefill, args(None), False)):
        compiled = program.lower(*describe(given)).compile()
        text = compiled.as_text()
        assert bool(re.search(r"%ragged-dot\S* = bf16\[1536,", text)) == step and bool(re.search(r"%gmm\S* = bf16\[6144,", text)) == (not step)
        walked = "128,64,1,640" if step else "1,64,512,640"  # the walk over the pool, a row's query or a chunk's tiles
        walks = re.findall(rf"= bf16\[{walked}\]\S* custom-call\(.*tpu_custom_call", text)
        assert len(walks) == 2, len(walks)  # one a sub-layer, inside the one body
        assert not re.search(r"= bf16\[\d+,16,640\]\S* fusion\(", text)  # and no view of a table is gathered
        for leaf in ("bf16[8,16385,16,640]", "s32[4,4,16385,16]"):
            assert leaf in text and not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), leaf
        assert not re.search(r"= bf16\[16,(6144,2048|2048,6144)\]\S* (fusion|copy)\(", text)  # no layer's experts materialised
        # nor a layer's pair of FFNs (sliced by the scan a pair was, 13 ms a step on the chip: ``generate._shortcut_layer``)
        assert not re.search(r"bf16\[(1,)?2,(6144,12288|12288,6144)\]", text)
        stats = compiled.memory_analysis()
        # (0.44 GB of it the two small projections' stacks, ``wq_b`` and ``wkv_b``, laid out by head once a call)
        assert stats.alias_size_in_bytes >= pool_bytes and stats.temp_size_in_bytes < 0.9e9, stats.temp_size_in_bytes
        assert 13.0e9 < stats.argument_size_in_bytes < 13.2e9  # 10.38 GB of weights, a 2.68 GB pool


@pytest.mark.parametrize("cell_name", sorted(_PROGRAMS_OF_PR_34))
def test_the_new_fields_at_their_defaults_are_the_configuration_that_states_neither(cell_name):
    """PR 47 sends every join of the cached layer through ``generate._residual``
    and gives ``TransformerConfig`` ``hc_mult`` and ``rope_scaling``. An accepted
    configuration, of each kind of pool the benchmark has, states neither; one
    that states both at their defaults (0: the plain residual; no scaling) is the
    same static argument, so the same programs, whose text
    ``test_a_configuration_without_a_layer_pattern_keeps_the_programs_it_had``
    holds to the parent's; and its softmax scale and rotary tables are the plain ones."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.transformer import _rope_tables, latent_softmax_scale

    cfg, _ = _cell_config(cell_name)
    assert cfg.hc_mult == 0 and cfg.rope_scaling == ()
    stated = dataclasses.replace(cfg, hc_mult=0, rope_scaling={})
    assert stated == cfg and hash(stated) == hash(cfg)
    if cfg.latent_attention:
        assert latent_softmax_scale(cfg) == (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    positions = jnp.arange(8)[None]
    plain, scaled = _rope_tables(positions, 64, cfg.rope_theta), _rope_tables(positions, 64, cfg.rope_theta, cfg.rope_scaling)
    assert all(bool((a == b).all()) for a, b in zip(plain, scaled))


# The same of EVERY serving configuration's two programs as a TPU backend gets them (each kernel's predicate told it
# is on a TPU: the pool read in place where the configuration has such a kernel, the prefill chunk's experts through
# ``ops/grouped_matmul.py``): the decode step at the whole table and the prefill chunk, kernels' bodies without their
# locations, as PR 49's tree gives them (computed from a copy of that commit, PR 50, which gave ``routed_experts`` a
# score function, scopes and a bounded path for a training step's share, ``grouped_matmul`` a ``custom_vjp`` of the
# repo's own and ``_project_qkv`` its rotary by kind of layer: none of it may reach a serving program).
_TPU_PROGRAMS_OF_PR_49 = {
    "serve16.chat-open": ("300a106ea60589510bbff1569b8ba27f96bd2eb2", "12bea586c90179b8fe8ea8526dc82e3f9d61ecfa"),
    "glm8.rollout-long": ("7f052d4f03462a7ada2a691555e5dd8d0e262440", "84e8fc4c41be17775d4d6e03b74b75432e221d6f"),
    "trinity5.rollout-longctx": ("3f92c0e52e7e1dc2cc33e5135e415cc8b96e01a8", "eef0631f6170706e36c020383d44ddd2cd9948a2"),
    "olmo16.longdoc-8k": ("e7be9e7bfae70d4fec81535e9821bc0a4a760f75", "0fda9676a9cf4a725d3b4724104b7306aaa6c29a"),
    "nemo14.chat-churn": ("88e952e949349c8c6516f24d03ffafa061a6acbf", "708022f335fd295af089b98832841c8e12c1d188"),
    "xing6.longdoc-12k": ("bf88be242df72adabf5f9c274ad0be66a09e2431", "f065f095363487da4fcdb34196e8dd95831a9d7f"),
}
# And of Xing4.0's two programs as a CPU backend gets them, which ``_PROGRAMS_OF_PR_34`` lacks.
def test_a_trained_share_sums_its_sorted_rows_to_their_tokens_by_the_kernel_and_scatters_none(one_v5e_chip, monkeypatch):
    """The counter that says PR 58's change engaged: the Mellum-shaped step that
    trains HALF its experts (2 x 512 tokens: 2,048 assignments under a bound of
    1,280 rows of 384), compiled for the v5e, holds a layer and outside the cond
    (the pieces behind the bound) ``ops/rows_to_tokens.py``'s kernel TWICE: once
    forward under ``moe_combine`` (the weighted rows to their tokens, float32)
    and once in the backward pass under ``moe_dispatch`` (the gradient of the
    rows' gather, in the model's dtype); and under neither scope, in the cond or
    out of it, a ``scatter`` over anything as wide as a row (what is left there
    scatters scalars: the gradient of the sorted rows' weights). With the
    kernel taken out the same step lowers to such scatters, two a layer and two
    more in the cond: the parent's program, and the proof that the search
    finds them."""
    import importlib
    import re

    B, T, D, layers_a_body = 2, 512, 384, 2
    cfg = _mellum_shaped(8, 1, T, 128, expert_share=(0, 2))
    moe, rows_to_tokens = importlib.import_module("ray_tpu.parallel.moe"), importlib.import_module("ray_tpu.ops.rows_to_tokens")
    for module in ("ray_tpu.ops.grouped_matmul", "ray_tpu.ops.rows_to_tokens"):  # compiled for the chip, not interpreted
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    lowered, compiled = _train_step_for_the_v5e(cfg, B, one_v5e_chip, monkeypatch)
    sorted_rows = f"tensor<{moe.held_rows(B * T * cfg.experts_per_token, cfg.expert_share)}x{D}x"  # 1280 rows

    def scatters(lowered_text):  # (a scatter's types stand behind its region, some lines below its name)
        return [m.group(0) for m in re.finditer(r'"?stablehlo\.scatter"?\(.*?\) -> tensor<[^>]*>', lowered_text, re.S) if sorted_rows in m.group(0)]

    assert scatters(lowered.as_text()) == []
    calls, scattered = {}, []
    for line in compiled.as_text().splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name is None or not ("moe_combine" in name.group(1) or "moe_dispatch" in name.group(1)):
            continue
        if re.search(r"= \S+ scatter\(", line) and re.search(rf"\[\d+,{D}\]", line):
            scattered.append(line.strip()[:200])
        if "custom-call(" in line and name.group(1).endswith("rows_to_tokens/pallas_call") and "/cond/" not in name.group(1):
            scope = "moe_combine" if "moe_combine" in name.group(1) else "moe_dispatch"
            key = (scope, "backward" if "transpose(jvp())" in name.group(1) else "forward", re.search(r"= (\w+)\[", line).group(1))
            calls[key] = calls.get(key, 0) + 1
    assert scattered == []
    assert calls == {("moe_combine", "forward", "f32"): layers_a_body, ("moe_dispatch", "backward", "bf16"): layers_a_body}
    monkeypatch.setattr(rows_to_tokens, "kernel_sums", lambda *a: False)
    assert len(scatters(_lowered_train_step_for_the_v5e(cfg, B, one_v5e_chip, monkeypatch).as_text())) == 4 * layers_a_body


_PROGRAMS_OF_PR_49 = {"xing6.longdoc-12k": ("338740d7603432f4faf41e8dec89afe6446efa2d", "4b7743fb4648293d40309ee3c41b4c2191bc95d7")}
# The train step of ``train2.dense-4k`` (Mistral-7B at 2 layers, T = 4096, batch 1, AdamW, donated) with the flash
# kernels, and as a CPU backend lowers it; the Switch layer's at a toy size. PR 53 moved all three on purpose (the test's
# docstring says by what); until then they were PR 51's: c74918ba..., d5d19381..., 74e29155... (PR 49's before: e13c36b6...,
# bf81db40..., 354aa964...). PR 60 moved Mistral's two on purpose (the docstring says by what; PR 53's were c45250bf...,
# 11c51b6d...); the Switch layer's is PR 53's still.
_TRAIN_STEPS_OF_PR_53 = {
    "train2@tpu": "588d1850abe65849c1b98262957765a8a4f04afe", "train2@cpu": "3f542ff1684ff1bcd611a17a4aba5c5bdb7df1fb", "switch": "5cf04e44eec22de9c8d7f7a1e642e2a502befc43",
}


def _digest(text, kernels=True):
    import hashlib

    if kernels:
        try:
            text = _without_locations(text)
        except AssertionError:  # no kernel in this program
            pass
    return hashlib.sha1(text.encode()).hexdigest()


@pytest.mark.parametrize("cell_name", sorted(_TPU_PROGRAMS_OF_PR_49))
def test_every_serving_configurations_tpu_programs_are_the_parents(cell_name, monkeypatch):
    import importlib

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.paged_attention", "ray_tpu.ops.latent_attention", "ray_tpu.ops.grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu", lambda: True)
    engine = importlib.import_module("ray_tpu.serve.llm.engine")
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    _, _, args = _cell_programs(cell_name)
    (fns,) = engine._JIT_CACHE.values()
    settings = _cell_config(cell_name)[1]
    n_max = -(-settings["max_model_len"] // settings["block_size"])
    texts = (
        fns[0].trace(*args(n_max)).lower(lowering_platforms=("tpu",)).as_text(),
        fns[1].trace(*args(None)).lower(lowering_platforms=("tpu",)).as_text(),
    )
    assert tuple(_digest(t) for t in texts) == _TPU_PROGRAMS_OF_PR_49[cell_name]


@pytest.mark.parametrize("cell_name", sorted(_PROGRAMS_OF_PR_49))
def test_the_seventh_configurations_cpu_programs_are_the_parents(cell_name):
    decode, prefill, args = _cell_programs(cell_name)
    texts = (
        decode.trace(*args(64)).lower(lowering_platforms=("tpu",)).as_text(),
        prefill.trace(*args(None)).lower(lowering_platforms=("tpu",)).as_text(),
    )
    assert tuple(_digest(t, kernels=False) for t in texts) == _PROGRAMS_OF_PR_49[cell_name]


@pytest.mark.parametrize("which", sorted(_TRAIN_STEPS_OF_PR_53))
def test_the_accepted_train_steps_are_the_parents(which, monkeypatch):
    """PR 50 gave the training block a pattern of layers, a rotary table a kind,
    routed experts and a balance coefficient: a configuration that states none of
    them (Mistral's two cells, the Switch layer) lowered to the parent's text.
    PR 51 moved all three on purpose, by ONE thing: a layer under ``remat`` keeps
    q, k, v and the attention core's results (``transformer._KEPT_UNDER_REMAT``),
    so the backward scan's body holds no second forward of the attention core
    (on a TPU: no second ``_flash_kernel``, counted in ``tests/test_models.py``)
    nor the projections, norms and rotary that fed it. PR 53 moved all three
    again, by ONE thing: ``_attention_block`` holds q, k, v heads before tokens,
    ``[B, H or KV, T, Dh]``, from the projections (``h @ w``, the result
    transposed, which the compiler folds into the matmul) to ``wo`` (the rotary
    as a signed permutation on the MXU, K and V never repeated), which is where
    the flash kernels read them: the CPU texts moved with the TPU's because the
    block is one program for both, only the attention core differs. PR 60
    moved Mistral's two, by ONE thing each: its window of 4096 over 4096 keys
    binds nothing and reaches the attention core as no window (on a CPU the
    reference builds no second mask; on a TPU the three kernels are called
    with ``window = 0``), and on a TPU each kernel's block edges are
    ``attention._blocks``' for that mask, (512, 512), (512, 512) and (512,
    1024), where they were (1024, 1024) forward and (512, 512) backward. The
    Switch layer's 128 tokens take the XLA core and stay. Later PRs that leave
    the training block alone keep these texts."""
    import importlib

    import jax
    import jax.numpy as jnp
    import optax

    from benchmarks.harness import registry
    from ray_tpu.models.transformer import TransformerConfig, init_params, make_train_step

    if which == "switch":
        cfg, tokens, donate = TransformerConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, max_seq_len=128, num_experts=4), (2, 129), ()
    else:
        cell = registry.load_cell(registry.load_manifest(), "train2.dense-4k")
        model = registry.load_architecture(cell, "config").model_config(cell["config"], 4096, "float32")
        for key in ("dtype", "param_dtype"):
            model[key] = jnp.dtype(model[key]).type
        cfg, tokens, donate = TransformerConfig(**model, remat=True, fused_loss=True), (1, 4097), (0, 1)
    if which == "train2@tpu":
        monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    assert cfg.balance_loss_coef == 0.01 and cfg.router_score == "sigmoid" and cfg.rope_scaling == ()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt = optax.adamw(1e-4)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=donate)
    text = step.trace(params, jax.eval_shape(opt.init, params), {"tokens": jax.ShapeDtypeStruct(tokens, jnp.int32)}).lower(lowering_platforms=("tpu",)).as_text()
    assert _digest(text, kernels=which == "train2@tpu") == _TRAIN_STEPS_OF_PR_53[which]
