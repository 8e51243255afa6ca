"""PR 63: the yardstick names work, not today's implementation of it.

- The per-kernel readers find a kernel by its NAME beside today's operations by
  their shapes: ``trace_ops.cache_attention`` of the five configurations with a
  pattern pool also takes ``%paged_attention*`` (the call of
  ``ops/paged_attention.py``'s walk kernel). (``trace_ops.moe_experts`` of the
  four whose decode step runs XLA's ``ragged_dot`` does NOT take ``%gmm*`` yet:
  ``tests/test_latent_moe.py`` holds such a pattern to name no ``gmm``, and a
  ``benchmark`` PR may not edit that file. PERF.md section 7, "Left by PR 63".)
- The whole decode step's share of the HBM peak bears ``mfu`` in its name and
  moves ``itl_p95_ms`` in every serving cell; the old name is gone.
- The training check takes an entry of ``deployment.mosaic_kernels`` as a
  PREFIX of a kernel's name.
"""

from __future__ import annotations

import ast
import inspect
import os
import re
import types

import pytest

from benchmarks.harness import registry, train_cell
from benchmarks.harness.peaks import peaks

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1}
STEPS = 20  # decode programs in the synthetic slice

# cell, one of today's operations that its ``trace_ops.cache_attention`` names, the kernel's call that may take its place
EDITED = [
    ("trinity5.rollout-longctx", "%fusion.19 fusion bf16[16384,16,4,128]", "%paged_attention.7 custom-call bf16[32,8,4,128]"),
    ("olmo16.longdoc-8k", "%fusion.308 fusion bf16[4096,16,32,128]", "%paged_attention.7 custom-call bf16[8,1,32,128]"),
    ("nemo14.chat-churn", "%fusion.887 fusion bf16[8192,16,2,128]", "%paged_attention.7 custom-call bf16[64,2,16,128]"),
    ("lfm9.rollout-wide", "%fusion.729 fusion bf16[16384,16,4,128]", "%paged_attention.7 custom-call bf16[128,4,8,128]"),
    ("sdar6.rollout-block", "%fusion.487 fusion bf16[16384,16,4,128]", "%paged_attention.7 custom-call bf16[128,4,32,128]"),
]
# What no edited pattern may take: the latent pools' two kernels, a prefill chunk's grouped matmuls, a matmul beside them.
NOT_THEIRS = [
    ["%paged_latent_attention.14 custom-call bf16[4,16,1,320]", 0.7],
    ["%paged_latent_chunk_attention.3 custom-call bf16[1,16,256,320]", 0.9],
    ["%gmm.13 custom-call bf16[2048,1536]", 1.1],
    ["%gmm.14 custom-call bf16[6144,2048]", 1.3],
    ["%fusion.9 fusion bf16[512,6144]", 0.5],
]


def _result(cell, ops):
    """What the pair of readers sees of a traced run of the cell, by hand, under the cell's OWN ``trace_ops``."""
    fields = ["t_start_ns", "rows", "prefill_tokens", "context_tokens", "window_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 8, 0, 40_000, 20_000, 10**6] for i in range(6)]
    counters = {"spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}}
    return {
        "cell": cell, "seconds": 51.0, "traced": True, "counters": counters, "device": DEVICE,
        "trace": {"devices": [{"programs": {"decode": [0.03] * STEPS, "prefill": [0.04] * 2}, "ops": ops + NOT_THEIRS}]},
    }


@pytest.mark.parametrize("cell_name, todays, kernels", EDITED, ids=[c for c, _, _ in EDITED])
def test_an_edited_pattern_finds_todays_operations_and_the_kernel_that_may_replace_them(manifest, cell_name, todays, kernels):
    """With the parent's operations alone a reader gives the parent's number; with the kernel's call in their place
    ``*_ms`` is that call's time a step and ``*_roofline`` stays under 100; with both they sum; the latent kernels'
    calls and a chunk's grouped matmuls are never taken."""
    cell = registry.load_cell(manifest, cell_name)
    readers = {suffix: registry.load_metric("per_layer", f"cache_attention_{suffix}") for suffix in ("ms", "roofline")}
    # the work, whatever does it: the rows' keys and values at their lengths
    least_s = (registry.load_architecture(cell, "costs").cache_attention_bytes(cell["config"], 40_000, 20_000)
               / peaks(DEVICE["kind"])["hbm_bytes_per_s"])

    def read(ops):
        result = _result(cell, ops)
        return {suffix: reader(result) for suffix, reader in readers.items()}

    assert not any(re.search(cell["config"]["trace_ops"]["cache_attention"], name) for name, _ in NOT_THEIRS)
    for ops, seconds in (([[todays, 0.4]], 0.4), ([[kernels, 0.3]], 0.3), ([[todays, 0.4], [kernels, 0.3]], 0.7)):
        got = read(ops)
        assert got["ms"] == pytest.approx(1000 * seconds / STEPS)
        assert got["roofline"] == pytest.approx(100 * least_s / (seconds / STEPS)) and 0 < got["roofline"] < 100
    assert read([]) == {"ms": None, "roofline": None}  # nothing of theirs in the slice: no 0, nothing


def test_the_whole_decode_steps_share_bears_mfu_in_its_name_in_every_serving_cell(manifest):
    """``decode_mfu_roofline`` is declared for every serving cell, moves ``itl_p95_ms`` there, and the name it had
    until PR 63 is left nowhere in the benchmark: a later PR whose kernel silences a per-kernel roofline still has a
    share of the WHOLE step, with ``mfu`` in its name, that bounds its claim."""
    old = "decode_" + "roofline"
    serving = [w["name"] for w in manifest["workloads"] if registry.load_cell(manifest, w["name"])["config"]["path"] == "serve"]
    entry = next(m for m in manifest["per_layer"] if m["name"] == "decode_mfu_roofline")
    assert len(serving) == 11 and entry["workloads"] == serving
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == ("%", "higher", "device_trace", "model", "itl_p95_ms")
    itl = next(m for m in manifest["end_to_end"] if m["name"] == "itl_p95_ms")
    assert set(entry["workloads"]) == set(itl["workloads"])
    assert old not in {m["name"] for m in manifest["per_layer"]}
    assert os.path.isfile(os.path.join(registry.BENCH_DIR, "layer_metrics", "decode_mfu_roofline.py"))
    left = []
    for top in (registry.BENCH_DIR, os.path.dirname(os.path.abspath(__file__))):
        for folder, _, files in os.walk(top):
            for name in files:
                if name.endswith((".py", ".json", ".md", ".txt")):
                    with open(os.path.join(folder, name)) as f:
                        if old in f.read():
                            left.append(os.path.join(folder, name))
    with open(os.path.join(registry.ROOT, "BENCHMARK.json")) as f:
        assert old not in f.read() and left == []
    # and the chunk-alone program's time is asked only of the cells whose slices hold one
    chunk = next(m for m in manifest["per_layer"] if m["name"] == "prefill_chunk_ms")
    assert chunk["workloads"] == ["serve16.long-prompt", "olmo16.longdoc-8k"] and chunk["moves"] == "ttft_p90_ms"


def _kernels_found(entries, lowered_text):
    """``train_cell.train_loop``'s own comprehension over ``dep["mosaic_kernels"]``, cut out of its source and run
    on a text in the lowered step's place."""
    comps = [n for n in ast.walk(ast.parse(inspect.getsource(train_cell)))
             if isinstance(n, ast.ListComp) and "mosaic_kernels" in ast.unparse(n)]
    assert len(comps) == 1
    code = compile(ast.fix_missing_locations(ast.Expression(comps[0])), "train_cell.py", "eval")
    return eval(code, {"dep": {"mosaic_kernels": entries}, "lowered": types.SimpleNamespace(as_text=lambda: lowered_text)})


def _lowered(*names):
    return "\n".join(f'  %{i} = tpu_custom_call ... kernel_name = "{name}", ...' for i, name in enumerate(names))


@pytest.mark.parametrize(
    "names, found",
    [
        (("_flash_kernel", "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel", "kernel"), ["_flash_kernel", "_flash_bwd", "kernel"]),
        (("_flash_kernel", "_flash_bwd_kernel", "kernel"), ["_flash_kernel", "_flash_bwd", "kernel"]),
        (("kernel",), ["kernel"]),  # a step that fell back to XLA's attention
        (("my_flash_kernel", "not_flash_bwd"), []),  # a prefix, not a part: the opening quote stands before it
    ],
    ids=["two-backward-kernels", "one-fused-backward-kernel", "no-flash-kernel", "a-prefix-not-a-part"],
)
def test_a_training_configuration_names_its_kernels_by_prefix(manifest, monkeypatch, tmp_path, names, found):
    """``deployment.mosaic_kernels`` asks for a flash forward and a flash backward kernel, not for today's three
    names: two backward kernels pass, one fused one passes, and a step without them reads ``correct`` false."""
    cell = registry.load_cell(manifest, "mellum4.moe-8k")
    entries = cell["config"]["deployment"]["mosaic_kernels"]
    assert entries == ["_flash_kernel", "_flash_bwd", "kernel"]
    assert registry.load_cell(manifest, "train2.dense-4k")["config"]["deployment"]["mosaic_kernels"] == entries[:2]
    assert _kernels_found(entries, _lowered(*names)) == found

    # the rest of train_cell.run over a worker's report that holds those kernels
    report = {
        "event": "done", "t_open": 10.0, "clock": {"first_step_s": 1.0}, "steps": 8, "window_s": 2.0, "losses": [2.0] * 8,
        "check": {"loss": 2.0, "ref_loss": 2.0, "grad_rel_l2": {"layers/wq": 0.0}}, "kernels_in_step": found,
        "longest_fetch_gap": (0.25, 3), "trace": None, "device": {"platform": "tpu"}, "pid": 0,
    }

    class Trainer:
        def __init__(self, *args, **kwargs):
            pass

        def fit(self):
            return types.SimpleNamespace(metrics=report)

    import ray_tpu.train.jax

    monkeypatch.setattr(ray_tpu.train.jax, "JaxTrainer", Trainer)
    result = train_cell.run(cell, seed=1, seconds=2.0, traced=False, t_process=0.0, scratch=str(tmp_path))
    assert result["correct"] is (found == entries) and result["failed"] == 0
