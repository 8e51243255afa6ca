"""The seventh architecture of the benchmark (PR 50), ``MellumForCausalLM``
(Mellum2-12B-A2.5B: dropless softmax top-8-of-64 experts under a 3 : 1 pattern
of window-1024 and YaRN-scaled full attention), TRAINED as one chip of a 4-way
expert-parallel job, and the cell PR 50 adds, the benchmark's first training
cell that is not dense Mistral: the configuration against the catalog's row, the
cut and the counts against hand arithmetic and against the parameter tree the
program draws, the job, the cell at a toy size through ``run.measure`` on the
CPU against its own float32 reference, what a program from before PR 50 is told,
and that nothing was entered but files and appended entries. Nothing here pins
the END of a list that a later PR may append to, nor a count."""

import json
import os
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry

MELLUM, CELL, JOB, ARCH = "mellum2-12b-a2.5b-train4", "mellum4.moe-8k", "moe-8k", "MellumForCausalLM"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "Mellum2-12B-A2.5B-Instruct"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts", "vocab_size"]
TRAINING_METRICS = {"trainer_first_step_s", "train_step_ms", "mfu_pct", "train_host_gap_ms", "device_idle_pct.train"}


def _config(manifest):
    return registry.load_cell(manifest, CELL)["config"]


def _part(part):
    return registry.load_architecture({"name": "these tests", "architecture": ARCH, "bench_dir": registry.BENCH_DIR}, part)


def test_the_configuration_holds_the_catalogs_row(manifest):
    """Every key of the catalog row's ``config`` under the same key with the same
    value (nested groups whole), but the five that the cut changes, which
    ``reduced`` and ``published`` both name."""
    cfg = _config(manifest)
    entry = next(c for c in manifest["configs"] if c["name"] == MELLUM)
    assert cfg["reduced"] == entry["reduced"] == REDUCED and set(cfg["published"]) == set(REDUCED)
    assert cfg["architectures"] == [ARCH] and cfg["path"] == "train" and len(entry["why"]) <= 200
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 24576)
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"] and cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["published"]["layer_types"][:4] == cfg["layer_types"] and len(cfg["published"]["layer_types"]) == 28
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    except OSError:
        pytest.skip("no catalog beside this installation")
    assert row["source_url"] == cfg["source"] == entry["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    for key in ("architectures", "query and key norms", "router", "rotary layout", "YaRN", "balance loss", "weights"):
        assert key in cfg["assumed"] and ("no network" in cfg["assumed"][key] or key in ("rotary layout", "weights")), key
    assert "multi-token-prediction" in cfg["left_out"] and "Nothing of them is built" in cfg["left_out"]
    assert cfg["deployment"]["expert_parallel"] == {"chips": 4, "index": 0} and cfg["deployment"]["balance_loss_coef"] == 0.001


def test_the_program_is_told_the_rows_keys_and_its_share(manifest):
    cfg = _config(manifest)
    model = _part("config").model_config(cfg, 8192, "float32")
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"], model["head_dim"], model["d_expert"]) == (2304, 32, 4, 128, 896)
    assert (model["n_layers"], model["num_experts"], model["experts_per_token"], model["expert_share"]) == (4, 64, 8, [0, 4])
    assert model["layer_kinds"] == ["window", "window", "window", "full"] and model["sliding_window"] == 1024
    assert model["rope_theta"] == 500000.0 and model["norm_eps"] == 1e-6
    assert model["rope_scaling"] == dict(factor=16, original_max_position_embeddings=8192, beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=0.0)
    assert model["router_score"] == "softmax" and model["router_bias"] is False and model["qk_norm"] is True
    assert model["balance_loss_coef"] == 0.001 and model["vocab_size"] == 24576 and model["tie_embeddings"] is False
    # the amplitude the program's tables take from (mscale 1, mscale_all_dim 0) is the published attention_factor
    import math

    assert 0.1 * math.log(16) + 1.0 == pytest.approx(cfg["rope_parameters"]["full_attention"]["attention_factor"], abs=1e-12)
    for key, wrong, why in (("norm_topk_prob", False, "norm_topk_prob"), ("attention_bias", True, "attention_bias"),
                            ("mlp_layer_types", ["dense"] * 4, "every layer is sparse"), ("num_experts", 32, "not the published 64")):
        with pytest.raises(ValueError, match=why):
            _part("config").model_config(dict(cfg, **{key: wrong}), 8192, "float32")
    wrong = json.loads(json.dumps(cfg))
    wrong["rope_parameters"]["full_attention"]["attention_factor"] = 1.0
    with pytest.raises(ValueError, match="attention_factor"):
        _part("config").model_config(wrong, 8192, "float32")


def test_a_program_from_before_the_training_block_is_refused_in_the_driver_process(manifest, monkeypatch):
    """The parent commit's ``TransformerConfig`` has no score function to state:
    ``model_config`` says so from its source, in the process that never imports
    jax, and ``run.py`` exits non-zero in seconds (the builder's chip run of the
    parent: rc 1)."""
    config = _part("config")
    assert {"router_score", "router_bias", "balance_loss_coef"} <= config._program_fields()
    monkeypatch.setattr(config, "_program_fields", lambda: config.__dict__["_fields_of_the_parent"])
    config._fields_of_the_parent = {
        "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim", "d_ff", "rope_theta", "norm_eps", "tie_embeddings",
        "dtype", "param_dtype", "max_seq_len", "sliding_window", "layer_kinds", "rope_scaling", "qk_norm", "num_experts",
        "experts_per_token", "d_expert", "expert_share",
    }
    with pytest.raises(NotImplementedError, match="balance_loss_coef, router_bias, router_score.*no training block"):
        config.model_config(_config(manifest), 8192, "float32")


def test_the_counts_against_hand_arithmetic(manifest):
    """ISSUE 50's arithmetic, part by part."""
    cfg, costs = _config(manifest), _part("costs")
    assert costs.attention_params(cfg) == 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 == 21_233_664
    assert costs.expert_params(cfg) == 3 * 2304 * 896 == 6_193_152 and costs.router_params(cfg) == 2304 * 64
    layer = 21_233_664 + 16 * 6_193_152 + 2304 * 64 + 2 * 2304 + 2 * 128
    assert costs.layer_params(cfg) == layer and round(layer / 1e6, 1) == 120.5
    assert costs.n_params(cfg) == 4 * layer + 2 * 24576 * 2304 + 2304 == 595_154_176
    assert 9.52e9 < 4 * costs.weight_bytes(cfg) < 9.53e9  # parameters, two moments, gradients: 16 B a parameter
    whole = dict(cfg, num_hidden_layers=28, num_experts=64, vocab_size=98304, layer_types=cfg["published"]["layer_types"])
    assert round(costs.n_params(whole) / 1e9, 2) == 12.15  # the model as published
    assert costs.held_experts_per_token(cfg) == 2.0
    assert costs.causal_pairs(8192) == 8192 * 8193 // 2 and costs.causal_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
    pairs = 3 * costs.causal_pairs(8192, 1024) + costs.causal_pairs(8192)
    assert costs.attention_fwd_flops(cfg, 8192) == 4 * 128 * 32 * pairs
    dense = 4 * (21_233_664 + 2304 * 64) + 2304 * 24576
    want = 2 * (8192 * 6 * dense + 3 * 4 * 128 * 32 * pairs) + 2 * 8192 * 6 * 4 * 2 * 6_193_152
    assert costs.train_step_flops(cfg, 8192, 2) == want and 24.0e12 < want < 25.0e12  # ISSUE 50: ~24.5 TFLOP a step
    assert costs.moe_train_flops(cfg, 8192, 2) == 2 * 8192 * 6 * 4 * 2 * 6_193_152
    assert 0.19 < costs.moe_train_flops(cfg, 8192, 2) / want < 0.21  # the held experts are a fifth of the operations
    assert costs.moe_train_bytes(cfg) == 2 * 4 * 16 * 6_193_152 * 4
    assert not hasattr(costs, "kv_bytes_per_token") and not hasattr(costs, "decode_step_bytes")  # it does not serve


def test_the_counts_are_the_parameter_tree_the_program_draws(manifest):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params, num_params

    cfg = _config(manifest)
    model = _part("config").model_config(cfg, 8192, "float32")
    model.update(dtype=jnp.bfloat16, param_dtype=jnp.float32)
    program = TransformerConfig(**model)
    assert program.inference_only == ""
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), program))
    assert num_params(tree) == _part("costs").n_params(cfg)
    layers = tree["layers"]
    assert layers["wg_e"].shape == (4, 16, 2304, 896) and layers["wo_e"].shape == (4, 16, 896, 2304)
    assert layers["gate"].shape == (4, 2304, 64) and layers["gate"].dtype == jnp.float32 and "gate_bias" not in layers
    assert layers["wq"].shape == (4, 2304, 4096) and layers["wk"].shape == (4, 2304, 512) and layers["q_norm"].shape == (4, 128)
    assert tree["embed"].shape == (24576, 2304) and tree["lm_head"].shape == (2304, 24576)
    assert set(_part("reference").LAYER_LEAVES.values()) == set(layers)


def test_the_reference_stands_on_its_own():
    """Float32 at ``highest``; nothing of the program's expert dispatch, kernels
    or rotary tables."""
    with open(os.path.join(registry.BENCH_DIR, "architectures", ARCH, "reference.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines() if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import math", "import jax", "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in source and "ragged_dot" not in source.split('"""', 2)[2]
    for name in ("ray_tpu", "_rope_tables", "routed_experts", "grouped_matmul", "argsort"):
        assert name not in source.split('"""', 2)[2], name


def test_the_job_is_the_issues_and_nothing_but_files_and_appended_entries_came(manifest):
    cell = registry.load_cell(manifest, CELL)
    job = cell["traffic"]
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["report_every"], job["trace_slice_s"]) == ("train", 8192, 2, 4, 3.0)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == dict(name=CELL, config=MELLUM, traffic=JOB, chips=1, why=entry["why"]) and len(entry["why"]) <= 200
    reports = {m["name"] for m in registry.cell_metrics(manifest, CELL, "end_to_end")}
    assert reports == {"train_tokens_per_s", "setup_s"}
    layers = {m["name"] for m in registry.cell_metrics(manifest, CELL, "per_layer")}
    # ISSUE 50's moe_train_ms / moe_train_roofline were NOT entered: harness/trace.py keeps a slice's forty longest
    # operations, and the 48 grouped calls of a step (0.8-1.1 ms each) are not among them (PERF.md section 5)
    assert layers == TRAINING_METRICS
    for m in manifest["per_layer"]:
        assert not m["name"].startswith("moe_train"), m["name"]
        if m["name"] in TRAINING_METRICS:
            assert m["workloads"].index("train2.dense-4k") < m["workloads"].index(CELL) and m["moves"] in ("train_tokens_per_s", "setup_s")
    throughput = next(m for m in manifest["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert throughput["workloads"].index("train2.dp4-4k") < throughput["workloads"].index(CELL) and throughput["bound"] == 0.01
    names = [c["name"] for c in manifest["configs"]]
    assert names.index(MELLUM) > names.index("xing4.0-29b-a4b-serve6")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("xing6.longdoc-12k") and sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cfg = cell["config"]
    assert cfg["deployment"]["mosaic_kernels"] == ["_flash_kernel", "_flash_bwd", "kernel"]
    assert cfg["trace_programs"] == {"train_step": "^jit_train_step"} and "trace_ops" not in cfg


def test_each_training_configuration_states_its_deployment(manifest):
    """What ``train_cell.py`` reads of a training configuration, for each the benchmark has."""
    for entry in manifest["workloads"]:
        cell = registry.load_cell(manifest, entry["name"])
        if cell["config"]["path"] != "train":
            continue
        dep, check = cell["config"]["deployment"], cell["config"]["check"]
        assert dep["param_dtype"] == "float32" and dep["remat"] is True and dep["fused_loss"] is True and dep["learning_rate"] == 1e-4
        assert check["loss_abs_tol"] > 0 and 0 < check["grad_rel_l2_tol"] < 1 and dep["mosaic_kernels"][:2] == [
            "_flash_kernel", "_flash_bwd"]
        costs = registry.load_architecture(cell, "costs")
        tokens = cell["traffic"]["seq_len"] * cell["traffic"]["batch_per_chip"] * cell["chips"]
        flops = costs.train_step_flops(cell["config"], cell["traffic"]["seq_len"], cell["traffic"]["batch_per_chip"] * cell["chips"])
        assert 1e9 < flops / tokens < 6 * costs.n_params(cell["config"]) * 2  # mfu_pct reads it: of the order of 6 a weight and token


def test_the_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path):
    """``toy_cell`` through ``run.measure`` on the CPU: ``JaxTrainer`` ->
    ``TrainWorker`` -> ``make_train_step``, the check against the architecture's
    own float32 reference (float32 activations at the toy size: no token's
    experts flip, so every leaf agrees to 1e-4), the window, the line."""
    cell = toy_cell(manifest, CELL)
    assert cell["config"]["torch_dtype"] == "float32" and cell["traffic"]["seq_len"] == 128
    result = bench_run.measure(
        cell, seed=2**31 + 50, seconds=2.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    check = result["notes"]["check"]
    assert abs(check["loss"] - check["ref_loss"]) < 1e-4 and max(check["grad_rel_l2"].values()) < 1e-4
    assert "layers/gate" in check["grad_rel_l2"] and "layers/gate_bias" not in check["grad_rel_l2"]
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, CELL, traced=False, platform="cpu")
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["train"]["tokens_per_step"] == result["train"]["batch"] * 128 and result["train"]["kernels_in_step"] == []
    assert {"check_s", "compile_s", "trainer_first_step_s", "setup_s"} <= set(result["clock"])
