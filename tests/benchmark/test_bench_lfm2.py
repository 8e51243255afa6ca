"""The eighth architecture of the benchmark (PR 54), ``Lfm2MoeForCausalLM``
(LFM2-24B-A2B: gated short-convolution layers three to one among QK-normed GQA
layers of 64-wide heads, each followed by sigmoid top-4-of-64 experts behind a
leading dense layer), and the cell PR 54 adds, ``lfm9.rollout-wide``: the
configuration against the catalog's row, the cut and the counts against hand
arithmetic and against the parameter tree the program draws, the cell at a toy
size through ``run.measure`` on the CPU, the two new per-layer metrics' readers
and the accepted ones that read this architecture's ``costs.py``, and what
stands in for the two tests of ``tests/benchmark/`` that a ninth configuration's
appended entries made wrong (``tests/conftest.py`` marks those). Nothing here
pins the END of a list that a later PR may append to: a new entry is held to
come AFTER the ones it was appended behind."""

import json
import os
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic

LFM, CELL, MIX, ARCH = "lfm2-24b-a2b-serve9", "lfm9.rollout-wide", "rollout-wide", "Lfm2MoeForCausalLM"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "LFM2-24B-A2B"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types"]
NINE = ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]
NEW_METRICS = ["short_conv_ms", "short_conv_roofline"]
CACHE_PAIR = {"cache_attention_ms", "cache_attention_roofline"}
CACHE_PAIR_CELLS = ["trinity5.rollout-longctx", "olmo16.longdoc-8k", "nemo14.chat-churn", CELL]
THE_EIGHT_OF_A_START = [
    "replica_spawn_s", "replica_backend_s", "programs_python_s", "programs_python_waiting_pct", "programs_compile_s",
    "programs_compiled_afresh", "trainer_spawn_s", "trainer_backend_s",
]
BEFORE = 11  # cells the benchmark had


def _config(manifest):
    return registry.load_cell(manifest, CELL)["config"]


def _part(part):
    return registry.load_architecture({"name": "these tests", "architecture": ARCH, "bench_dir": registry.BENCH_DIR}, part)


def test_the_configuration_holds_the_catalogs_row(manifest):
    """Every number of the catalog row's ``config`` under the same key, but the
    three that the cut changes, which ``reduced`` and ``published`` both name."""
    cfg = _config(manifest)
    entry = next(c for c in manifest["configs"] if c["name"] == LFM)
    assert cfg["reduced"] == entry["reduced"] == REDUCED and set(cfg["published"]) == set(REDUCED)
    assert cfg["architectures"] == [ARCH] and cfg["path"] == "serve" and cfg["torch_dtype"] == "bfloat16"
    assert entry["file"] == f"benchmarks/configs/{LFM}.json" and len(entry["why"]) <= 200
    types = cfg["published"]["layer_types"]
    assert (cfg["published"]["num_hidden_layers"], cfg["published"]["num_dense_layers"]) == (40, 2)
    assert (types.count("conv"), types.count("full_attention"), len(types)) == (30, 10, 40)
    assert [i for i, t in enumerate(types) if t == "full_attention"] == list(range(2, 40, 4))  # one in four from layer 2 on
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    except OSError:
        pytest.skip("no catalog beside this installation")
    assert row["source_url"] == cfg["source"] == entry["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    for key in ("architectures", "torch_dtype", "tie_word_embeddings", "head_dim", "split order", "no activation", "router", "weights"):
        assert key in cfg["assumed"], key
    assert "1e-6" in cfg["assumed"]["router"] and "B | C | u" in cfg["assumed"]["split order"] and "head_dim" not in cfg


def test_the_cut_is_the_leading_dense_layer_and_two_whole_periods(manifest):
    cfg = _config(manifest)
    types = cfg["published"]["layer_types"]
    assert cfg["layer_types"] == NINE == [types[0], *types[2:10]] and cfg["num_hidden_layers"] == 9 and cfg["num_dense_layers"] == 1
    assert NINE[1:] == ["full_attention", "conv", "conv", "conv"] * 2 and types[2:38] == NINE[1:5] * 9
    # the guide's floors: the leading dense layers once, a whole period and four layers more; every expert, the whole vocabulary
    assert cfg["num_hidden_layers"] >= 1 + 4 + 4 and cfg["num_experts"] == 64 and cfg["vocab_size"] == 65536
    engine = cfg["deployment"]["engine"]
    assert engine["num_slots"] in (128, 64)  # ISSUE 54: 128 clients on 128 slots, or the stated fallback
    assert engine == dict(num_slots=engine["num_slots"], block_size=16, max_model_len=2048, num_blocks=engine["num_slots"] * 128 + 1, prefill_chunk=512)
    model = _part("config").model_config(cfg, 2048, "bfloat16")
    assert model["layer_kinds"] == ["conv", "full", "conv", "conv", "conv", "full", "conv", "conv", "conv"]
    assert (model["conv_cache"], model["full_layers_rope"], model["qk_norm"], model["first_dense_layers"]) == (3, True, True, 1)
    assert (model["num_experts"], model["experts_per_token"], model["d_expert"], model["d_ff"]) == (64, 4, 1536, 11776)
    assert (model["n_heads"], model["n_kv_heads"], model["head_dim"], model["d_model"], model["vocab_size"]) == (32, 8, 64, 2048, 65536)
    assert model["rope_theta"] == 1e6 and model["norm_eps"] == 1e-5 and model["routed_scaling_factor"] == 1 and model["tie_embeddings"] is True
    assert "embed_multiplier" not in model and "expert_share" not in model and "num_shared_experts" not in model
    check = cfg["check"]
    assert check["prompt_lens"] == [200, 700, 1400] and check["new_tokens"] == 128
    assert 0 < check["state_gap_tol"] < 0.1 and 0 < check["logit_gap_tol"] < 1.0
    for key in ("logit_gap_tol_why", "state_gap_tol_why"):
        assert "TWO READINGS" in check[key]
    assert "measured" in cfg["deployment"]["sizing"].lower() and "pipeline stages" in cfg["cut"]


def test_the_counts_against_hand_arithmetic(manifest):
    """ISSUE 54's arithmetic, layer by layer, and the model as published by the same count."""
    cfg, costs = _config(manifest), _part("costs")
    assert costs.conv_mixer_params(cfg) == 2048 * 6144 + 2048 * 2048 + 2048 * 3 == 16_783_360
    assert costs.attention_mixer_params(cfg) == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 == 10_485_888
    assert costs.expert_block_params(cfg) == 64 * 3 * 2048 * 1536 + 2048 * 64 + 64 == 604_110_912
    assert costs.dense_mlp_params(cfg) == 3 * 2048 * 11776 == 72_351_744
    dense, conv, attn = 16_783_360 + 72_351_744 + 4096, 16_783_360 + 604_110_912 + 4096, 10_485_888 + 604_110_912 + 4096
    assert (dense, conv, attn) == (89_139_200, 620_898_368, 614_600_896)
    assert costs.n_params(cfg) == dense + 6 * conv + 2 * attn + 65536 * 2048 + 2048 == 5_177_950_976
    assert 10.35e9 < costs.weight_bytes(cfg) < 10.37e9
    whole = dict(cfg, num_hidden_layers=40, num_dense_layers=2, layer_types=cfg["published"]["layer_types"])
    assert costs.n_params(whole) == 2 * dense + 28 * conv + 10 * attn + 65536 * 2048 + 2048 == 23_843_661_440  # 23.84B: the published 24B
    assert costs.kv_bytes_per_token(cfg) == 2 * 2 * 8 * 64 * 2 == 4096 and costs.kv_bytes_per_token(whole) == 20 * 1024
    assert costs.state_bytes_per_slot(cfg) == 7 * 2 * 2048 * 2 == 57_344
    assert costs.short_conv_step_bytes(cfg, 128) == 7 * 16_783_360 * 2 + 128 * 2 * 57_344
    assert costs.moe_experts_bytes(cfg, 64.0) == 8 * 64 * 3 * 2048 * 1536 * 2  # 9.66 GB: every expert of every layer
    assert costs.cache_attention_bytes(cfg, 100_000, 100_000) == 100_000 * 4096
    assert 63.9 < costs.expected_experts_touched(cfg, 128) < 64.0 and costs.expected_experts_touched(cfg, 1) == pytest.approx(4.0)
    step = costs.decode_step_bytes(cfg, 128 * 1024)
    assert 10.8e9 < step < 11.0e9  # 9.66 GB of experts, 0.56 of other matrices and the head, 0.54 of cache, the carried rows
    assert costs.moe_steps_alone(cfg, traced=True) == {"steps": 2 * 3 * 127 + 10, "experts_touched": 4, "fullest_expert_load": 1}


def test_the_counts_are_the_drawn_parameter_trees(manifest):
    """``costs.py`` against what the program draws and holds: leaf for leaf at
    a toy size, and by shape alone at the published widths."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import cache_token_bytes, state_slot_bytes
    from ray_tpu.models.transformer import TransformerConfig, init_params, num_params

    costs, config = _part("costs"), _part("config")
    for cfg in (toy_cell(manifest, CELL)["config"], _config(manifest)):
        model = config.model_config(cfg, 256, "bfloat16")
        model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        tc = TransformerConfig(**model)
        tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), tc))
        assert num_params(tree) == costs.n_params(cfg)
        assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)) == costs.weight_bytes(cfg)
        assert tree["conv_layers"]["wi_e"].shape == (6, cfg["num_experts"], cfg["hidden_size"], cfg["moe_intermediate_size"])
        assert tree["layers"]["wi_e"].shape[0] == 2 and tree["dense_layers"]["w_in"].shape == (1, cfg["hidden_size"], 3 * cfg["hidden_size"])
        assert state_slot_bytes(tc) == costs.state_bytes_per_slot(cfg)
        assert cache_token_bytes(tc) == {"full": costs.kv_bytes_per_token(cfg)}  # two heads a row or one: nothing padded


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cfg, config = _config(manifest), _part("config")
    for key, value in (("conv_bias", True), ("norm_topk_prob", False), ("use_expert_bias", False)):
        with pytest.raises(ValueError, match=f"{key} = .*the program computes"):
            config.model_config(dict(cfg, **{key: value}), 2048, "bfloat16")
    with pytest.raises(ValueError, match="rope_type = 'yarn'"):
        config.model_config(dict(cfg, rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), 2048, "bfloat16")
    with pytest.raises(ValueError, match="conv_L_cache = 1"):
        config.model_config(dict(cfg, conv_L_cache=1), 2048, "bfloat16")
    with pytest.raises(ValueError, match="of kinds the program has not: \\['sliding_attention'\\]"):
        config.model_config(dict(cfg, layer_types=["sliding_attention", *NINE[1:]]), 2048, "bfloat16")
    # The parent of PR 54: its TransformerConfig lacks the fields; refused in the driver process, by name.
    fields = config._program_fields()
    assert {"conv_cache", "full_layers_rope", "layer_kinds", "qk_norm"} <= fields
    monkeypatch.setattr(config, "_program_fields", lambda: fields - {"conv_cache", "full_layers_rope"})
    with pytest.raises(NotImplementedError, match="no conv_cache, full_layers_rope: it cannot run gated short-convolution"):
        config.model_config(cfg, 2048, "bfloat16")


def test_the_reference_imports_nothing_of_the_programs_kernels():
    with open(os.path.join(registry.BENCH_DIR, "architectures", ARCH, "reference.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines() if line.lstrip().startswith(("import ", "from "))]
    assert not any("ray_tpu.ops" in line or "ray_tpu.parallel" in line or "ray_tpu.models" in line for line in imports), imports


def test_the_mix_is_the_issues_and_fits_the_cell(manifest):
    cell = registry.load_cell(manifest, CELL)
    mix, engine = cell["traffic"], cell["config"]["deployment"]["engine"]
    assert mix["arrival"] == {"process": "closed", "clients": engine["num_slots"], "requests_per_client": 5}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert mix["output_len"] == {"dist": "uniform", "min": 768, "max": 1280}
    assert mix["sampling"] == {"sampled_share": 1.0, "temperature": 1.0, "top_k": 0}
    assert mix["stratified"] is True and mix["schedule_seed"] == 54 and "lengths_why" in mix
    assert (mix["preroll_s"], mix["grace_s"], mix["trace_slice_s"]) == (10.0, 5.0, 3.0)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1792 < engine["max_model_len"] == 2048
    assert mix["prompt_len"]["max"] == engine["prefill_chunk"]  # one chunk a request
    vocab = cell["config"]["vocab_size"]
    plans = [traffic.schedule(mix, seed, 51.0, vocab) for seed in (1, 2**31 + 5)]
    assert traffic.offered_tokens(plans[0]) == traffic.offered_tokens(plans[1])  # a pinned schedule: the same load
    for plan in plans:
        reqs = [r for client in plan["closed"] for r in client]
        assert len(plan["closed"]) == engine["num_slots"] and all(len(c) == 5 for c in plan["closed"])
        assert all(len(r["tokens"]) + r["max_new_tokens"] <= 1792 and max(r["tokens"]) < vocab for r in reqs)
        assert all(r["temperature"] == 1.0 and r["top_k"] == 0 for r in reqs)
    # no client runs out: a request is ~1024 steps of ~23 ms, ~25 s; five of them last 120 s, the run's 66


def test_the_new_entries_are_appended_behind_what_was_there(manifest):
    """Stands in for test_bench_setup_stages.py::test_benchmark_json_gains_exactly_the_eight_at_the_end_of_per_layer,
    which holds per_layer to END with PR 52's eight, the cells to be eleven and the serving six to eight cells, and is
    marked xfail (strict) in tests/conftest.py since PR 54 appends its two metrics behind the eight and its cell to
    the six's lists. The eight still stand together, in ISSUE 52's order, behind the 49 that were there, each as it
    was declared; PR 54's two come behind them; every list that names the new cell names it behind the cells that
    were there."""
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index(THE_EIGHT_OF_A_START[0])
    assert first == 49 and names[first : first + 8] == THE_EIGHT_OF_A_START and names[first - 2 : first] == ["latent_prefill_ms", "latent_prefill_roofline"]
    assert names[first + 8 : first + 10] == NEW_METRICS
    declared = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    serving = [w["name"] for w in manifest["workloads"][:BEFORE] if not w["name"].startswith(("train", "mellum"))]
    training = [c for c in cells[:BEFORE] if c not in serving]
    assert len(serving) == 8 and len(training) == 3
    for name in THE_EIGHT_OF_A_START:
        m = declared[name]
        assert (m["better"], m["moves"]) == ("lower", "setup_s")
        assert m["workloads"] == (training if name.startswith("trainer") else [*serving, CELL])
        assert os.path.isfile(os.path.join(registry.BENCH_DIR, "layer_metrics", name + ".py"))
    for name, unit, better in zip(NEW_METRICS, ("ms", "%"), ("lower", "higher")):
        assert declared[name] == dict(name=name, unit=unit, better=better, source="device_trace", layer="model",
                                      moves="itl_p95_ms", workloads=[CELL])
        assert os.path.isfile(os.path.join(registry.BENCH_DIR, "layer_metrics", name + ".py"))
    assert cells.index(CELL) == BEFORE and [c["name"] for c in manifest["configs"]].index(LFM) == 8
    entry = manifest["workloads"][BEFORE]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (LFM, MIX, 1) and len(entry["why"]) <= 200
    assert "128 clients on 128 slots" in entry["why"] or "64 clients on 64 slots" in entry["why"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed:  # appended to each list: behind every cell that was there before it
            assert all(listed.index(c) < listed.index(CELL) for c in listed if c in cells[:BEFORE]), m["name"]
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(CELL, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}  # as glm8.rollout-long: no ttft_p90_ms
    glm = want("glm8.rollout-long", True) - {"latent_attention_ms", "latent_attention_roofline"}
    assert want(CELL, True) == glm | CACHE_PAIR | {"prefill_pass_share_pct"} | set(NEW_METRICS)
    for name in ("linear_state_ms", "linear_state_roofline", "linear_scan_ms", "linear_scan_roofline", "moe_held_share_pct"):
        assert CELL not in declared[name]["workloads"]  # a carried row is no recurrent state; every expert is held
    for w in cells:
        assert bool(set(NEW_METRICS) & want(w, True)) == (w == CELL) and not set(NEW_METRICS) & want(w, False)
        assert not set(THE_EIGHT_OF_A_START) & want(w, False)


def test_trinitys_mix_is_still_the_issues(manifest):
    """Stands in for test_bench_nemotron_h.py::test_trinitys_mix_is_still_the_issues, which holds the cache pair to
    three cells and is marked xfail (strict) in tests/conftest.py since the two attention layers of PR 54's cut report
    the pair too. Everything else it holds is held here."""
    trinity, glm = "trinity5.rollout-longctx", "glm8.rollout-long"
    rollout = registry.load_cell(manifest, trinity)["traffic"]
    assert rollout["arrival"] == {"process": "closed", "clients": 32, "requests_per_client": 4}
    assert rollout["sampling"] == {"sampled_share": 1.0, "temperature": 1.0, "top_k": 0}
    assert rollout["stratified"] is True and rollout["schedule_seed"] == 35
    assert (rollout["grace_s"], rollout["trace_slice_s"]) == (5.0, 3.0) and 16.0 <= rollout["preroll_s"] <= 24.0
    lo, hi = rollout["prompt_len"]["min"], rollout["prompt_len"]["max"]
    assert rollout["prompt_len"]["dist"] == rollout["output_len"]["dist"] == "uniform"
    assert hi - lo == 512 and abs(lo - 3072) <= 512 and "lengths_why" in rollout
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(trinity, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert want(trinity, True) == (want(glm, True) - {"latent_attention_ms", "latent_attention_roofline"}) | CACHE_PAIR
    # the pair reads the views of full layers: Trinity's, Olmo-Hybrid's four, Nemotron's two blocks, and since PR 54 LFM2's two
    for w in manifest["workloads"][: BEFORE + 1]:
        assert bool(CACHE_PAIR & want(w["name"], True)) == (w["name"] in CACHE_PAIR_CELLS)
    for name in CACHE_PAIR:
        listed = next(m for m in manifest["per_layer"] if m["name"] == name)["workloads"]
        assert listed[:4] == CACHE_PAIR_CELLS
    entry = next(w for w in manifest["workloads"] if w["name"] == trinity)
    assert entry["chips"] == 1 and entry["config"] == "trinity-mini-serve5"


def _result(manifest, **over):
    """What the readers see of a traced run of the cell, by hand."""
    cell = registry.load_cell(manifest, CELL)
    cell["config"]["trace_ops"] = {
        "moe_experts": r"^%gmm\S* custom-call bf16\[512,", "cache_attention": r"bf16\[16384,16,4,128\]$",
        "short_conv": r"fusion bf16\[128,1,6144\]$|fusion bf16\[128,2,2048\]$",
    }
    fields = ["t_start_ns", "rows", "prefill_tokens", "context_tokens", "window_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 128 if i != 2 else 0, 512 if i == 4 else 0, 130_000, 130_000, 10**6] for i in range(6)]
    steps = 2000 + 772
    moe = {"steps": steps, "assignments": [[steps * 8] * 64] * 8, "experts_touched": [2000 * 63 + 772 * 4] * 8,
           "fullest_expert_load": [2000 * 16 + 772] * 8}
    result = {
        "cell": cell, "seconds": 51.0, "traced": True, "trace": None,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {"moe": {"decode": moe}, "running_polls": [128] * 50,
                     "spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}},
    }
    result["trace"] = {"devices": [{
        "programs": {"decode": [0.022] * 20, "prefill": [0.03] * 2},
        "ops": [["%gmm.24 custom-call bf16[512,1536]", 0.13], ["%gmm.25 custom-call bf16[512,2048]", 0.13],
                ["%gmm.27 custom-call bf16[2048,1536]", 0.5], ["%fusion.727 fusion bf16[16384,16,4,128]", 0.1],
                ["%fusion.669 fusion bf16[128,1,6144]", 0.006], ["%fusion.691 fusion bf16[128,2,2048]", 0.002],
                ["%fusion.9 fusion bf16[512,6144]", 0.5]],
    }]}
    result.update(over)
    return result


def test_the_readers_on_a_result_written_by_hand(manifest):
    """The new metrics, and the accepted ones whose readers take this
    architecture's ``costs.py``: a share of a roofline stays under 100."""
    result = _result(manifest)
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert read("short_conv_ms") == pytest.approx(0.4)  # the step's, not the chunk's bf16[512,6144]
    assert read("short_conv_roofline") == pytest.approx(100 * (7 * 16_783_360 * 2 + 128 * 2 * 57_344) / 819e9 / 0.0004)
    assert read("moe_experts_touched_mean") == pytest.approx(63.0)  # the check's 772 lone rows taken out at 4 experts each
    assert read("moe_experts_ms") == pytest.approx(13.0)  # the step's grouped matmuls, not the chunk's bf16[2048, ...]
    assert read("moe_experts_roofline") == pytest.approx(100 * 8 * 63 * 3 * 2048 * 1536 * 2 / 819e9 / 0.013)
    assert read("cache_attention_ms") == pytest.approx(5.0)
    assert read("cache_attention_roofline") == pytest.approx(100 * 130_000 * 4096 / 819e9 / 0.005)
    assert read("prefill_pass_share_pct") == pytest.approx(100 / 6)
    mix = result["cell"]["traffic"]
    context = int(128 * (traffic.mean_length(mix["prompt_len"]) + traffic.mean_length(mix["output_len"]) / 2))
    least = _part("costs").decode_step_bytes(result["cell"]["config"], context) / 819e9
    assert read("decode_mfu_roofline") == pytest.approx(100 * least / 0.022)
    for name in ("short_conv_roofline", "moe_experts_roofline", "cache_attention_roofline", "decode_mfu_roofline"):
        assert 0 < read(name) <= 100, name


@pytest.mark.parametrize("lacking", ["trace_ops", "spans", "another cell"])
def test_a_new_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """A configuration that names no such operations, a program without
    spans, and a cell whose architecture has no conv mixer to count."""
    result = _result(manifest)
    silent = set(NEW_METRICS)
    if lacking == "trace_ops":
        del result["cell"]["config"]["trace_ops"]["short_conv"]
    elif lacking == "spans":
        del result["counters"]["spans"]
        silent = {"short_conv_roofline"}
    else:
        glm = registry.load_cell(manifest, "glm8.rollout-long")
        glm["config"]["trace_ops"] = dict(glm["config"]["trace_ops"], short_conv=r"bf16\[128,1,6144\]$")
        result = dict(result, cell=glm)
        silent = {"short_conv_roofline"}  # its costs.py counts no conv mixer
    for name in NEW_METRICS:
        value = registry.load_metric("per_layer", name)(result)
        assert (value is None) == (name in silent), (name, value)


def test_the_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check
    against the architecture's own float32 reference through the carried rows,
    the experts taken and the cache, the mix, the line. The toy sizes state
    float32 activations over the bfloat16 weights, as the other state kinds'
    do, and heads 64 wide: two KV heads a cached row, as at the published widths."""
    cell = toy_cell(manifest, CELL)
    toy = cell["config"]
    assert toy["torch_dtype"] == "float32" and toy["deployment"]["param_dtype"] == "bfloat16"
    assert toy["hidden_size"] // toy["num_attention_heads"] == 64 and toy["layer_types"] == NINE
    toy["deployment"]["engine"]["prefill_chunk"] = 32
    cell["traffic"]["arrival"]["clients"] = 4  # the toy engine has 4 slots: as many clients as slots, as in the cell
    result = bench_run.measure(
        cell, seed=2**31 + 54, seconds=3.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    gaps = result["notes"]["reference_gaps"]
    assert len(gaps) == 2 and all(g["finite"] and g["max_gap"] <= toy["check"]["logit_gap_tol"] for g in gaps)
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, CELL, traced=False, platform="cpu")
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    counters, costs = result["counters"], _part("costs")
    assert counters["kv_pool_not_donated"] == 0 and counters["host_logit_rows"] == 0
    assert counters["decode_steps_with_chunk"] == 0 and counters["decode_steps"] > 0
    groups = counters["kv_groups"]
    assert set(groups) == {"full", "state"}
    # float32 activations at the toy size: four bytes a cached value and a carried row's
    assert groups["full"]["kv_token_bytes"] == costs.kv_bytes_per_token(toy, itemsize=4) == counters["kv_token_bytes"]
    assert groups["state"] == dict(kind="conv", bytes_per_slot=costs.state_bytes_per_slot(toy, itemsize=4), num_slots=4,
                                   slots_in_use=groups["state"]["slots_in_use"])
    assert counters["state_resets"] == counters["admitted"] > 0
    assert (counters["prefix_hit_blocks"], counters["prefix_miss_blocks"], counters["cached_blocks"]) == (0, 0, 0)
    moe = counters["moe"]["decode"]
    assert len(moe["assignments"]) == 8 and len(moe["assignments"][0]) == toy["num_experts"]  # eight expert layers, two stacks
    assert registry.load_metric("per_layer", "prefill_pass_share_pct")(result) > 0.0
