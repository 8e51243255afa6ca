"""The sixth architecture of the benchmark (PR 47), ``Xing4_0ForCausalLM``
(Xing4.0-29B-A4B: a residual path of four streams mixed by manifold-constrained
hyper-connections round latent attention at YaRN-scaled positions and sigmoid
top-4-of-64 experts), and the cell PR 47 adds: the configuration against the
catalog's row, the cut and the counts against hand arithmetic and against the
parameter tree the program draws, the mix, the cell at a toy size through
``run.measure`` on the CPU, the two new per-layer metrics' readers and the
accepted ones that read this architecture's ``costs.py``, and what stands in
for the tests of ``tests/benchmark/`` that a seventh configuration, a 12k-token
mix and appended lists made wrong (``tests/conftest.py`` marks those). Nothing
here pins the END of a list that a later PR may append to."""

import json
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic

XING, CELL, MIX = "xing4.0-29b-a4b-serve6", "xing6.longdoc-12k", "longdoc-12k"
ARCH = "Xing4_0ForCausalLM"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "Xing4.0-29B-A4B"
REDUCED = ["num_hidden_layers", "first_k_dense_replace"]
NEMO_CELL, OLMO_CELL, GLM_CELL = "nemo14.chat-churn", "olmo16.longdoc-8k", "glm8.rollout-long"
# ISSUE 47 asked for two more, hyper_connection_ms / _roofline: the decode step's mixing and joining are 67 operations
# of 1-3 us, none among the forty longest of a slice that harness/trace.py keeps, so neither was entered (PERF.md section 5).
NEW_METRICS = {
    "latent_prefill_ms": ("ms", "lower", "itl_p95_ms"),
    "latent_prefill_roofline": ("%", "higher", "itl_p95_ms"),
}
# The cell reports itl_p95_ms, serve_tokens_per_s and setup_s: ttft_p90_ms spread by 6.7 / 6.4 % in the builder's two sets
# of six (half its bound: 5), ISSUE 47's fallback, as nemo14.chat-churn; so the cell is declared for none of the
# per-layer metrics that move it (the two new ones move itl_p95_ms here: in a closed loop whose passes carry a chunk,
# the gap between two tokens of a stream is a chunk and a step).
MOVE_WHAT_IT_DOES_NOT_REPORT = {
    "serve_path_overhead_ms", "prefill_chunk_ms", "serve_ingress_p90_ms", "queue_wait_p90_ms", "prefill_span_p90_ms",
}
# The accepted per-layer metrics this cell does not report: training's, those of a cache of keys and values, and
# those of layers that keep a state.
NOT_THIS_CELLS = {
    "trainer_first_step_s", "train_step_ms", "mfu_pct", "train_host_gap_ms", "collective_exposed_ms",
    "device_idle_pct.train", "cache_attention_ms", "cache_attention_roofline", "linear_state_ms",
    "linear_state_roofline", "linear_scan_ms", "linear_scan_roofline", "moe_held_share_pct",
}


def _config(manifest):
    return registry.load_cell(manifest, CELL)["config"]


def _part(part):
    return registry.load_architecture({"name": "these tests", "architecture": ARCH, "bench_dir": registry.BENCH_DIR}, part)


def test_the_configuration_holds_the_catalogs_row(manifest):
    """Every number of the catalog row's ``config`` under the same key, but the
    two that the cut changes, which ``reduced`` and ``published`` both name."""
    cfg = _config(manifest)
    entry = next(c for c in manifest["configs"] if c["name"] == XING)
    assert cfg["reduced"] == entry["reduced"] == REDUCED and cfg["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2}
    assert cfg["architectures"] == [ARCH] and cfg["path"] == "serve" and cfg["torch_dtype"] == "bfloat16"
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (6, 1) and len(entry["why"]) <= 200
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
    for key in ("the stream's start and end", "hc_eps and rms_norm_eps", "Sinkhorn", "phi's columns", "rotary layout", "YaRN", "weights"):
        assert key in cfg["assumed"], key
    assert "multi-token-prediction" in cfg["left_out"] and "Nothing of it is built" in cfg["left_out"]


def test_the_cut_is_depth_alone_and_the_engine_is_the_issues(manifest):
    cfg = _config(manifest)
    assert cfg["n_routed_experts"] == 64 and cfg["num_experts_per_tok"] == 4 and cfg["vocab_size"] == 131072
    assert cfg["hc_mult"] == 4 and cfg["rope_scaling"]["factor"] == 64 and cfg["max_position_embeddings"] == 262144
    engine = cfg["deployment"]["engine"]
    assert engine == dict(num_slots=8, block_size=16, max_model_len=12288, num_blocks=8 * 768 + 1, prefill_chunk=512)
    model = _part("config").model_config(cfg, engine["max_model_len"], "bfloat16")
    assert (model["hc_mult"], model["hc_sinkhorn_iters"], model["hc_eps"], model["hc_res_clamp"]) == (4, 20, 1e-6, [-30.0, 30.0])
    assert model["rope_scaling"] == dict(factor=64.0, original_max_position_embeddings=4096.0, beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    assert (model["n_layers"], model["first_dense_layers"], model["num_experts"], model["experts_per_token"]) == (6, 1, 64, 4)
    assert (model["d_model"], model["n_heads"], model["q_lora_rank"], model["kv_lora_rank"]) == (3584, 32, 768, 512)
    assert (model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"], model["d_expert"], model["d_ff"]) == (128, 64, 128, 1024, 9216)
    assert model["routed_scaling_factor"] == 2.0 and model["norm_eps"] == 1e-6 and model["rope_theta"] == 10000.0
    check = cfg["check"]
    # one padded chunk, three, twenty: inside the mix's 8192-11776 (the lengths the window reads), past YaRN's 4096
    assert check["prompt_lens"] == [300, 1400, 10000] and check["new_tokens"] == 128
    mix = registry.load_cell(manifest, CELL)["traffic"]
    assert mix["prompt_len"]["min"] <= check["prompt_lens"][-1] <= mix["prompt_len"]["max"]
    # between the served system's largest reading in 30 runs and the 3-mantissa-bit control's lowest in 12 (PERF.md section 6)
    assert 0.0434 < check["logit_gap_tol"] < 0.0715 and "TWO READINGS" in check["logit_gap_tol_why"]
    assert "measured" in cfg["deployment"]["sizing"].lower() and "5 of the 38 expert layers" in cfg["cut"]


def test_the_counts_against_hand_arithmetic(manifest):
    """ISSUE 47's arithmetic, part by part."""
    cfg, costs = _config(manifest), _part("costs")
    assert costs.attention_params(cfg) == 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584 == 28_409_856
    assert costs.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert costs.hyper_connection_params(cfg) == 14336 * 24 + 24 + 3 == 344_091
    norms = 2 * 3584 + 768 + 512
    dense = 28_409_856 + 3 * 3584 * 9216 + 2 * 344_091 + norms
    expert = 28_409_856 + 65 * 11_010_048 + 3584 * 64 + 64 + 2 * 344_091 + norms
    assert round(dense / 1e6, 1) == 128.2 and round(expert / 1e6, 1) == 745.0
    assert costs.n_params(cfg) == dense + 5 * expert + 2 * 131072 * 3584 + 3584 == 4_792_669_828
    whole = dict(cfg, num_hidden_layers=40, first_k_dense_replace=2)
    assert round(costs.n_params(whole) / 1e9, 1) == 29.5  # the model as published
    float32 = 5 * 64 * 3585 + 12 * 344_091  # the routers and the hyper-connections
    assert costs.weight_bytes(cfg) == 2 * 4_792_669_828 + 2 * float32 and 9.59e9 < costs.weight_bytes(cfg) < 9.60e9
    assert costs.kv_bytes_per_token(cfg) == 6 * 576 * 2 == 6912  # as held: 6 x 640 x 2 = 7680 (the sizing says so)
    assert costs.latent_attention_bytes(cfg, 70_000) == 70_000 * 6912
    assert costs.moe_experts_bytes(cfg, 25.0) == 5 * 25.0 * 11_010_048 * 2
    assert 25.7 < costs.expected_experts_touched(cfg, 8) < 25.9 and costs.expected_experts_touched(cfg, 1) == pytest.approx(4.0)
    assert costs.hyper_connection_bytes(cfg, 8) == 12 * (2 * 8 * 14336 * 2 + 14336 * 24 * 4)
    # a step beside the prefill lane: of 8 held slots (what the reader's context counts) 6.6 rows step
    rows = cfg["deployment"]["stepping_rows"]
    assert 6.0 < rows < cfg["deployment"]["engine"]["num_slots"] - 1 and "decode_rows_mean" in cfg["deployment"]["stepping_rows_why"]
    touched = costs.expected_experts_touched(cfg, rows)
    assert 22.0 < touched < 22.6
    shared = 2 * (28_409_856 + 3 * 3584 * 9216 + 5 * (28_409_856 + 11_010_048 + 3584 * 64) + 3584 * 131072)
    step = costs.decode_step_bytes(cfg, 8 * 10_000)
    assert step == int(shared + costs.moe_experts_bytes(cfg, touched) + 80_000 * rows / 8 * 6912 + costs.hyper_connection_bytes(cfg, 8))
    assert 4.4e9 < step < 4.7e9  # ~2.5 GB of touched experts, ~1.6 GB of other matrices, 0.46 GB of latents, 22 MB of streams
    # a chunk of 512 behind 5000 tokens: the expanded form is the cheaper (0.1 TFLOP a layer for the absorbed 0.19)
    pairs = 512 * 5000 + 512 * 513 / 2
    expanded = 2 * 32 * (pairs * 320 + 5512 * 512 * 256)
    assert costs.latent_prefill_flops(cfg, 512, 5000) == 6 * expanded < 6 * 2 * 32 * (pairs * 1088 + 512 * 512 * 256)
    assert costs.latent_prefill_flops(cfg, 512, 0) == 6 * 2 * 32 * (512 * 513 / 2 * 320 + 512 * 512 * 256)
    assert costs.latent_prefill_bytes(cfg, 512, 5000) == 6 * 2 * (5512 * 576 + 512 * 32 * 320 + 512 * 32 * 256)
    assert costs.moe_steps_alone(cfg, traced=True) == {"steps": 2 * 3 * 127 + 10, "experts_touched": 4, "fullest_expert_load": 1}


def test_the_counts_are_the_drawn_parameter_trees(manifest):
    """``costs.py`` against what the program draws and holds: leaf for leaf at
    a toy size, and by shape alone at the published widths."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import cache_token_bytes
    from ray_tpu.models.transformer import TransformerConfig, init_params, num_params

    costs, config = _part("costs"), _part("config")
    for cfg in (toy_cell(manifest, CELL)["config"], _config(manifest)):
        model = config.model_config(cfg, 256, "bfloat16")
        model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        tc = TransformerConfig(**model)
        tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), tc))
        assert num_params(tree) == costs.n_params(cfg)
        assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)) == costs.weight_bytes(cfg)
        n, D = cfg["hc_mult"], cfg["hidden_size"]
        for stack in ("dense_layers", "layers"):
            for sub in ("attn", "mlp"):
                assert tree[stack][f"hc_{sub}_phi"].shape[1:] == (2 * n + n * n, n * D) and tree[stack][f"hc_{sub}_phi"].dtype == jnp.float32
                assert tree[stack][f"hc_{sub}_b"].shape[1:] == (2 * n + n * n,) and tree[stack][f"hc_{sub}_alpha"].shape[1:] == (3,)
        held = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128  # padded to the lanes
        assert cache_token_bytes(tc) == {"full": cfg["num_hidden_layers"] * held * 2} and held * 2 * cfg["num_hidden_layers"] >= costs.kv_bytes_per_token(cfg)


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cfg, config = _config(manifest), _part("config")
    for key, value in (("n_group", 2), ("norm_topk_prob", False), ("scoring_func", "softmax"), ("moe_layer_freq", 2), ("attention_bias", True)):
        with pytest.raises(ValueError, match=f"{key} = .*the program computes"):
            config.model_config(dict(cfg, **{key: value}), 12288, "bfloat16")
    with pytest.raises(ValueError, match="rope_scaling = .*type 'yarn'"):
        config.model_config(dict(cfg, rope_scaling=dict(cfg["rope_scaling"], type="linear")), 12288, "bfloat16")
    # The parent of PR 47: its TransformerConfig lacks the fields; refused in the driver process, by name.
    fields = config._program_fields()
    assert {"hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp", "rope_scaling"} <= fields
    monkeypatch.setattr(config, "_program_fields", lambda: fields - {"hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp", "rope_scaling"})
    with pytest.raises(NotImplementedError, match="no hc_eps, hc_mult, hc_res_clamp, hc_sinkhorn_iters, rope_scaling: it cannot run"):
        config.model_config(cfg, 12288, "bfloat16")


def test_the_mix_is_the_issues_and_fits_the_cell(manifest):
    """Stands in for test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[longdoc-12k], which holds every
    serving mix under 2560 tokens a request and is marked xfail (strict) in tests/conftest.py: the mix under its own
    cell's limit, and two seeds' equal load."""
    cell = registry.load_cell(manifest, CELL)
    mix, engine = cell["traffic"], cell["config"]["deployment"]["engine"]
    assert mix["arrival"] == {"process": "closed", "clients": 8, "requests_per_client": 16} and engine["num_slots"] == 8
    assert mix["prompt_len"] == {"dist": "uniform", "min": 8192, "max": 11776}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 192}
    assert mix["sampling"] == {"sampled_share": 0.0, "temperature": 0.0, "top_k": 0}
    assert mix["stratified"] is True and mix["schedule_seed"] == 47 and (mix["preroll_s"], mix["grace_s"]) == (15.0, 5.0)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 11968 < engine["max_model_len"] == 12288
    assert mix["prompt_len"]["min"] == 16 * engine["prefill_chunk"] and mix["prompt_len"]["max"] == 23 * engine["prefill_chunk"]
    assert mix["prompt_len"]["min"] > cell["config"]["rope_scaling"]["original_max_position_embeddings"]  # every request past YaRN's original range
    vocab = cell["config"]["vocab_size"]
    plans = [traffic.schedule(mix, seed, 51.0, vocab) for seed in (1, 2**31 + 5)]
    assert traffic.offered_tokens(plans[0]) == traffic.offered_tokens(plans[1])  # a pinned schedule: the same load
    for plan in plans:
        reqs = [r for client in plan["closed"] for r in client]
        assert len(plan["closed"]) == 8 and all(len(c) == 16 for c in plan["closed"])
        assert all(len(r["tokens"]) + r["max_new_tokens"] <= 11968 and max(r["tokens"]) < vocab for r in reqs)
        assert all(r["temperature"] == 0.0 for r in reqs)
    for w in manifest["workloads"]:  # every serving mix fits the configuration that runs it
        other = registry.load_cell(manifest, w["name"])
        if other["config"]["path"] == "serve":
            longest = other["traffic"]["prompt_len"]["max"] + other["traffic"]["output_len"]["max"]
            assert longest <= other["config"]["deployment"]["engine"]["max_model_len"] or "sessions" in other["traffic"], w["name"]


def test_the_new_entries_are_appended_behind_what_was_there(manifest):
    """Stands in for test_bench_nemotron_h.py::test_the_new_entries_are_appended_behind_what_was_there, which holds
    ``prefill_pass_share_pct`` to Nemotron's cell alone and is marked xfail (strict) in tests/conftest.py since PR 47
    appends its cell to that list. PR 43's two still stand behind Olmo-Hybrid's four, PR 47's two behind those;
    cells and configurations in the order they came; every list that names the new cell names it last of the cells
    that were there."""
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index("linear_state_ms")
    assert names[first : first + 6] == ["linear_state_ms", "linear_state_roofline", "linear_scan_ms", "linear_scan_roofline",
                                        "moe_held_share_pct", "prefill_pass_share_pct"]
    assert names[first + 6 : first + 8] == list(NEW_METRICS)  # appended, in ISSUE 47's order
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index(NEMO_CELL) + 1 == 9
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(XING) == configs.index("nemotron-3-nano-30b-a3b-serve14") + 1 == 6
    entry = manifest["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (XING, MIX, 1) and len(entry["why"]) <= 200
    assert "hyper-connections few %" in entry["why"] and "12288-wide view" in entry["why"]
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better, moves) in NEW_METRICS.items():
        assert declared[name] == dict(name=name, unit=unit, better=better, source="device_trace", layer="model",
                                      moves=moves, workloads=[CELL])  # ISSUE 47 had them move ttft_p90_ms, which the cell does not report
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(CELL, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    moved = {m["name"] for m in manifest["per_layer"][: first + 6] if m["moves"] == "ttft_p90_ms"}
    assert MOVE_WHAT_IT_DOES_NOT_REPORT <= moved and not any(CELL in m.get("workloads", ()) for m in manifest["per_layer"] if m["name"] in moved)
    accepted = set(names[: first + 6]) - NOT_THIS_CELLS - moved
    assert want(CELL, True) == want(CELL, False) | accepted | set(NEW_METRICS)
    # every accepted metric of Olmo-Hybrid's cell or GLM's that reads no cache of keys and values and no state
    for m in manifest["per_layer"][: first + 6]:
        listed = m.get("workloads", ())
        theirs = (OLMO_CELL in listed or GLM_CELL in listed) and not m["name"].startswith(("cache_attention", "linear_")) and m["moves"] in ("itl_p95_ms", "serve_tokens_per_s") or m["moves"] == "setup_s" and GLM_CELL in listed
        assert (CELL in listed) == (theirs or m["name"] == "prefill_pass_share_pct"), m["name"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed:  # appended to each list: behind every cell that was there before it
            assert all(listed.index(c) < listed.index(CELL) for c in listed if c in cells[:9]), m["name"]
    assert declared["prefill_pass_share_pct"]["workloads"][:2] == [NEMO_CELL, CELL]
    assert declared["moe_held_share_pct"]["workloads"] == [NEMO_CELL]
    for w in manifest["workloads"]:
        assert bool(set(NEW_METRICS) & want(w["name"], True)) == (w["name"] == CELL)
        assert ("latent_attention_ms" in want(w["name"], True)) == (w["name"] in (GLM_CELL, CELL))


def _result(manifest, **over):
    """What the readers see of a traced run of the cell, by hand."""
    cell = registry.load_cell(manifest, CELL)
    cell["config"]["trace_ops"] = {
        "moe_experts": r"^%ragged-dot-none\S* custom-call bf16\[32,", "latent_attention": r"bf16\[8,32,1,640\]",
        "latent_prefill": r"f32\[1,32,512,12288\]",
    }
    fields = ["t_start_ns", "rows", "prefill_tokens", "context_tokens", "chunk_context_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 7, 512 if i % 3 else 0, 70_000, 4000 + 512 * i if i % 3 else 0, 10**6] for i in range(6)]
    steps = 2000 + 772
    moe = {"steps": steps, "assignments": [[steps * 2] * 64] * 5, "experts_touched": [2000 * 22 + 772 * 4] * 5,
           "fullest_expert_load": [2000 * 3 + 772] * 5}
    result = {
        "cell": cell, "seconds": 51.0, "traced": True, "trace": None,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {"moe": {"decode": moe}, "running_polls": [7] * 50,
                     "spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}},
    }
    result["trace"] = {"devices": [{
        "programs": {"decode": [0.01] * 20, "prefill": [0.08] * 10},
        "ops": [["%ragged-dot-none.3 custom-call bf16[32,1024]", 0.12], ["%custom-call.5 custom-call bf16[8,32,1,640]", 0.02],
                ["%fusion.71 fusion f32[1,32,512,12288]", 0.5], ["%pad_maximum_fusion.6 fusion bf16[8,1,14336]", 0.004],
                ["%fusion.1604 fusion f32[24,8]", 0.002], ["%fusion.9 fusion bf16[8,3584]", 0.5]],
    }]}
    result.update(over)
    return result


def test_the_readers_on_a_result_written_by_hand(manifest):
    """The new metrics, and the accepted ones whose readers take this
    architecture's ``costs.py``: a share of a roofline stays under 100."""
    result = _result(manifest)
    costs, cfg = _part("costs"), result["cell"]["config"]
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert read("latent_prefill_ms") == pytest.approx(50.0)  # 0.5 s over ten chunks
    context = (4000 + 512 + 4000 + 1024 + 4000 + 2048 + 4000 + 2560) / 4  # the four passes with a chunk
    least = costs.latent_prefill_flops(cfg, 512, context) / 197e12
    assert least > costs.latent_prefill_bytes(cfg, 512, context) / 819e9  # operations bound it
    assert read("latent_prefill_roofline") == pytest.approx(100 * least / 0.050)
    assert read("prefill_pass_share_pct") == pytest.approx(100 * 4 / 6)
    assert read("moe_experts_touched_mean") == pytest.approx(22.0)  # the check's 772 lone rows taken out at 4 experts each
    assert read("moe_experts_ms") == pytest.approx(6.0)
    assert read("moe_experts_roofline") == pytest.approx(100 * 5 * 22 * 11_010_048 * 2 / 819e9 / 0.006)
    assert read("latent_attention_ms") == pytest.approx(1.0)
    assert read("latent_attention_roofline") == pytest.approx(100 * 70_000 * 6912 / 819e9 / 0.001)
    assert read("prefill_chunk_ms") == pytest.approx(80.0) and read("decode_step_ms") == pytest.approx(10.0)
    mix = result["cell"]["traffic"]
    held = int(7 * (traffic.mean_length(mix["prompt_len"]) + traffic.mean_length(mix["output_len"]) / 2))
    assert read("decode_mfu_roofline") == pytest.approx(100 * costs.decode_step_bytes(cfg, held) / 819e9 / 0.010)
    for name in ("latent_prefill_roofline", "moe_experts_roofline", "latent_attention_roofline", "decode_mfu_roofline"):
        assert 0 < read(name) <= 100, name


@pytest.mark.parametrize("lacking", ["chunk_context_tokens", "trace_ops", "spans", "no prefill in the slice", "another cell"])
def test_a_new_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """The parent of PR 47 under PR 47's benchmark files (its records know no
    ``chunk_context_tokens``, and no cell of its own names the new operations), a
    program without spans, a slice without a chunk, and a cell of another architecture."""
    result = _result(manifest)
    silent = set()
    if lacking == "chunk_context_tokens":
        spans = result["counters"]["spans"]
        at = spans["fields"]["iterations"].index("chunk_context_tokens")
        width = len(spans["fields"]["iterations"])
        spans["iterations"] = [x for i, x in enumerate(spans["iterations"]) if i % width != at]
        spans["fields"]["iterations"].remove("chunk_context_tokens")
        silent = {"latent_prefill_roofline"}
    elif lacking == "trace_ops":
        del result["cell"]["config"]["trace_ops"]
        silent = set(NEW_METRICS)
    elif lacking == "spans":
        del result["counters"]["spans"]
        silent = {"latent_prefill_roofline"}
    elif lacking == "no prefill in the slice":
        del result["trace"]["devices"][0]["programs"]["prefill"]
        silent = {"latent_prefill_ms", "latent_prefill_roofline"}
    else:
        result = dict(result, cell=registry.load_cell(manifest, GLM_CELL))
        silent = set(NEW_METRICS)
    for name in NEW_METRICS:
        value = registry.load_metric("per_layer", name)(result)
        assert (value is None) == (name in silent), (name, value)


def test_the_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check
    against the architecture's own float32 reference under the system's
    routing, the mix, the line. The toy sizes state float32 activations over the
    bfloat16 weights, as Olmo-Hybrid's and Nemotron's do."""
    cell = toy_cell(manifest, CELL)
    cell["config"]["torch_dtype"] = "float32"
    cell["config"]["deployment"]["engine"]["prefill_chunk"] = 32
    cell["traffic"]["arrival"]["clients"] = 4  # the toy engine has 4 slots: as many clients as slots, as in the cell
    result = bench_run.measure(
        cell, seed=2**31 + 47, seconds=3.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    gaps = result["notes"]["reference_gaps"]
    assert len(gaps) == 2 and all(g["finite"] and g["max_gap"] <= cell["config"]["check"]["logit_gap_tol"] for g in gaps)
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, CELL, traced=False, platform="cpu")
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    counters = result["counters"]
    assert counters["kv_pool_not_donated"] == 0 and counters["host_logit_rows"] == 0
    assert counters["residual_streams"] == 4 and counters["latent_softmax_scale"] == pytest.approx(40**-0.5 * 2.00474, rel=1e-5)
    assert counters["decode_steps_with_chunk"] == 0 and counters["decode_steps"] > 0
    assert set(counters["kv_groups"]) == {"full"} and len(counters["moe"]["decode"]["assignments"]) == 2
    fields = counters["spans"]["fields"]["iterations"]
    assert "chunk_context_tokens" in fields and fields.index("chunk_context_tokens") == fields.index("chunk_tokens") + 1
    passes = registry.load_metric("per_layer", "prefill_pass_share_pct")(result)
    assert 0.0 < passes < 100.0
