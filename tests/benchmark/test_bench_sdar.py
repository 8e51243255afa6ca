"""The ninth architecture of the benchmark (PR 56), ``SDARMoeForCausalLM``
(SDAR-30B-A3B-Chat: QK-normed GQA and softmax top-8-of-128 experts, GENERATED by
diffusion over blocks of four positions), and the cell PR 56 adds,
``sdar6.rollout-block``: the configuration against the catalog's row, the cut and
the counts against hand arithmetic and against the parameter tree the program
draws, the cell at a toy size through ``run.measure`` on the CPU, the three new
per-layer metrics' readers and the accepted ones that read this architecture's
``costs.py``, and what stands in for the tests of ``tests/benchmark/`` that a
tenth configuration's appended entries made wrong (``tests/conftest.py`` marks
those). Nothing here pins the END of a list that a later PR may append to: a
new entry is held to come AFTER the ones it was appended behind."""

import json
import os
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic
from benchmarks.harness.spans import decode_iterations

SDAR, CELL, MIX, ARCH = "sdar-30b-a3b-chat-serve6", "sdar6.rollout-block", "rollout-block", "SDARMoeForCausalLM"
LFM_CELL = "lfm9.rollout-wide"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "SDAR-30B-A3B-Chat"
NEW_METRICS = ["block_tokens_per_pass", "block_commit_share_pct", "block_draw_ms"]
SHORT_CONV = ["short_conv_ms", "short_conv_roofline"]
THE_EIGHT_OF_A_START = [
    "replica_spawn_s", "replica_backend_s", "programs_python_s", "programs_python_waiting_pct", "programs_compile_s",
    "programs_compiled_afresh", "trainer_spawn_s", "trainer_backend_s",
]
CACHE_PAIR = {"cache_attention_ms", "cache_attention_roofline"}
EXPERTS_ON_THE_DEVICE = {"moe_experts_ms", "moe_experts_roofline"}
CACHE_PAIR_CELLS = ["trinity5.rollout-longctx", "olmo16.longdoc-8k", "nemo14.chat-churn", LFM_CELL, CELL]
BEFORE = 12  # cells the benchmark had


def _config(manifest):
    return registry.load_cell(manifest, CELL)["config"]


def _part(part):
    return registry.load_architecture({"name": "these tests", "architecture": ARCH, "bench_dir": registry.BENCH_DIR}, part)


def test_the_configuration_holds_the_catalogs_row(manifest):
    """Every key of the catalog row's ``config`` under the same key, but the one
    that the cut changes, which ``reduced`` and ``published`` both name."""
    cfg = _config(manifest)
    entry = next(c for c in manifest["configs"] if c["name"] == SDAR)
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["architectures"] == [ARCH] and cfg["path"] == "serve" and cfg["torch_dtype"] == "bfloat16"
    assert entry["file"] == f"benchmarks/configs/{SDAR}.json" and len(entry["why"]) <= 200
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (6, 128, 151936)
    assert (cfg["block_length"], cfg["mask_token_id"]) == (4, 151669)
    for key in ("architectures", "block_length", "mask_token_id", "torch_dtype", "denoising_steps", "logits unshifted"):
        assert key in cfg["assumed"], key  # what the row does not carry is said to be assumed
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    except OSError:
        pytest.skip("no catalog beside the guides here")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == (6 if key == "num_hidden_layers" else value), key
    assert row["not_given"] == ["block length", "noise schedule"]  # hence ``assumed``


def test_the_counts_against_hand_arithmetic(manifest):
    cfg, costs = _config(manifest), _part("costs")
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    experts = 128 * 3 * 2048 * 768
    layer = attention + 256 + 2048 * 128 + experts + 2 * 2048
    assert (attention, experts, layer) == (18_874_368, 603_979_776, 623_120_640) and costs.layer_params(cfg) == layer
    assert costs.n_params(cfg) == 6 * layer + 2 * 151936 * 2048 + 2048 == 4_361_055_744
    assert costs.n_params(dict(cfg, num_hidden_layers=48)) == 30_532_122_624  # its 30B
    assert costs.weight_bytes(cfg) == 2 * 4_361_055_744 + 2 * 6 * 2048 * 128  # the router in float32
    assert costs.kv_bytes_per_token(cfg) == 6 * 2 * 4 * 128 * 2 == 12_288
    assert costs.kv_bytes_per_token(dict(cfg, num_hidden_layers=48)) == 98_304
    engine = cfg["deployment"]["engine"]
    assert engine == dict(num_slots=128, block_size=16, max_model_len=2048, num_blocks=16385, prefill_chunk=512,
                          denoising_steps=2)
    assert engine["num_blocks"] * 16 * 12_288 == 3_221_422_080  # the pool: 3.22 GB
    assert engine["block_size"] % cfg["block_length"] == 0 == engine["prefill_chunk"] % cfg["block_length"]
    # A pass touches every expert of every layer: 512 positions x 8 of 128.
    assert costs.expected_experts_touched(cfg, 512) > 127.99
    assert costs.moe_experts_bytes(cfg, 128) == 6 * experts * 2
    assert costs.cache_attention_bytes(cfg, 1000.0, 123.0) == 1000 * 12_288
    shared = 6 * attention * 2 + 2048 * 151936 * 2 + 6 * 2048 * 128 * 4
    assert costs.decode_step_bytes(cfg, 130_000) == int(shared + costs.moe_experts_bytes(cfg, costs.expected_experts_touched(cfg, 512)) + 130_000 * 12_288)
    alone = costs.moe_steps_alone(cfg, traced=True)
    assert alone == {"steps": 2 * 3 * 32 * 3 + 2 * 5 * 3, "experts_touched": 12, "fullest_expert_load": 2}


def test_the_counts_are_the_drawn_parameter_trees(manifest):
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg, costs = _config(manifest), _part("costs")
    model = _part("config").model_config(cfg, 2048, "bfloat16")
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TransformerConfig(**model)))
    leaves = jax.tree.leaves(tree)
    assert sum(leaf.size for leaf in leaves) == costs.n_params(cfg)
    assert sum(leaf.size * leaf.dtype.itemsize for leaf in leaves) == costs.weight_bytes(cfg)
    assert tree["layers"]["gate"].dtype == "float32" and "gate_bias" not in tree["layers"] and "lm_head" in tree
    assert tree["layers"]["wg_e"].shape == (6, 128, 2048, 768) and tree["layers"]["q_norm"].shape == (6, 128)


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cfg, config = _config(manifest), _part("config")
    assert config.model_config(cfg, 2048, "bfloat16")["block_diffusion"] == 4
    for key, value in (("use_sliding_window", True), ("norm_topk_prob", False), ("mlp_only_layers", [0]),
                       ("decoder_sparse_step", 2), ("tie_word_embeddings", True), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            config.model_config(dict(cfg, **{key: value}), 2048, "bfloat16")
    with pytest.raises(ValueError, match="mask_token_id"):
        config.model_config(dict(cfg, mask_token_id=151936), 2048, "bfloat16")
    # A program from before generation by diffusion over blocks: refused in the driver process, by the fields' names.
    monkeypatch.setattr(config, "_program_fields", lambda: {"vocab_size"})
    with pytest.raises(NotImplementedError, match="block_diffusion"):
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
    assert mix["stratified"] is True and mix["schedule_seed"] == 56 and "lengths_why" in mix
    assert (mix["preroll_s"], mix["grace_s"], mix["trace_slice_s"]) == (10.0, 5.0, 3.0)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1792 < engine["max_model_len"] == 2048
    assert mix["prompt_len"]["max"] == engine["prefill_chunk"]  # one chunk a request
    lfm = registry.load_cell(manifest, LFM_CELL)["traffic"]  # LFM2's lengths on purpose: the same mix beside an autoregressive expert model
    assert all(mix[key] == lfm[key] for key in ("arrival", "prompt_len", "output_len", "sampling", "preroll_s"))
    vocab = cell["config"]["vocab_size"]
    plans = [traffic.schedule(mix, seed, 51.0, vocab) for seed in (1, 2**31 + 5)]
    assert traffic.offered_tokens(plans[0]) == traffic.offered_tokens(plans[1])  # a pinned schedule: the same load
    for plan in plans:
        reqs = [r for client in plan["closed"] for r in client]
        assert len(plan["closed"]) == engine["num_slots"] and all(len(c) == 5 for c in plan["closed"])
        assert all(len(r["tokens"]) + r["max_new_tokens"] <= 1792 and max(r["tokens"]) < vocab for r in reqs)
        assert all(r["temperature"] == 1.0 and r["top_k"] == 0 for r in reqs)
        # Neither prompts nor outputs are multiples of 4: partial first and last blocks are on the timed path.
        assert any(len(r["tokens"]) % 4 for r in reqs) and any(r["max_new_tokens"] % 4 for r in reqs)
    check = cell["config"]["check"]
    assert check["prompt_lens"] == [202, 700, 1401] and [n % 4 for n in check["prompt_lens"]] == [2, 0, 1] and check["new_tokens"] == 128


def test_the_new_entries_are_appended_behind_what_was_there(manifest):
    """Stands in for test_bench_lfm2.py::test_the_new_entries_are_appended_behind_what_was_there and ::
    test_trinitys_mix_is_still_the_issues and for the tests they stood in for, all marked xfail (strict) in
    tests/conftest.py since PR 56 appends a cell to the lists they pin. PR 52's eight still stand together behind the
    49 that were there, PR 54's two behind them, PR 56's three behind those; every list that names the new cell names
    it behind the cells that were there; the cell reports what LFM2's does but the conv mixer's pair, and its three."""
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index(THE_EIGHT_OF_A_START[0])
    assert first == 49 and names[first : first + 8] == THE_EIGHT_OF_A_START and names[first + 8 : first + 10] == SHORT_CONV
    assert names[first + 10 : first + 13] == NEW_METRICS
    declared = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == BEFORE and [c["name"] for c in manifest["configs"]].index(SDAR) == 9
    entry = manifest["workloads"][BEFORE]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (SDAR, MIX, 1) and len(entry["why"]) <= 200
    assert "128 clients on 128 slots" in entry["why"] or "64 clients on 64 slots" in entry["why"]
    assert "gaps between blocks" in entry["why"]  # how itl_p95_ms reads where four tokens arrive at once
    spec = {
        "block_tokens_per_pass": ("tokens", "higher", "program_counter", "LLM engine", "serve_tokens_per_s"),
        "block_commit_share_pct": ("%", "lower", "program_counter", "LLM engine", "serve_tokens_per_s"),
        "block_draw_ms": ("ms", "lower", "device_trace", "model", "itl_p95_ms"),
    }
    for name, (unit, better, source, layer, moves) in spec.items():
        assert declared[name] == dict(name=name, unit=unit, better=better, source=source, layer=layer, moves=moves, workloads=[CELL])
        assert os.path.isfile(os.path.join(registry.BENCH_DIR, "layer_metrics", name + ".py"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed:  # appended to each list: behind every cell that was there before it
            assert all(listed.index(c) < listed.index(CELL) for c in listed if c in cells[:BEFORE]), m["name"]
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(CELL, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    # The experts' device time is not read here: the chunk's grouped matmuls bear the pass's names and shapes, the reduced
    # trace sums by name over both programs, and a number that mixes two programs is no layer's metric.
    assert want(CELL, True) == (want(LFM_CELL, True) - set(SHORT_CONV) - EXPERTS_ON_THE_DEVICE) | set(NEW_METRICS)
    assert "moe_experts" not in _config(manifest)["trace_ops"]
    for w in cells:
        assert bool(set(NEW_METRICS) & want(w, True)) == (w == CELL) and not set(NEW_METRICS) & want(w, False)
        assert bool(set(SHORT_CONV) & want(w, True)) == (w == LFM_CELL)
        assert bool(CACHE_PAIR & want(w, True)) == (w in CACHE_PAIR_CELLS)
    serving = [c for c in cells[:BEFORE] if not c.startswith(("train", "mellum"))]
    for name in THE_EIGHT_OF_A_START:
        assert declared[name]["workloads"] == ([c for c in cells[:BEFORE] if c not in serving] if name.startswith("trainer") else [*serving, CELL])
    for name in CACHE_PAIR:
        assert declared[name]["workloads"] == CACHE_PAIR_CELLS
    # one four-chip cell of thirteen, as there was
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == ["train2.dp4-4k"]


def _result(manifest, **over):
    """What the readers see of a traced run of the cell, by hand."""
    cell = registry.load_cell(manifest, CELL)
    cell["config"]["trace_ops"] = {
        "moe_experts": r"^%gmm\S* custom-call bf16\[4096,", "cache_attention": r"bf16\[16384,16,4,128\]$",
        "block_draw": r"\[512,151936\]$",
    }
    fields = ["t_start_ns", "rows", "prefill_tokens", "context_tokens", "window_tokens", "llm.iteration", "block_commits", "tokens_unmasked"]
    iterations = [[10**9 * (i + 1), 126 if i != 2 else 0, 512 if i == 4 else 0, 130_000, 130_000, 10**6, 42 if i != 2 else 0,
                   168 if i != 2 else 0] for i in range(6)]
    steps = 2000 + 606
    moe = {"steps": steps, "assignments": [[steps * 32] * 128] * 6, "experts_touched": [2000 * 128 + 606 * 12] * 6,
           "fullest_expert_load": [2000 * 50 + 606 * 2] * 6}
    result = {
        "cell": cell, "seconds": 51.0, "traced": True, "trace": None,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {"moe": {"decode": moe}, "running_polls": [128] * 50,
                     "spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}},
    }
    result["trace"] = {"devices": [{
        "programs": {"decode": [0.040] * 20, "prefill": [0.03] * 2},
        "ops": [["%gmm.13 custom-call bf16[4096,768]", 0.2], ["%gmm.15 custom-call bf16[4096,2048]", 0.1],
                ["%gmm.27 custom-call bf16[2048,768]", 0.5], ["%fusion.475 fusion bf16[16384,16,4,128]", 0.2],
                ["%convolution.13 convolution bf16[512,151936]", 0.02], ["%fusion.9 fusion f32[512,151936]", 0.02],
                ["%fusion.77 fusion bf16[512,6144]", 0.5]],
    }]}
    result.update(over)
    return result


def test_the_readers_on_a_result_written_by_hand(manifest):
    """The new metrics, and the accepted ones whose readers take this
    architecture's ``costs.py``: a share of a roofline stays under 100. The
    experts' pair is read off a pattern written here, for ``costs.moe_experts_bytes``'
    sake: the configuration names none and the cell's line has neither."""
    result = _result(manifest)
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    as_configured = dict(result, cell=registry.load_cell(manifest, CELL))
    assert all(registry.load_metric("per_layer", name)(as_configured) is None for name in EXPERTS_ON_THE_DEVICE)
    assert read("block_tokens_per_pass") == pytest.approx(168 / 126)  # 4/3: the pass that fed no row is no pass over blocks
    assert read("block_commit_share_pct") == pytest.approx(100 / 3)
    assert read("block_draw_ms") == pytest.approx(2.0)  # the head and the draw over [512, V], not the chunk's
    assert read("decode_rows_mean") == pytest.approx(126.0)  # rows FED
    assert read("moe_experts_touched_mean") == pytest.approx(128.0)  # the check's 606 lone passes taken out at 12 experts each
    assert read("moe_experts_ms") == pytest.approx(15.0)  # the pass's grouped matmuls, not the chunk's bf16[2048, ...]
    assert read("moe_experts_roofline") == pytest.approx(100 * 6 * 128 * 3 * 2048 * 768 * 2 / 819e9 / 0.015)
    assert read("cache_attention_ms") == pytest.approx(10.0)
    assert read("cache_attention_roofline") == pytest.approx(100 * 130_000 * 12_288 / 819e9 / 0.010)
    assert read("prefill_pass_share_pct") == pytest.approx(100 / 6)
    mix = result["cell"]["traffic"]
    context = int(128 * (traffic.mean_length(mix["prompt_len"]) + traffic.mean_length(mix["output_len"]) / 2))
    least = _part("costs").decode_step_bytes(result["cell"]["config"], context) / 819e9
    assert read("decode_mfu_roofline") == pytest.approx(100 * least / 0.040)
    for name in ("moe_experts_roofline", "cache_attention_roofline", "decode_mfu_roofline"):
        assert 0 < read(name) <= 100, name


@pytest.mark.parametrize("lacking", ["trace_ops", "ring fields", "spans", "trace"])
def test_a_new_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """A configuration that names no such operations, a program whose ring has
    no such fields (the parent's), one without spans, and an untraced run."""
    result = _result(manifest)
    silent = set()
    if lacking == "trace_ops":
        del result["cell"]["config"]["trace_ops"]["block_draw"]
        silent = {"block_draw_ms"}
    elif lacking == "ring fields":
        spans = result["counters"]["spans"]
        width = len(spans["fields"]["iterations"])
        spans["iterations"] = [x for i in range(0, len(spans["iterations"]), width) for x in spans["iterations"][i : i + width - 2]]
        spans["fields"]["iterations"] = spans["fields"]["iterations"][:-2]
        silent = {"block_tokens_per_pass", "block_commit_share_pct"}
    elif lacking == "spans":
        del result["counters"]["spans"]
        silent = {"block_tokens_per_pass", "block_commit_share_pct"}
    else:
        result["trace"] = None
        silent = {"block_draw_ms"}
    for name in NEW_METRICS:
        value = registry.load_metric("per_layer", name)(result)
        assert (value is None) == (name in silent), (name, value)


def test_the_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check
    against the architecture's own float32 reference (each checked request
    served once more pass by pass, every pass's input rebuilt, the transfers
    inside their margin), the mix, the line."""
    cell = toy_cell(manifest, CELL)
    toy = cell["config"]
    assert toy["torch_dtype"] == "float32" and toy["block_length"] == 4 and toy["mask_token_id"] < toy["vocab_size"]
    toy["deployment"]["engine"].update(prefill_chunk=32, denoising_steps=2)
    cell["traffic"]["arrival"]["clients"] = 4  # the toy engine has 4 slots: as many clients as slots, as in the cell
    result = bench_run.measure(
        cell, seed=2**31 + 56, seconds=3.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    gaps = result["notes"]["reference_gaps"]
    assert len(gaps) == 2 and all(g["finite"] and g["max_gap"] <= toy["check"]["logit_gap_tol"] for g in gaps)
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, CELL, traced=False, platform="cpu")
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    counters = result["counters"]
    assert counters["kv_pool_not_donated"] == 0 and counters["host_logit_rows"] == 0
    assert counters["block_passes"] == counters["decode_steps"] > 0 and counters["block_passes_synced"] == 0
    assert counters["decode_steps_run_ahead"] > 0.9 * counters["decode_steps"]  # by count, a pass ahead
    assert (counters["block_length"], counters["denoising_steps"]) == (4, 2)
    assert counters["block_tokens_emitted"] >= result["client"]["tokens_in_window"] > 0
    assert set(counters["kv_groups"]) == {"full"}
    moe = counters["moe"]["decode"]
    # The device's counters are copied between two passes and the host's counts are read behind them, any number of
    # passes later on a loaded machine (four, with six workers: the driver's run of PR 62). By count and not by the
    # clock: they hold every pass of the window's iterations, which all began before the copy was asked for, and no
    # pass that was not dispatched. The window's iterations are ~0.85 of all passes here, so a counter that stepped
    # once a block (a third of the passes) falls under them, and one that stepped once a position passes the upper side.
    in_window = len(decode_iterations(result))
    assert 0 < in_window <= moe["steps"] <= counters["block_passes"]
    assert len(moe["assignments"]) == 2 and len(moe["assignments"][0]) == toy["num_experts"]
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert 1.0 < read("block_tokens_per_pass") < 4 / 3 + 0.05 and 30.0 < read("block_commit_share_pct") < 40.0
    assert read("block_draw_ms") is None and read("prefill_pass_share_pct") > 0.0
