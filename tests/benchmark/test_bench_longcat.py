"""The tenth architecture of the benchmark (PR 61), ``LongcatFlashForCausalLM``
(LongCat-Flash-Omni's language model: shortcut-connected double layers of two
latent-attention sub-layers, two dense FFNs and one branch of routed experts
joined a sub-layer late, identity experts in a 768-wide top-12 router), and the
cell PR 61 adds, ``longcat4.rollout-wide``: the configuration against the
catalog's row, the cut and the counts against hand arithmetic and against the
parameter tree the program draws, the cell at a toy size through
``run.measure`` on the CPU, the two new per-layer metrics' readers and the
accepted ones that read this architecture's ``costs.py``, and what stands in
for the two tests of ``tests/benchmark/`` that an eleventh configuration's
appended entries made wrong (``tests/conftest.py`` marks those). Nothing here
pins the END of a list that a later PR may append to."""

import json
import os
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic

LONGCAT, CELL, MIX, ARCH = "longcat-flash-omni-serve4", "longcat4.rollout-wide", "rollout-wide", "LongcatFlashForCausalLM"
LFM_CELL, SDAR_CELL, GLM_CELL, XING_CELL, NEMO_CELL = (
    "lfm9.rollout-wide", "sdar6.rollout-block", "glm8.rollout-long", "xing6.longdoc-12k", "nemo14.chat-churn",
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "LongCat-Flash-Omni"
NEW_METRICS = ["moe_identity_pick_share_pct", "moe_rows_without_held_pct"]
REDUCED = {"num_layers": (4, 28), "n_routed_experts": (16, 512), "vocab_size": (16384, 131072)}
THE_EIGHT_OF_A_START = [
    "replica_spawn_s", "replica_backend_s", "programs_python_s", "programs_python_waiting_pct", "programs_compile_s",
    "programs_compiled_afresh", "trainer_spawn_s", "trainer_backend_s",
]
SHORT_CONV = ["short_conv_ms", "short_conv_roofline"]
OF_SDAR = ["block_tokens_per_pass", "block_commit_share_pct", "block_draw_ms"]
CACHE_PAIR = {"cache_attention_ms", "cache_attention_roofline"}
LATENT_PAIR = {"latent_attention_ms", "latent_attention_roofline"}
LATENT_PREFILL_PAIR = {"latent_prefill_ms", "latent_prefill_roofline"}
BEFORE = 13  # cells the benchmark had


def _config(manifest):
    return registry.load_cell(manifest, CELL)["config"]


def _part(part):
    return registry.load_architecture({"name": "these tests", "architecture": ARCH, "bench_dir": registry.BENCH_DIR}, part)


def test_the_configuration_holds_the_catalogs_row(manifest):
    """Every key of the catalog row's ``config`` under the same key, but the three
    that the cut changes, which ``reduced`` and ``published`` both name."""
    cfg = _config(manifest)
    entry = next(c for c in manifest["configs"] if c["name"] == LONGCAT)
    assert cfg["reduced"] == entry["reduced"] == list(REDUCED)
    assert cfg["published"] == {key: published for key, (_, published) in REDUCED.items()}
    assert all(cfg[key] == cut for key, (cut, _) in REDUCED.items())
    assert cfg["architectures"] == [ARCH] and cfg["path"] == "serve" and cfg["torch_dtype"] == "bfloat16"
    assert entry["file"] == f"benchmarks/configs/{LONGCAT}.json" and len(entry["why"]) <= 200
    # every published width, the router's 768 columns, the 12 picks and the scaling
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"], cfg["num_attention_heads"]) == (6144, 12288, 2048, 64)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"] == 768 and (cfg["moe_topk"], cfg["routed_scaling_factor"]) == (12, 6)
    ep = cfg["deployment"]["expert_parallel"]
    assert ep == {"chips": 32, "index": 0} and cfg["n_routed_experts"] * ep["chips"] == cfg["published"]["n_routed_experts"]
    assert cfg["deployment"]["engine"] == dict(num_slots=128, block_size=16, max_model_len=2048, num_blocks=16385, prefill_chunk=512)
    for key in ("architectures", "un-normalised top-12 weights", "router", "e_score_correction_bias", "the router's matrix",
                "mla_scale_q_lora and mla_scale_kv_lora", "tie_word_embeddings", "torch_dtype", "rotary layout", "the double layer"):
        assert key in cfg["assumed"], key  # what the row does not carry is said to be assumed
    assert "not re-read" in cfg["assumed"]["un-normalised top-12 weights"]
    assert "TOWERS" in cfg["left_out"] and "EXCHANGE" in cfg["left_out"] and "measured" in cfg["deployment"]["sizing"].lower()
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    except OSError:
        pytest.skip("no catalog beside the guides here")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = REDUCED[key][0] if key in REDUCED else value
        assert cfg[key] == want and type(cfg[key]) is type(want), key
    assert "architectures" not in row["config"]  # hence the directory's name is ``assumed``


def test_the_counts_against_hand_arithmetic(manifest):
    cfg, costs = _config(manifest), _part("costs")
    attention = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 8192 * 6144
    ffn, expert, router = 3 * 6144 * 12288, 3 * 6144 * 2048, 6144 * 768
    assert (attention, ffn, expert, router) == (90_570_752, 226_492_416, 37_748_736, 4_718_592)
    assert (costs.attention_params(cfg), costs.ffn_params(cfg), costs.expert_params(cfg)) == (attention, ffn, expert)
    shared = 2 * (attention + ffn) + router
    assert costs.layer_shared_matmul_params(cfg) == shared == 638_844_928  # ISSUE 61's 638.9M
    norms = 2 * (2 * 6144 + 1536 + 512)
    layer = shared + 768 + 16 * expert + norms
    assert costs.n_params(cfg) == 4 * layer + 2 * 16384 * 6144 + 6144 == 5_172_749_312
    assert costs.weight_bytes(cfg) == 2 * 5_172_749_312 + 2 * 4 * 768 * 6145  # the router and its bias in float32
    assert round(costs.weight_bytes(cfg) / 1e9, 2) == 10.38
    # as published: 28 layers, all 512 experts, the whole vocabulary
    whole = dict(cfg, num_layers=28, n_routed_experts=512, vocab_size=131072)
    assert round(costs.n_params(whole) / 1e9) == 561  # the row's "560B"
    assert costs.kv_bytes_per_token(cfg) == 2 * 4 * 576 * 2 == 9216  # two cached layers a double layer
    assert costs.router_width(cfg) == 768 and costs.experts_routed_among(cfg) == 512
    # a step of 128 rows: a held expert is picked by a row with probability 12 / 768, 13 % of the 16 stay untouched
    assert costs.expected_experts_touched(cfg, 128) == pytest.approx(16 * (1 - (63 / 64) ** 128))
    assert 13.8 < costs.expected_experts_touched(cfg, 128) < 13.9
    assert costs.moe_experts_bytes(cfg, 13.0) == 4 * 13 * expert * 2
    assert costs.latent_attention_bytes(cfg, 1000) == 9_216_000
    assert costs.latent_attention_flops(cfg, 1000) == 2 * 4 * 1000 * 64 * 2 * (2 * 512 + 64)
    # a chunk of 512 behind nothing: the expanded form is the cheaper (scores over 192 + sums over 128 columns a pair)
    pairs = 512 * 513 / 2
    assert costs.latent_prefill_flops(cfg, 512, 0) == 8 * 2 * 64 * (pairs * 320 + 512 * 512 * 256) < 8 * 2 * 64 * (pairs * 1088 + 512 * 512 * 256)
    assert costs.latent_prefill_flops(cfg, 512, 1000) == 8 * 2 * 64 * ((pairs + 512_000) * 320 + 1512 * 512 * 256)
    assert costs.latent_prefill_bytes(cfg, 512, 1000) == 8 * 2 * (1512 * 576 + 512 * 64 * 320 + 512 * 64 * 256)
    everyone = (4 * shared + 6144 * 16384) * 2 + 4 * router * 2
    experts = costs.moe_experts_bytes(cfg, costs.expected_experts_touched(cfg, 128))
    assert costs.decode_step_bytes(cfg, 0) == int(everyone + experts)
    assert costs.decode_step_bytes(cfg, 130_000) == int(everyone + experts + 130_000 * 9216)
    assert 10.6e9 < costs.decode_step_bytes(cfg, 130_000) < 10.8e9
    alone = costs.moe_steps_alone(cfg, traced=True)
    assert alone["steps"] == 2 * 3 * 127 + 10 and alone["experts_touched"] == pytest.approx(0.25)
    assert alone["fullest_expert_load"] == pytest.approx(1 - (1 - 16 / 768) ** 12)


def test_the_counts_are_the_drawn_parameter_trees(manifest):
    import jax

    from ray_tpu.models.generate import cache_token_bytes, expert_layers, init_moe_choice, init_moe_counts
    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg, costs = _config(manifest), _part("costs")
    model = _part("config").model_config(cfg, 2048, "bfloat16")
    program = TransformerConfig(**model)
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), program))
    leaves = jax.tree.leaves(tree)
    assert sum(leaf.size for leaf in leaves) == costs.n_params(cfg)
    assert sum(leaf.size * leaf.dtype.itemsize for leaf in leaves) == costs.weight_bytes(cfg)
    layers = tree["layers"]
    assert layers["gate"].shape == (4, 6144, 768) and layers["gate"].dtype == layers["gate_bias"].dtype == "float32"
    assert layers["wg_e"].shape == (4, 16, 6144, 2048) and layers["wi"].shape == (4, 2, 6144, 12288)
    assert layers["wq_b"].shape == (4, 2, 1536, 64 * 192) and layers["wo"].shape == (4, 2, 8192, 6144) and "lm_head" in tree
    # the pool: 8 cached layers of 640-wide rows (576 padded to the lanes); the picks of a token a layer in 4 words of 3
    assert cache_token_bytes(program) == {"full": 8 * 640 * 2} and expert_layers(program) == 4
    assert jax.eval_shape(lambda: init_moe_choice(program, 16385, 16)).shape == (4, 4, 16385, 16)
    assert jax.eval_shape(lambda: init_moe_counts(program)).shape == (2, 4, 16 + 3 + 1 + 2)


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cfg, config = _config(manifest), _part("config")
    model = config.model_config(cfg, 2048, "bfloat16")
    assert model["shortcut_moe"] and (model["num_experts"], model["zero_experts"], model["experts_per_token"]) == (512, 256, 12)
    assert model["expert_share"] == [0, 32] and model["router_normalize"] is False and model["router_score"] == "softmax"
    assert model["mla_scale_q_lora"] and model["mla_scale_kv_lora"] and model["n_layers"] == 4 and model["rope_theta"] == 1e7
    for key, value in (("zero_expert_type", "copy"), ("attention_bias", True), ("attention_method", "MHA"),
                       ("rope_scaling", {"type": "yarn", "factor": 4})):
        with pytest.raises(ValueError, match=key):
            config.model_config(dict(cfg, **{key: value}), 2048, "bfloat16")
    with pytest.raises(ValueError, match="n_routed_experts"):
        config.model_config(dict(cfg, n_routed_experts=8), 2048, "bfloat16")
    # A program from before the double layer (the parent the driver tries the new cell on): refused in the driver
    # process, at once, by the names of the fields it lacks.
    monkeypatch.setattr(config, "_program_fields", lambda: {"vocab_size", "kv_lora_rank", "expert_share"})
    with pytest.raises(NotImplementedError, match="shortcut_moe.*zero_experts.*cannot run the shortcut-connected double layer"):
        config.model_config(cfg, 2048, "bfloat16")


def test_the_reference_imports_nothing_of_the_programs_kernels():
    with open(os.path.join(registry.BENCH_DIR, "architectures", ARCH, "reference.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines() if line.lstrip().startswith(("import ", "from "))]
    assert not any("ray_tpu.ops" in line or "ray_tpu.parallel" in line or "ray_tpu.models" in line for line in imports), imports
    assert 'default_matmul_precision("highest")' in source and "F32 = jnp.float32" in source


def test_the_mix_is_lfm2s_unedited_and_fits_the_cell(manifest):
    cell = registry.load_cell(manifest, CELL)
    mix, engine = cell["traffic"], cell["config"]["deployment"]["engine"]
    assert cell["traffic_name"] == MIX and mix == registry.load_cell(manifest, LFM_CELL)["traffic"]
    assert mix["arrival"] == {"process": "closed", "clients": engine["num_slots"], "requests_per_client": 5}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 256, "max": 512} and mix["output_len"] == {"dist": "uniform", "min": 768, "max": 1280}
    assert mix["sampling"] == {"sampled_share": 1.0, "temperature": 1.0, "top_k": 0} and mix["schedule_seed"] == 54
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1792 < engine["max_model_len"] and mix["prompt_len"]["max"] == engine["prefill_chunk"]
    vocab = cell["config"]["vocab_size"]
    plans = [traffic.schedule(mix, seed, 51.0, vocab) for seed in (1, 2**31 + 5)]
    assert traffic.offered_tokens(plans[0]) == traffic.offered_tokens(plans[1])
    for plan in plans:  # tokens over the held slice of the vocabulary
        reqs = [r for client in plan["closed"] for r in client]
        assert all(len(r["tokens"]) + r["max_new_tokens"] <= 1792 and max(r["tokens"]) < vocab for r in reqs)
    check = cell["config"]["check"]
    assert check["prompt_lens"] == [200, 700, 1400] and check["new_tokens"] == 128 and 0 < check["logit_gap_tol"] < 1


def test_the_new_entries_are_appended_behind_what_was_there(manifest):
    """Stands in for test_bench_sdar.py::test_the_new_entries_are_appended_behind_what_was_there and test_bench_setup_
    stages.py::test_xings_cell_reports_what_it_did_and_the_six_of_its_start, both marked xfail (strict) in
    tests/conftest.py since PR 61 appends a cell to lists they pin (the six of a replica's start and every other list
    LFM2's cell is in; ``moe_held_share_pct``, which was Nemotron's alone; the latent pair, which was GLM's and
    Xing's). PR 52's eight still stand together behind the 49, PR 54's two behind them, PR 56's three behind those and
    PR 61's two behind those; every list that names the new cell names it behind the cells that were there; the cell
    reports what LFM2's does but the conv mixer's and the K/V cache's pairs, and the latent pair, the chunk's latent
    pair (Xing's alone before), the held share and its two."""
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index(THE_EIGHT_OF_A_START[0])
    assert first == 49 and names[first : first + 8] == THE_EIGHT_OF_A_START and names[first + 8 : first + 10] == SHORT_CONV
    assert names[first + 10 : first + 13] == OF_SDAR and names[first + 13 : first + 15] == NEW_METRICS
    declared = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == BEFORE and cells.index(SDAR_CELL) == BEFORE - 1
    assert [c["name"] for c in manifest["configs"]].index(LONGCAT) == 10
    entry = manifest["workloads"][BEFORE]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (LONGCAT, MIX, 1) and len(entry["why"]) <= 200
    assert "128 clients on 128 slots" in entry["why"] and "2 rows a step" in entry["why"] and "ALL picks" in entry["why"]
    for name in NEW_METRICS:
        assert declared[name] == dict(name=name, unit="%", better="higher", source="program_counter", layer="model",
                                      moves="itl_p95_ms", workloads=[CELL])
        assert os.path.isfile(os.path.join(registry.BENCH_DIR, "layer_metrics", name + ".py"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed:  # appended to each list: behind every cell that was there before it
            assert all(listed.index(c) < listed.index(CELL) for c in listed if c in cells[:BEFORE]), m["name"]
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(CELL, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}  # no ttft_p90_ms, as the other rollout cells
    assert want(CELL, True) == (want(LFM_CELL, True) - set(SHORT_CONV) - CACHE_PAIR) | LATENT_PAIR | LATENT_PREFILL_PAIR | {"moe_held_share_pct"} | set(NEW_METRICS)
    for w in cells:
        assert bool(set(NEW_METRICS) & want(w, True)) == (w == CELL) and not set(NEW_METRICS) & want(w, False)
        assert bool(LATENT_PAIR & want(w, True)) == (w in (GLM_CELL, XING_CELL, CELL))
        assert bool(LATENT_PREFILL_PAIR & want(w, True)) == (w in (XING_CELL, CELL))  # GLM's chunk is a view's: no such pattern
        assert ("moe_held_share_pct" in want(w, True)) == (w in (NEMO_CELL, CELL))
        assert bool(set(OF_SDAR) & want(w, True)) == (w == SDAR_CELL)
    serving = [c for c in cells[:BEFORE] if not c.startswith(("train", "mellum"))]
    for name in THE_EIGHT_OF_A_START:
        assert declared[name]["workloads"] == ([c for c in cells[:BEFORE] if c not in serving] if name.startswith("trainer") else [*serving, CELL])
    assert declared["moe_held_share_pct"]["workloads"] == [NEMO_CELL, CELL]
    assert declared["latent_attention_ms"]["workloads"] == declared["latent_attention_roofline"]["workloads"] == [GLM_CELL, XING_CELL, CELL]
    assert declared["latent_prefill_ms"]["workloads"] == declared["latent_prefill_roofline"]["workloads"] == [XING_CELL, CELL]
    assert declared["prefill_pass_share_pct"]["workloads"][:2] == [NEMO_CELL, XING_CELL]
    # what test_xings_cell_reports... held beside the lists: Xing's cell reports what it did
    xing = want(XING_CELL, True)
    assert LATENT_PREFILL_PAIR <= xing and not set(NEW_METRICS) & xing and "moe_held_share_pct" not in xing
    assert list(contract.expected_metrics(manifest, XING_CELL, traced=True))[-6:] == [n for n in THE_EIGHT_OF_A_START if not n.startswith("trainer")]
    # one four-chip cell of fourteen, as there was
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == ["train2.dp4-4k"] and len(cells) == 14


def _result(manifest, **over):
    """What the readers see of a traced run of the cell, by hand: 2000 steps under
    traffic of 128 rows and the check's 772 lone steps, 4 layers."""
    cell = registry.load_cell(manifest, CELL)
    steps, alone = 2000, 2 * 3 * 127 + 10
    rows = steps * 128 + alone
    picks = rows * 12
    layer = dict(held=picks // 48, identity=picks // 3)
    moe = {
        "steps": steps + alone, "assignments": [[layer["held"] // 16] * 16] * 4, "assignments_all": [picks] * 4,
        "experts_touched": [steps * 13.5 + alone * 0.25] * 4, "fullest_expert_load": [steps * 5 + alone * 0.2] * 4,
        "picks_identity": [layer["identity"]] * 4, "rows_without_held": [rows * 3 // 4] * 4,
    }
    fields = ["t_start_ns", "rows", "prefill_tokens", "context_tokens", "window_tokens", "chunk_context_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 128, 512 if i == 4 else 0, 130_000, 0, 0, 10**6] for i in range(6)]
    result = {
        "cell": cell, "seconds": 51.0, "traced": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {"moe": {"decode": moe}, "running_polls": [128] * 50,
                     "spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}},
        "trace": {"devices": [{
            "programs": {"decode": [0.020] * 20, "prefill": [0.03] * 2},
            "ops": [["%ragged-dot-none.2 custom-call bf16[1536,6144]", 0.03], ["%ragged-dot-none.1 custom-call bf16[1536,2048]", 0.03],
                    ["%ragged-dot-none custom-call bf16[1536,2048]", 0.04], ["%gmm.3 custom-call bf16[6144,2048]", 0.5],
                    ["%paged_latent_attention.13 custom-call bf16[128,64,1,640]", 0.03],
                    ["%paged_latent_attention.12 custom-call bf16[128,64,1,640]", 0.03],
                    ["%paged_latent_chunk_attention.3 custom-call bf16[1,64,512,640]", 0.4]],
        }]},
    }
    result.update(over)
    return result


def test_the_readers_on_a_result_written_by_hand(manifest):
    """The two new metrics, and the accepted ones whose readers take this
    architecture's ``costs.py`` and the configuration's ``trace_ops``: a share
    of a roofline stays under 100."""
    result = _result(manifest)
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert read("moe_identity_pick_share_pct") == pytest.approx(100 / 3, rel=1e-4)
    assert read("moe_rows_without_held_pct") == pytest.approx(75.0, rel=1e-4)
    assert read("moe_held_share_pct") == pytest.approx(100 / 48, rel=1e-3)  # held picks over ALL picks, identities among them
    assert read("moe_experts_touched_mean") == pytest.approx(13.5)  # the check's lone steps taken out at a quarter of an expert each
    assert read("moe_expert_load_max_mean") == pytest.approx(5.0, rel=0.01)
    assert read("moe_experts_ms") == pytest.approx(5.0)  # the step's three grouped matmuls, not the chunk's %gmm
    assert read("moe_experts_roofline") == pytest.approx(100 * 4 * 13.5 * 37_748_736 * 2 / 819e9 / 0.005)
    assert read("latent_attention_ms") == pytest.approx(3.0)  # both sub-layers' walks, not the chunk's
    assert read("latent_attention_roofline") == pytest.approx(100 * 130_000 * 9216 / 819e9 / 0.003)
    assert read("prefill_pass_share_pct") == pytest.approx(100 / 6)
    assert read("latent_prefill_ms") == pytest.approx(200.0)  # the chunk kernel's 0.4 s over the slice's two chunks, not the step's walks
    costs, cfg = _part("costs"), result["cell"]["config"]
    least = costs.latent_prefill_flops(cfg, 512, 0) / 197e12
    assert least > costs.latent_prefill_bytes(cfg, 512, 0) / 819e9  # operations bound a chunk behind nothing
    assert read("latent_prefill_roofline") == pytest.approx(100 * least / 0.200)
    mix = result["cell"]["traffic"]
    context = int(128 * (traffic.mean_length(mix["prompt_len"]) + traffic.mean_length(mix["output_len"]) / 2))
    least = _part("costs").decode_step_bytes(result["cell"]["config"], context) / 819e9
    assert read("decode_mfu_roofline") == pytest.approx(100 * least / 0.020)
    for name in ("moe_experts_roofline", "latent_attention_roofline", "latent_prefill_roofline", "decode_mfu_roofline"):
        assert 0 < read(name) <= 100, name


@pytest.mark.parametrize("lacking", ["the picks apart", "moe", "counters"])
def test_a_new_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """A program whose counters do not tell a step's picks apart (every
    configuration before this one; the parent's), one without expert counters,
    and a result without counters: the two new readers return None and raise nothing."""
    result = _result(manifest)
    moe = result["counters"]["moe"]["decode"]
    if lacking == "the picks apart":
        for key in ("picks_identity", "rows_without_held"):
            del moe[key]
        assert registry.load_metric("per_layer", "moe_held_share_pct")(result) is not None
    elif lacking == "moe":
        del result["counters"]["moe"]
    else:
        result["counters"] = None
    assert all(registry.load_metric("per_layer", name)(result) is None for name in NEW_METRICS)


def test_the_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check
    against the architecture's own float32 reference under the system's picks,
    the mix, the line, and the counters of what a step's picks were."""
    cell = toy_cell(manifest, CELL)
    toy = cell["config"]
    assert toy["torch_dtype"] == "float32" and toy["zero_expert_num"] == 256 and toy["published"]["n_routed_experts"] == 512
    cell["traffic"]["arrival"]["clients"] = 4  # the toy engine has 4 slots: as many clients as slots, as in the cell
    result = bench_run.measure(
        cell, seed=2**31 + 61, seconds=2.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    gaps = result["notes"]["reference_gaps"]
    assert len(gaps) == 2 and all(g["finite"] and g["max_gap"] <= toy["check"]["logit_gap_tol"] and g["logit_std"] > 0.3 for g in gaps)
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, CELL, traced=False, platform="cpu")
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    counters = result["counters"]
    assert counters["kv_pool_not_donated"] == 0 and counters["host_logit_rows"] == 0
    assert counters["kv_token_bytes"] == 2 * toy["num_layers"] * 128 * 4  # two cached layers a layer: 32 + 16 padded to the lanes, float32
    for kind in ("decode", "prefill"):
        moe = counters["moe"][kind]
        assert moe["steps"] > 0 and len(moe["assignments"]) == toy["num_layers"] and len(moe["assignments"][0]) == 16
        for layer in range(toy["num_layers"]):  # a pick is one of three things, and every row has twelve
            every, held = moe["assignments_all"][layer], sum(moe["assignments"][layer])
            assert every % 12 == 0 and 0 < moe["picks_identity"][layer] < every - held
            assert 0 < moe["rows_without_held"][layer] <= every // 12 and every - 12 * moe["rows_without_held"][layer] >= held
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert 25.0 < read("moe_identity_pick_share_pct") < 42.0  # a third where the routing is even over 512 and 256
    assert 60.0 < read("moe_rows_without_held_pct") < 95.0 and 0.5 < read("moe_held_share_pct") < 5.0
    assert read("prefill_pass_share_pct") > 0.0 and read("moe_experts_ms") is None
