"""The second architecture of the benchmark (PR 32), ``Glm4MoeLiteForCausalLM``
(GLM-4.7-Flash: latent attention, dropless routed experts with a shared expert
behind a leading dense layer), and the two cells PR 32 adds: the
configuration against the catalog's numbers, the counts against hand
arithmetic, both cells at a toy size through ``run.measure`` on the CPU, the
six per-layer metrics' readers, and what stands in for four tests of PR 26
that a second architecture made wrong (``tests/conftest.py`` marks those)."""

import json
import os
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic

GLM, ROLLOUT, LONG_PROMPT = "glm-4.7-flash-serve8", "glm8.rollout-long", "serve16.long-prompt"
# https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json as the
# model-configs catalog quotes it (every key of the row's ``config``).
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}
MISTRAL = dict(
    hidden_size=4096, num_attention_heads=32, num_key_value_heads=8, intermediate_size=14336,
    vocab_size=32000, sliding_window=4096,
)


def _config(manifest, name):
    cell = next(w["name"] for w in manifest["workloads"] if w["config"] == name)
    return registry.load_cell(manifest, cell)["config"]


def _glm_costs():
    return registry.load_architecture(
        {"name": "these tests", "architecture": "Glm4MoeLiteForCausalLM", "bench_dir": registry.BENCH_DIR}, "costs"
    )


def test_each_configuration_holds_its_own_published_widths(manifest):
    """Stands in for test_bench_costs.py::test_the_configuration_files_hold_the_published_widths,
    which holds EVERY configuration to Mistral-7B's widths and is marked xfail (strict) in
    tests/conftest.py since one of them is GLM-4.7-Flash: each is held to its own here."""
    published = {"MistralForCausalLM": MISTRAL, "Glm4MoeLiteForCausalLM": PUBLISHED}
    for cfg in manifest["configs"]:
        held = _config(manifest, cfg["name"])
        want = published[held["architectures"][0]]
        assert held["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
        assert held["source"] == cfg["source"]
        for key, value in want.items():
            if key not in held["reduced"]:
                assert held[key] == value and type(held[key]) is type(value), (cfg["name"], key)
    glm = _config(manifest, GLM)
    assert glm["num_hidden_layers"] == 8 and glm["published"] == {"num_hidden_layers": 47}
    # the leading dense layer and at least four of the layers that follow; all experts, the whole vocabulary
    assert glm["num_hidden_layers"] - glm["first_k_dense_replace"] >= 4
    assert glm["assumed"] and glm["cut"] and "multi-token-prediction" in glm["left_out"]
    assert glm["deployment"]["engine"] == dict(
        num_slots=32, block_size=16, max_model_len=4096, num_blocks=8193, prefill_chunk=512
    )


def test_the_two_mixes_are_the_issues(manifest):
    rollout = registry.load_cell(manifest, ROLLOUT)["traffic"]
    assert rollout["arrival"] == {"process": "closed", "clients": 32, "requests_per_client": 4}
    assert rollout["prompt_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert rollout["output_len"] == {"dist": "uniform", "min": 1536, "max": 3072}
    assert rollout["sampling"] == {"sampled_share": 1.0, "temperature": 1.0, "top_k": 0}
    assert (rollout["preroll_s"], rollout["grace_s"], rollout["trace_slice_s"]) == (8.0, 5.0, 3.0)
    assert rollout["stratified"] is True and rollout["schedule_seed"] == 32
    long_prompt = registry.load_cell(manifest, LONG_PROMPT)["traffic"]
    assert long_prompt["arrival"] == {"process": "closed", "clients": 4, "requests_per_client": 16}
    assert long_prompt["prompt_len"] == {"dist": "uniform", "min": 1536, "max": 2000}
    assert long_prompt["output_len"] == {"dist": "uniform", "min": 16, "max": 32}
    assert long_prompt["sampling"]["sampled_share"] == 0.0
    assert (long_prompt["preroll_s"], long_prompt["grace_s"]) == (8.0, 5.0)
    assert long_prompt["stratified"] is True and long_prompt["schedule_seed"] == 32
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(ROLLOUT, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert want(LONG_PROMPT, False) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    six = {"moe_experts_touched_mean", "moe_expert_load_max_mean", "moe_experts_ms", "moe_experts_roofline",
           "latent_attention_ms", "latent_attention_roofline"}
    assert six <= want(ROLLOUT, True) and not six & want(LONG_PROMPT, True)
    assert {"decode_mfu_roofline", "decode_step_ms", "engine_iteration_ms"} <= want(ROLLOUT, True) & want(LONG_PROMPT, True)


def test_the_span_metrics_stand_and_new_cells_are_only_appended(manifest):
    """Stands in for test_bench_span_metrics.py::test_expected_metrics_lists_each_span_metric_for_exactly_its_cells
    and ::test_benchmark_json_declares_them_and_the_parked_copy_is_gone, which hold that ``per_layer`` ENDS
    with PR 26's twelve span metrics and that exactly PR 26's cells report them (both marked xfail, strict, in
    tests/conftest.py: PR 32 appends six metrics and two cells). Here: the twelve are declared where they
    were, after PR 23's fourteen, and each still lists PR 26's cells first; what came later was appended."""
    from test_bench_span_metrics import CELLS

    assert [m["name"] for m in manifest["per_layer"][14:26]] == list(CELLS)
    declared = {m["name"]: m for m in manifest["per_layer"]}
    order = [w["name"] for w in manifest["workloads"]]
    for name, cells in CELLS.items():
        listed = declared[name]["workloads"]
        assert set(listed[: len(cells)]) == cells and listed == sorted(listed, key=order.index)
        assert declared[name]["source"] in ("program_span", "program_counter")
    for w in manifest["workloads"]:
        traced = contract.expected_metrics(manifest, w["name"], traced=True)
        assert not set(CELLS) & set(contract.expected_metrics(manifest, w["name"], traced=False))
        for name in CELLS:
            assert (name in traced) == (w["name"] in declared[name]["workloads"])
    assert len(contract.expected_metrics(manifest, "serve16.chat-open", traced=True)) == 24
    assert len(contract.expected_metrics(manifest, "serve16.batch-decode", traced=True)) == 18


def test_every_mix_fits_the_configurations_that_run_it(manifest):
    """Stands in for test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[rollout-long]:
    a request fits the ``max_model_len`` of the cell that sends it (that test
    holds every mix to 2560, one configuration's limit, and is marked xfail, strict,
    in tests/conftest.py for the mix that runs under 4096), and every seed offers the same load."""
    for w in manifest["workloads"]:
        cell = registry.load_cell(manifest, w["name"])
        if cell["config"]["path"] != "serve":
            continue
        limit = cell["config"]["deployment"]["engine"]["max_model_len"]
        vocab = cell["config"]["vocab_size"]
        plans = [traffic.schedule(cell["traffic"], seed, 20, vocab) for seed in (1, 2, 2**31 + 99)]
        assert len({traffic.offered_tokens(p) for p in plans}) == 1
        for plan in plans:
            reqs = plan["open"] if "open" in plan else [r for c in plan["closed"] for r in c]
            assert all(len(r["tokens"]) + r["max_new_tokens"] <= limit for r in reqs), w["name"]
            assert all(0 <= t < vocab for r in reqs for t in r["tokens"])
    rollout = registry.load_cell(manifest, ROLLOUT)["traffic"]
    a, b = (traffic.schedule(rollout, seed, 51, 154880)["closed"] for seed in (3, 2**31 + 5))
    lengths = lambda plan: [[(len(r["tokens"]), r["max_new_tokens"]) for r in c] for c in plan]  # noqa: E731
    assert lengths(a) == lengths(b)  # schedule_seed: the same lengths in the same order for every --seed
    assert a[0][0]["tokens"] != b[0][0]["tokens"]


def test_the_counts_against_hand_arithmetic(manifest):
    costs, m = _glm_costs(), _config(manifest, GLM)
    # q: 2048x768 + 768x(20x256); kv: 2048x576 + 512x(20x448); o: 5120x2048
    attention = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert costs.attention_params(m) == attention == 21_757_952
    assert costs.expert_params(m) == 3 * 2048 * 1536 == 9_437_184
    assert costs.expert_layers(m) == 7
    dense = attention + 3 * 2048 * 10240
    shared = attention + 9_437_184 + 2048 * 64
    norms = 2 * 2048 + 768 + 512
    total = dense + norms + 7 * (shared + 64 * (9_437_184 + 1) + norms) + 2 * 154880 * 2048 + 2048
    assert costs.n_params(m) == total
    assert round(costs.weight_bytes(m) / 1e9, 2) == 10.33
    assert costs.kv_bytes_per_token(m) == 8 * 576 * 2 == 9216
    assert costs.expected_experts_touched(m, 32) == pytest.approx(64 * (1 - (15 / 16) ** 32))
    assert costs.expected_experts_touched(m, 1) == pytest.approx(4.0)
    assert costs.moe_experts_bytes(m, 56.0) == 7 * 56 * 9_437_184 * 2
    assert costs.latent_attention_bytes(m, 1000) == 9_216_000
    everyone = (dense + 7 * shared + 2048 * 154880) * 2
    experts = 7 * 64 * (1 - (15 / 16) ** 32) * 9_437_184 * 2
    assert costs.decode_step_bytes(m, 0) == int(everyone + experts)
    assert costs.decode_step_bytes(m, 80_000) == int(everyone + experts + 80_000 * 9216)
    assert 9.3e9 < costs.decode_step_bytes(m, 80_000) < 9.5e9


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cell = registry.load_cell(manifest, ROLLOUT)
    config = registry.load_architecture(cell, "config")
    model = config.model_config(cell["config"], 4096, "bfloat16")
    assert (model["kv_lora_rank"], model["experts_per_token"], model["first_dense_layers"]) == (512, 4, 1)
    assert model["n_layers"] == 8 and model["rope_theta"] == 1e6 and model["d_expert"] == 1536
    for key, other in (("n_group", 2), ("norm_topk_prob", False), ("rope_scaling", {"type": "yarn"}),
                       ("partial_rotary_factor", 0.5), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            config.model_config(dict(cell["config"], **{key: other}), 4096, "bfloat16")
    # A program from before PR 32 (the parent the driver tries the new cell on) is refused in
    # the driver process, at once, by name of what it lacks.
    monkeypatch.setattr(config, "_program_fields", lambda: {"vocab_size", "d_model", "n_layers", "n_heads"})
    with pytest.raises(NotImplementedError, match="kv_lora_rank.*cannot run latent attention and routed experts"):
        config.model_config(cell["config"], 4096, "bfloat16")


ALONE = 2 * 3 * 127 + 10  # each of three prompts twice, 128 new tokens of which 127 by a step; five pairs of probes


def _result(manifest, **over):
    """What the six readers see of a traced run, by hand."""
    cell = registry.load_cell(manifest, ROLLOUT)
    cell["config"]["trace_ops"] = {
        "moe_experts": r"^%ragged-dot\S* custom-call bf16\[128,",
        "latent_attention": r"\[32,4096,640\]|\[32,20,1,4096\]",
    }
    fields = ["t_start_ns", "rows", "context_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 32, 80_000 + 1000 * i, 10**6] for i in range(4)]
    result = {
        "cell": cell, "seconds": 51.0, "traced": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {
            # 100 steps under traffic, and the check's and the probes' steps of one row (4 experts, 1 token)
            "moe": {"decode": {"steps": 100 + ALONE, "assignments": [], "experts_touched": [5600 + 4 * ALONE] * 7,
                               "fullest_expert_load": [600 + ALONE] * 6 + [670 + ALONE]},
                    "prefill": {"steps": 3, "assignments": [], "experts_touched": [192] * 7,
                                "fullest_expert_load": [150] * 7}},
            "spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]},
        },
        "trace": {"devices": [{
            "programs": {"decode": [0.02] * 10, "prefill": [0.03]},
            "ops": [["%ragged-dot-none.2 custom-call bf16[128,1536]", 0.030],
                    ["%ragged-dot-none.1 custom-call bf16[128,1536]", 0.030],
                    ["%ragged-dot-none custom-call bf16[128,2048]", 0.040],
                    ["%ragged-dot-none.5 custom-call bf16[2048,1536]", 0.5],
                    ["%fusion.7 fusion bf16[32,4096,640]", 0.020], ["%fusion.9 fusion f32[32,20,1,4096]", 0.010]],
        }]},
    }
    result.update(over)
    return result


def test_the_six_readers_on_a_result_written_by_hand(manifest):
    result = _result(manifest)
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert read("moe_experts_touched_mean") == pytest.approx(56.0)
    assert read("moe_expert_load_max_mean") == pytest.approx(6.1)
    assert read("moe_experts_ms") == pytest.approx(10.0)  # 0.1 s over 10 steps; the chunk's operations are not taken
    assert read("moe_experts_roofline") == pytest.approx(100 * 7 * 56 * 9_437_184 * 2 / 819e9 / 0.010)
    assert read("latent_attention_ms") == pytest.approx(3.0)
    assert read("latent_attention_roofline") == pytest.approx(100 * 81_500 * 9216 / 819e9 / 0.003)
    assert 0 < read("moe_experts_roofline") <= 100 and 0 < read("latent_attention_roofline") <= 100


@pytest.mark.parametrize("lacking", ["counters", "trace_ops", "ops", "context_tokens"])
def test_a_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """The parent of PR 32 under PR 32's benchmark files: no expert counters,
    no ``context_tokens``; and a configuration that names no operations."""
    result = _result(manifest)
    names = ["moe_experts_touched_mean", "moe_expert_load_max_mean", "moe_experts_ms", "moe_experts_roofline",
             "latent_attention_ms", "latent_attention_roofline"]
    if lacking == "counters":
        result["counters"] = {"spans": result["counters"]["spans"]}
        silent = {"moe_experts_touched_mean", "moe_expert_load_max_mean", "moe_experts_roofline"}
    elif lacking == "trace_ops":
        result["cell"] = dict(result["cell"], config={k: v for k, v in result["cell"]["config"].items() if k != "trace_ops"})
        silent = set(names[2:])
    elif lacking == "ops":
        result["trace"] = {"devices": [{"programs": {"decode": [0.02]}, "ops": [["%fusion.1 fusion f32[7]", 1.0]]}]}
        silent = set(names[2:])
    else:
        fields = result["counters"]["spans"]["fields"]["iterations"]
        fields[fields.index("context_tokens")] = "view_blocks"
        silent = {"latent_attention_roofline"}
    for name in names:
        value = registry.load_metric("per_layer", name)(result)
        assert (value is None) == (name in silent), (name, value)


@pytest.mark.parametrize("workload", [ROLLOUT, LONG_PROMPT])
def test_each_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path, workload):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check
    against the architecture's own float32 reference, the mix, the line."""
    cell = toy_cell(manifest, workload)
    if workload == ROLLOUT:  # the toy engine has 4 slots: as many clients as slots, as in the cell
        cell["traffic"]["arrival"]["clients"] = 4
    result = bench_run.measure(
        cell, seed=2**31 + 32, seconds=2.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    gaps = result["notes"]["reference_gaps"]
    assert len(gaps) == 2 and all(g["finite"] and g["max_gap"] <= cell["config"]["check"]["logit_gap_tol"] for g in gaps)
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, workload, traced=False, platform="cpu")
    counters = result["counters"]
    assert counters["kv_pool_not_donated"] == 0 and counters["host_logit_rows"] == 0
    names = counters["spans"]["fields"]["iterations"]
    width = len(names)
    context = counters["spans"]["iterations"][names.index("context_tokens")::width]
    rows = counters["spans"]["iterations"][names.index("rows")::width]
    assert all((c > 0) == (r > 0) and c >= r for c, r in zip(context, rows)) and any(context)
    if workload == ROLLOUT:
        moe, toy = counters["moe"], cell["config"]
        layers = toy["num_hidden_layers"] - toy["first_k_dense_replace"]
        assert counters["kv_token_bytes"] == toy["num_hidden_layers"] * 128 * 2  # 32 + 16 padded to the lanes
        for kind in ("decode", "prefill"):
            assert moe[kind]["steps"] > 0 and len(moe[kind]["assignments"]) == layers
            sent = [sum(per_expert) for per_expert in moe[kind]["assignments"]]
            assert len(set(sent)) == 1 and sent[0] % toy["num_experts_per_tok"] == 0  # every layer saw the same tokens
        assert 1 <= registry.load_metric("per_layer", "moe_experts_touched_mean")(result) <= toy["n_routed_experts"]
        assert registry.load_metric("per_layer", "moe_expert_load_max_mean")(result) >= 1
    else:
        assert "moe" not in counters and counters["kv_token_bytes"] == 2 * 2 * 2 * 32 * 2  # k and v, 2 layers, 2 heads of 32
