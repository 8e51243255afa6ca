"""The third architecture of the benchmark (PR 35), ``AfmoeForCausalLM``
(Trinity-Mini: window and full attention mixed by layer, gated QK-normed GQA,
sigmoid top-8 of 128 experts with a shared expert behind a leading dense
layer), and the cell PR 35 adds: the configuration against the catalog's
numbers, the counts against hand arithmetic and against the parameter tree the
program draws, the cell at a toy size through ``run.measure`` on the CPU (and,
beside it, sessions behind shared prefixes on the dense model: the mix of a
cell the benchmark cannot judge yet, PERF.md section 7), the two new per-layer
metrics' readers, and what stands in for two more tests of
``tests/benchmark/`` that a third architecture and a long mix made wrong
(``tests/conftest.py`` marks those)."""

import copy
import json
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic

TRINITY, ROLLOUT = "trinity-mini-serve5", "trinity5.rollout-longctx"
# ISSUE 35's ``serve16.sessions-prefix`` mix. It is no file and no cell: the client makes a follow-up due 2 s after
# the reply before it ENDS, so three arrivals in four move with the service and ``ttft_p90_ms``, which would judge the
# prefix cache, does not repeat (PERF.md section 7). It is sent here, at a toy size, because nothing else drives the
# block-hash prefix cache through the harness's sessions.
SESSIONS = {
    "kind": "serve", "arrival": {"process": "poisson", "rate_per_s": 0.5}, "preroll_s": 8.0, "grace_s": 5.0,
    "prompt_len": {"dist": "uniform", "min": 640, "max": 896}, "output_len": {"dist": "uniform", "min": 48, "max": 96},
    "stratified": True, "shared_prefix": {"groups": 4, "prefix_len": 512, "share": 1.0},
    "sessions": {"turns": 4, "think_time_s": 2.0, "growth": {"dist": "uniform", "min": 32, "max": 96}},
    "sampling": {"sampled_share": 0.5, "temperature": 0.7, "top_k": 50}, "schedule_seed": 35,
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json as the
# model-configs catalog quotes it (every key of the row's ``config``).
SLIDING, FULL = "sliding_attention", "full_attention"
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
# The two other architectures' published keys are held by the tests that came with them.
REDUCED = {
    "mistral-7b-v0.1-serve16": ["num_hidden_layers"], "mistral-7b-v0.1-train2": ["num_hidden_layers"],
    "glm-4.7-flash-serve8": ["num_hidden_layers"], TRINITY: ["num_hidden_layers", "num_dense_layers", "layer_types"],
}


def _config(manifest, name):
    cell = next(w["name"] for w in manifest["workloads"] if w["config"] == name)
    return registry.load_cell(manifest, cell)["config"]


def _costs():
    return registry.load_architecture(
        {"name": "these tests", "architecture": "AfmoeForCausalLM", "bench_dir": registry.BENCH_DIR}, "costs"
    )


def test_each_configuration_holds_its_own_published_keys(manifest):
    """Stands in for test_bench_glm.py::test_each_configuration_holds_its_own_published_widths, which looks
    every configuration's architecture up in a table of two and holds every ``reduced`` to the depth alone, and
    is marked xfail (strict) in tests/conftest.py since the third cuts its leading dense layers and its list of
    layer types with its depth. Each configuration is held to its own published keys here, by architecture."""
    import test_bench_glm as glm

    published = {"MistralForCausalLM": glm.MISTRAL, "Glm4MoeLiteForCausalLM": glm.PUBLISHED, "AfmoeForCausalLM": PUBLISHED}
    assert {c["name"] for c in manifest["configs"]} == set(REDUCED)
    for cfg in manifest["configs"]:
        held = _config(manifest, cfg["name"])
        assert held["reduced"] == cfg["reduced"] == REDUCED[cfg["name"]]
        assert held["source"] == cfg["source"] and set(held["published"]) == set(held["reduced"])
        for key, value in published[held["architectures"][0]].items():
            if key not in held["reduced"]:
                assert held[key] == value and type(held[key]) is type(value), (cfg["name"], key)
    # and the catalog's own row, where this machine has the catalog
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    except OSError:
        row = None
    if row is not None:
        assert row["config"] == PUBLISHED and row["source_url"] == _config(manifest, TRINITY)["source"]


def test_the_cut_is_of_depth_alone_and_keeps_a_whole_period(manifest):
    m = _config(manifest, TRINITY)
    assert (m["num_hidden_layers"], m["num_dense_layers"]) == (5, 1)
    assert m["layer_types"] == PUBLISHED["layer_types"][:5] == [SLIDING, SLIDING, SLIDING, FULL, SLIDING]
    expert_layers = m["layer_types"][m["num_dense_layers"]:]
    assert len(expert_layers) >= 4 and expert_layers.count(SLIDING) == 3 * expert_layers.count(FULL)  # 3 : 1, as published
    assert m["published"]["num_hidden_layers"] == 32 and m["published"]["num_dense_layers"] == 2
    for text in ("cut", "assumed"):
        assert m[text]
    for mechanism in ("gated attention", "query and key norms", "four norms a layer", "rotary in window layers only",
                      "mup_enabled", "routing", "expert_bias", "rotary layout", "torch_dtype", "architectures"):
        assert mechanism in m["assumed"], mechanism
    assert m["deployment"]["engine"] == dict(
        num_slots=32, block_size=16, max_model_len=9216, num_blocks=32 * 576 + 1, prefill_chunk=512
    )
    check = m["check"]
    assert check["prompt_lens"] == [128, 1536, 3072] and check["new_tokens"] == 128
    assert check["prompt_lens"][-1] + check["new_tokens"] > m["sliding_window"] + 512 + 16  # the ring wraps in the check
    assert set(m["trace_ops"]) == {"moe_experts", "cache_attention", "why"}


def test_the_mix_is_the_issues(manifest):
    rollout = registry.load_cell(manifest, ROLLOUT)["traffic"]
    assert rollout["arrival"] == {"process": "closed", "clients": 32, "requests_per_client": 4}
    assert rollout["sampling"] == {"sampled_share": 1.0, "temperature": 1.0, "top_k": 0}
    assert rollout["stratified"] is True and rollout["schedule_seed"] == 35
    assert (rollout["grace_s"], rollout["trace_slice_s"]) == (5.0, 3.0) and 16.0 <= rollout["preroll_s"] <= 24.0
    # ISSUE 35 lets both ends of prompt_len move by up to 512, the outputs and max_model_len with them
    lo, hi = rollout["prompt_len"]["min"], rollout["prompt_len"]["max"]
    assert rollout["prompt_len"]["dist"] == rollout["output_len"]["dist"] == "uniform"
    assert hi - lo == 512 and abs(lo - 3072) <= 512 and "lengths_why" in rollout
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(ROLLOUT, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    new = {"cache_attention_ms", "cache_attention_roofline"}
    assert want(ROLLOUT, True) == (want("glm8.rollout-long", True) - {"latent_attention_ms", "latent_attention_roofline"}) | new
    for w in manifest["workloads"]:
        assert bool(new & want(w["name"], True)) == (w["name"] == ROLLOUT)
    assert [m["name"] for m in manifest["per_layer"][-2:]] == ["cache_attention_ms", "cache_attention_roofline"]
    assert manifest["workloads"][-1]["name"] == ROLLOUT and manifest["workloads"][-1]["chips"] == 1
    # ISSUE 35's second cell, serve16.sessions-prefix, is left out: its ttft_p90_ms does not repeat (PERF.md section 7)
    assert len(manifest["workloads"]) == 7 and [c["name"] for c in manifest["configs"]][-1] == TRINITY


def test_every_mix_fits_the_cells_that_send_it(manifest):
    """Stands in for test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[rollout-longctx], which holds
    every serving mix under 2560 tokens a request (marked xfail, strict, in tests/conftest.py for the mix that runs
    under 9216): a request fits the ``max_model_len`` of every cell that sends it, every seed offers the same load,
    and a session's last turn fits too."""
    for w in manifest["workloads"]:
        cell = registry.load_cell(manifest, w["name"])
        if cell["config"]["path"] != "serve":
            continue
        limit, vocab = cell["config"]["deployment"]["engine"]["max_model_len"], cell["config"]["vocab_size"]
        plans = [traffic.schedule(cell["traffic"], seed, 20, vocab) for seed in (1, 2, 2**31 + 99)]
        assert len({traffic.offered_tokens(p) for p in plans}) == 1
        for plan in plans:
            reqs = plan["open"] if "open" in plan else [r for c in plan["closed"] for r in c]
            for r in reqs:
                tokens, most = len(r["tokens"]), len(r["tokens"]) + r["max_new_tokens"]
                for follow in r.get("followups", ()):
                    tokens = most + len(follow["new_tokens"])
                    most = tokens + follow["max_new_tokens"]
                assert most <= limit, w["name"]
    mix, vocab = registry.load_cell(manifest, ROLLOUT)["traffic"], 200192
    a, b = (traffic.schedule(mix, seed, 51, vocab)["closed"] for seed in (3, 2**31 + 5))
    lengths = lambda plan: [[(len(r["tokens"]), r["max_new_tokens"]) for r in c] for c in plan]  # noqa: E731
    assert lengths(a) == lengths(b) and a[0][0]["tokens"] != b[0][0]["tokens"]  # schedule_seed pins the lengths
    firsts = [c[0] for c in a]
    assert len(firsts) == 32 and all(lo <= len(r["tokens"]) <= hi for r in firsts
                                     for lo, hi in [(mix["prompt_len"]["min"], mix["prompt_len"]["max"])])
    # every first request outlasts the window at 75 tokens a second a row and ends under the 8192-token rung
    assert min(r["max_new_tokens"] for r in firsts) > 75 * 51
    assert max(len(r["tokens"]) + r["max_new_tokens"] for r in firsts) <= 8192 + 512
    # sessions behind shared prefixes (no cell sends them yet): the last turn fits the dense model's 2560 too
    sessions = traffic.schedule(SESSIONS, 7, 51, 32000)["open"]
    prefixes = {tuple(r["tokens"][:512]) for r in sessions}
    assert len(prefixes) == 4 and all(len(r["followups"]) == 3 for r in sessions)
    assert max(len(r["tokens"]) + r["max_new_tokens"] + sum(len(f["new_tokens"]) + f["max_new_tokens"] for f in r["followups"])
               for r in sessions) <= 1568


def test_the_counts_against_hand_arithmetic(manifest):
    costs, m = _costs(), _config(manifest, TRINITY)
    # queries and their gate 2048x4096 each, keys and values 2048x512 each, output 4096x2048
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert costs.attention_params(m) == attention == 27_262_976
    assert costs.expert_params(m) == 3 * 2048 * 1024 == 6_291_456
    assert costs.expert_layers(m) == 4 and costs.kind_layers(m, FULL) == 1 and costs.kind_layers(m, SLIDING) == 4
    dense = attention + 3 * 2048 * 6144
    shared = attention + 6_291_456 + 2048 * 128
    norms = 4 * 2048 + 2 * 128
    total = dense + norms + 4 * (shared + 128 * (6_291_456 + 1) + norms) + 2 * 200192 * 2048 + 2048
    assert costs.n_params(m) == total and round(total / 1e9, 2) == 4.24
    assert round(costs.weight_bytes(m) / 1e9, 2) == 8.49
    assert round((shared + 128 * 6_291_456) / 1e6, 1) == 839.1  # an expert layer, as ISSUE 35 reckons it
    token = 2 * 4 * 128 * 2
    assert costs.kv_bytes_per_token(m) == 5 * token == 10_240
    assert costs.kv_bytes_per_token(m, FULL) == token and costs.kv_bytes_per_token(m, SLIDING) == 4 * token
    assert costs.expected_experts_touched(m, 32) == pytest.approx(128 * (1 - (15 / 16) ** 32))
    assert costs.expected_experts_touched(m, 1) == pytest.approx(8.0)
    assert costs.moe_experts_bytes(m, 112.0) == 4 * 112 * 6_291_456 * 2
    # 32 rows of 6000 tokens: the full layer reads them all, a window layer 2048 a row
    assert costs.cache_attention_bytes(m, 192_000, 65_536) == 192_000 * token + 65_536 * 4 * token
    everyone = (dense + 4 * shared + 2048 * 200192) * 2
    experts = 4 * 128 * (1 - (15 / 16) ** 32) * 6_291_456 * 2
    assert costs.decode_step_bytes(m, 0) == int(everyone + experts)
    assert costs.decode_step_bytes(m, 192_000) == int(everyone + experts + 192_000 * token + 32 * 2048 * 4 * token)
    assert costs.decode_step_bytes(m, 32 * 100) == int(everyone + experts + 3200 * 5 * token)  # under a window: all of it
    assert 7.6e9 < costs.decode_step_bytes(m, 192_000) < 8.0e9
    alone = costs.moe_steps_alone(m, traced=True)
    assert alone == {"steps": 2 * 3 * 127 + 10, "experts_touched": 8, "fullest_expert_load": 1}


def test_the_counts_are_the_drawn_parameter_trees(manifest):
    """``costs.py`` against what the program draws: leaf for leaf at a toy
    size, and by shape alone (nothing is drawn) at the published widths."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params, num_params

    costs = _costs()
    cell = registry.load_cell(manifest, ROLLOUT)
    config = registry.load_architecture(cell, "config")
    toy = toy_cell(manifest, ROLLOUT)["config"]

    def program_config(m):
        model = config.model_config(m, 256, "bfloat16")
        model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        return TransformerConfig(**model)

    for m in (toy, cell["config"]):
        cfg = program_config(m)
        shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))  # noqa: B023
        assert num_params(shapes) == costs.n_params(m)
        assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) == costs.weight_bytes(m)
        assert cfg.layer_kinds == ("window", "window", "window", "full", "window")
        assert cfg.head_dim * cfg.n_heads == 2 * cfg.d_model  # heads twice as wide as the hidden size holds
    drawn = init_params(jax.random.PRNGKey(0), program_config(toy))
    assert num_params(drawn) == costs.n_params(toy)
    # the embedding is drawn 1 / multiplier wide, so that multiplied it has unit scale
    assert float(jnp.std(drawn["embed"].astype(jnp.float32))) == pytest.approx(toy["hidden_size"] ** -0.5, rel=0.05)


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cell = registry.load_cell(manifest, ROLLOUT)
    config = registry.load_architecture(cell, "config")
    model = config.model_config(cell["config"], 9216, "bfloat16")
    assert (model["head_dim"], model["n_heads"], model["n_kv_heads"], model["sliding_window"]) == (128, 32, 4, 2048)
    assert model["layer_kinds"] == ["window", "window", "window", "full", "window"]
    assert (model["experts_per_token"], model["num_experts"], model["first_dense_layers"]) == (8, 128, 1)
    assert model["embed_multiplier"] == 2048 ** 0.5 and model["routed_scaling_factor"] == 2.826
    assert model["attn_gate"] and model["qk_norm"] and model["post_norms"]
    for key, other in (("n_group", 2), ("topk_group", 2), ("num_limited_groups", 4), ("num_expert_groups", 8),
                       ("score_func", "softmax"), ("rope_scaling", {"type": "yarn"}), ("route_norm", False)):
        with pytest.raises(ValueError, match=key):
            config.model_config(dict(cell["config"], **{key: other}), 9216, "bfloat16")
    with pytest.raises(ValueError, match="layer_types names 4 layers"):
        config.model_config(dict(cell["config"], layer_types=[SLIDING] * 4), 9216, "bfloat16")
    with pytest.raises(ValueError, match="chunked_attention"):
        config.model_config(dict(cell["config"], layer_types=[SLIDING] * 4 + ["chunked_attention"]), 9216, "bfloat16")
    assert config.model_config(dict(cell["config"], mup_enabled=False), 9216, "bfloat16")["embed_multiplier"] == 1.0
    # A program from before PR 35 (the parent the driver tries the new cell on) is refused in the
    # driver process, at once, by name of what it lacks.
    monkeypatch.setattr(config, "_program_fields", lambda: set(model) - {"layer_kinds", "head_dim", "qk_norm"})
    with pytest.raises(NotImplementedError, match="no head_dim, layer_kinds, qk_norm: it cannot run a layer pattern"):
        config.model_config(cell["config"], 9216, "bfloat16")


def _result(manifest, **over):
    """What the two new readers see of a traced run, by hand."""
    cell = registry.load_cell(manifest, ROLLOUT)
    cell["config"]["trace_ops"] = {"cache_attention": r"bf16\[(5152|16384),16,4,128\]|bf16\[32,1,4,8,128\]$"}
    fields = ["t_start_ns", "rows", "context_tokens", "window_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 32, 190_000 + 2000 * i, 65_536, 10**6] for i in range(3)]
    result = {
        "cell": cell, "seconds": 51.0, "traced": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {"spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}},
        "trace": {"devices": [{
            "programs": {"decode": [0.015] * 10, "prefill": [0.1]},
            "ops": [["%fusion.20 fusion bf16[16384,16,4,128]", 0.008], ["%fusion.19 fusion bf16[16384,16,4,128]", 0.008],
                    ["%fusion.10 fusion bf16[5152,16,4,128]", 0.012], ["%fusion.463 fusion bf16[32,1,4,8,128]", 0.002],
                    ["%fusion.77 fusion bf16[576,16,4,128]", 0.5], ["%ragged-dot-none.2 custom-call bf16[256,1024]", 0.09]],
        }]},
    }
    result.update(over)
    return result


def test_the_two_new_readers_on_a_result_written_by_hand(manifest):
    result = _result(manifest)
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert read("cache_attention_ms") == pytest.approx(3.0)  # 0.03 s over 10 steps; the chunk's gather is not taken
    least = 192_000 * 2048 + 65_536 * 4 * 2048
    assert read("cache_attention_roofline") == pytest.approx(100 * least / 819e9 / 0.003)
    assert 0 < read("cache_attention_roofline") <= 100


@pytest.mark.parametrize("lacking", ["trace_ops", "ops", "window_tokens", "spans"])
def test_a_new_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """The parent of PR 35 under PR 35's benchmark files keeps no
    ``window_tokens``; and a configuration that names no operations."""
    result = _result(manifest)
    silent = {"cache_attention_roofline"}
    if lacking == "trace_ops":
        result["cell"] = dict(result["cell"], config={k: v for k, v in result["cell"]["config"].items() if k != "trace_ops"})
        silent = {"cache_attention_ms", "cache_attention_roofline"}
    elif lacking == "ops":
        result["trace"] = {"devices": [{"programs": {"decode": [0.02]}, "ops": [["%fusion.1 fusion f32[7]", 1.0]]}]}
        silent = {"cache_attention_ms", "cache_attention_roofline"}
    elif lacking == "window_tokens":
        fields = result["counters"]["spans"]["fields"]["iterations"]
        fields[fields.index("window_tokens")] = "view_blocks"
    else:
        result["counters"] = {}
    for name in ("cache_attention_ms", "cache_attention_roofline"):
        value = registry.load_metric("per_layer", name)(result)
        assert (value is None) == (name in silent), (name, value)


@pytest.mark.parametrize("workload, mix", [(ROLLOUT, None), ("serve16.long-prompt", SESSIONS)],
                         ids=["rollout-longctx", "sessions-prefix"])
def test_the_new_cell_and_sessions_run_at_a_toy_size_against_their_reference(manifest, fake_chips, tmp_path, workload, mix):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check
    against the architecture's own float32 reference, the mix, the line."""
    cell = toy_cell(manifest, workload)
    if mix is None:  # the toy engine has 4 slots: as many clients as slots, as in the cell
        cell["traffic"]["arrival"]["clients"] = 4
    else:  # the dense model's cell under a toy session: a prefix of two blocks, turns that fit 256 tokens
        cell["traffic"] = copy.deepcopy(mix)
        cell["traffic"].update(trace_slice_s=0.5, preroll_s=0.5, arrival={"process": "poisson", "rate_per_s": 4.0})
        cell["traffic"]["shared_prefix"]["prefix_len"] = 32
        cell["traffic"]["prompt_len"].update(min=40, max=60)
        cell["traffic"]["output_len"].update(min=4, max=12)
        cell["traffic"]["sessions"].update(think_time_s=0.1, growth={"dist": "uniform", "min": 8, "max": 24})
    result = bench_run.measure(
        cell, seed=2**31 + 35, seconds=3.0, traced=False, t_process=time.monotonic(),
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
    column = lambda name: counters["spans"]["iterations"][names.index(name)::len(names)]  # noqa: E731
    context, window, rows = column("context_tokens"), column("window_tokens"), column("rows")
    assert any(context) and all(r <= w <= c for r, w, c in zip(rows, window, context))
    groups = counters["kv_groups"]
    if mix is None:
        toy = cell["config"]
        ring = -(-(toy["sliding_window"] + 32) // 16) + 1  # the engine's default chunk of 32, blocks of 16
        token = 2 * toy["num_key_value_heads"] * toy["head_dim"] * 2
        assert groups["window"] == dict(kv_token_bytes=4 * token, num_blocks=4 * ring, ring_blocks=ring,
                                        blocks_in_use=groups["window"]["blocks_in_use"])
        assert groups["full"]["kv_token_bytes"] == token and counters["kv_token_bytes"] == 5 * token
        assert any(w < c for w, c in zip(window, context))  # rows longer than the window: it bites
        assert (counters["prefix_hit_blocks"], counters["prefix_miss_blocks"], counters["cached_blocks"]) == (0, 0, 0)
        moe = counters["moe"]
        for kind in ("decode", "prefill"):
            assert moe[kind]["steps"] > 0 and len(moe[kind]["assignments"]) == 4
            sent = [sum(per_expert) for per_expert in moe[kind]["assignments"]]
            assert len(set(sent)) == 1 and sent[0] % 8 == 0  # every layer saw the same tokens, eight experts each
        assert 8 <= registry.load_metric("per_layer", "moe_experts_touched_mean")(result) <= 128
    else:
        assert set(groups) == {"full"}  # one group without a pattern: Mistral's window is a mask over blocks it holds
        # the prefix cache did the work: first turns hit their group's two prefix blocks, later turns their history
        assert counters["prefix_hit_blocks"] > counters["prefix_miss_blocks"] > 0
