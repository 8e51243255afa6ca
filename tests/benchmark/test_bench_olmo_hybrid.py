"""The fourth architecture of the benchmark (PR 41), ``OlmoHybridForCausalLM``
(Olmo-Hybrid-7B: gated-delta-rule linear-attention layers three to one among
full-attention layers, a recurrent state a slot beside the paged cache), and the
cell PR 41 adds: the configuration against the catalog's numbers, the counts
against hand arithmetic and against the parameter tree the program draws, the
cell at a toy size through ``run.measure`` on the CPU, the four new per-layer
metrics' readers, and what stands in for the tests of ``tests/benchmark/`` that
a fourth configuration and a mix of 8k-token requests made wrong
(``tests/conftest.py`` marks those). Nothing here pins the END of a list that a
later PR may append to: a new entry is held to come AFTER the ones it was
appended behind."""

import json
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, traffic

OLMO, CELL, MIX = "olmo-hybrid-7b-serve16", "olmo16.longdoc-8k", "longdoc-8k"
ARCH = "OlmoHybridForCausalLM"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LINEAR, FULL = "linear_attention", "full_attention"
# https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json as the
# model-configs catalog quotes it (every key of the row's ``config``).
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}
NEW_METRICS = {
    "linear_state_ms": ("ms", "lower", "itl_p95_ms"), "linear_state_roofline": ("%", "higher", "itl_p95_ms"),
    "linear_scan_ms": ("ms", "lower", "ttft_p90_ms"), "linear_scan_roofline": ("%", "higher", "ttft_p90_ms"),
}
# The accepted per-layer metrics ISSUE 41 lists for the cell, beside its own four.
SHARED_METRICS = {
    "replica_ready_s", "replica_params_s", "serve_path_overhead_ms", "serve_ingress_p90_ms", "queue_wait_p90_ms",
    "prefill_span_p90_ms", "engine_host_gap_ms", "engine_iteration_ms", "engine_fetch_ms", "engine_sample_ms",
    "engine_build_ms", "engine_admit_ms", "engine_emit_ms", "slot_occupancy_pct", "decode_rows_mean", "decode_step_ms",
    "decode_mfu_roofline", "prefill_chunk_ms", "compiles_in_window", "device_idle_pct.serve", "cache_attention_ms",
    "cache_attention_roofline",
    # a token's way back (PR 38's seven): the cell streams through the same proxy, and eight streams were chosen so
    # that the proxy's poll round does not make up ``itl_p95_ms``: these say whether it does
    "itl_emit_p95_ms", "itl_socket_p95_ms", "deliver_wake_p95_ms", "deliver_pickup_p95_ms", "deliver_reply_p95_ms",
    "deliver_write_p95_ms", "replica_gc_pause_ms_per_s",
}


def _config(manifest, name=OLMO):
    cell = next(w["name"] for w in manifest["workloads"] if w["config"] == name)
    return registry.load_cell(manifest, cell)["config"]


def _costs():
    return registry.load_architecture({"name": "these tests", "architecture": ARCH, "bench_dir": registry.BENCH_DIR}, "costs")


def test_each_configuration_holds_its_own_published_keys(manifest):
    """Stands in for test_bench_trinity.py::test_each_configuration_holds_its_own_published_keys, whose table names
    three architectures and whose ``REDUCED`` names three models' configurations, and which is marked xfail (strict)
    in tests/conftest.py since the fourth. Each configuration is held to its own published keys here, by
    architecture; one this table does not know yet is held to state ``reduced`` and ``published`` alike, no more."""
    import test_bench_glm as glm
    import test_bench_trinity as trinity

    published = {"MistralForCausalLM": glm.MISTRAL, "Glm4MoeLiteForCausalLM": glm.PUBLISHED,
                 "AfmoeForCausalLM": trinity.PUBLISHED, ARCH: PUBLISHED}
    reduced = dict(trinity.REDUCED, **{OLMO: ["num_hidden_layers", "layer_types"]})
    assert set(reduced) <= {c["name"] for c in manifest["configs"]}
    for cfg in manifest["configs"]:
        held = _config(manifest, cfg["name"])
        assert held["reduced"] == cfg["reduced"] == reduced.get(cfg["name"], cfg["reduced"])
        assert held["source"] == cfg["source"] and set(held["published"]) == set(held["reduced"])
        for key, value in published.get(held["architectures"][0], {}).items():
            if key not in held["reduced"]:
                assert held[key] == value and type(held[key]) is type(value), (cfg["name"], key)
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    except OSError:
        row = None
    if row is not None:
        assert row["config"] == PUBLISHED and row["source_url"] == _config(manifest)["source"]


def test_the_cut_is_of_depth_alone_and_keeps_four_whole_periods(manifest):
    m = _config(manifest)
    assert m["num_hidden_layers"] == 16 and m["layer_types"] == PUBLISHED["layer_types"][:16] == [LINEAR, LINEAR, LINEAR, FULL] * 4
    assert m["published"]["num_hidden_layers"] == 32 and m["architectures"] == [ARCH] and m["path"] == "serve"
    assert m["torch_dtype"] == "bfloat16" and m["deployment"]["param_dtype"] == "bfloat16"
    for text in ("cut", "assumed"):
        assert m[text]
    for mechanism in ("architectures", "torch_dtype", "block layout", "query and key norms", "no rotary", "convolution",
                      "gates", "A_log, dt_bias and the [192] norm weight", "state in float32", "weights"):
        assert mechanism in m["assumed"], mechanism
    for key in ("architectures", "torch_dtype", "block layout", "query and key norms", "no rotary", "convolution", "gates"):
        assert "(not re-read, no network)" in m["assumed"][key], key
    assert m["deployment"]["engine"] == dict(
        num_slots=8, block_size=16, max_model_len=8192, num_blocks=8 * 512 + 1, prefill_chunk=512
    )
    check = m["check"]
    assert check["prompt_lens"] == [200, 1400, 6144] and check["new_tokens"] == 128
    assert (check["probe_len"], check["probe_pairs"]) == (128, 5) and 0 < check["logit_gap_tol"] < 1 and check["logit_gap_tol_why"]
    # the state a slot carries is held by a limit of its own, between the served system's reading and the bfloat16 state's
    assert 0.00265 < check["state_gap_tol"] < 0.00877 and "0.00263-0.00265" in check["state_gap_tol_why"]
    # a padded single chunk; three chunks with a padded last; twelve whole chunks through one carried state
    assert [(-(-n // 512), n % 512 != 0) for n in check["prompt_lens"]] == [(1, True), (3, True), (12, False)]
    assert check["prompt_lens"][-1] + check["new_tokens"] <= m["deployment"]["engine"]["max_model_len"]
    assert set(m["trace_ops"]) == {"linear_state", "linear_scan", "cache_attention", "why"}
    assert set(m["trace_programs"]) == {"decode", "prefill"}


def test_the_mix_is_the_issues_and_fits_the_cell(manifest):
    cell = registry.load_cell(manifest, CELL)
    mix, engine = cell["traffic"], cell["config"]["deployment"]["engine"]
    assert mix["arrival"] == {"process": "closed", "clients": 8, "requests_per_client": 16}
    assert mix["arrival"]["clients"] == engine["num_slots"]
    assert mix["prompt_len"] == {"dist": "uniform", "min": 6144, "max": 7680}
    assert mix["output_len"] == {"dist": "uniform", "min": 192, "max": 256}
    assert mix["sampling"]["sampled_share"] == 0.0 and mix["stratified"] is True
    assert (mix["preroll_s"], mix["grace_s"], mix["schedule_seed"]) == (12.0, 5.0, 41)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 7936 < engine["max_model_len"] == 8192
    assert [mix["prompt_len"][k] // engine["prefill_chunk"] for k in ("min", "max")] == [12, 15]
    vocab = cell["config"]["vocab_size"]
    a, b = (traffic.schedule(mix, seed, 51, vocab)["closed"] for seed in (3, 2**31 + 5))
    lengths = lambda plan: [[(len(r["tokens"]), r["max_new_tokens"]) for r in c] for c in plan]  # noqa: E731
    assert lengths(a) == lengths(b) and a[0][0]["tokens"] != b[0][0]["tokens"]  # schedule_seed pins the lengths
    assert len(a) == 8 and all(len(c) == 16 for c in a)
    for r in (r for c in a for r in c):
        assert 6144 <= len(r["tokens"]) <= 7680 and 192 <= r["max_new_tokens"] <= 256 and r["temperature"] == 0.0
        assert len(r["tokens"]) + r["max_new_tokens"] <= engine["max_model_len"]
        # every decode step of the window runs at one rung: the ladder's last doubling is 4096 tokens
        assert len(r["tokens"]) > 4096


def test_every_mix_fits_the_cells_that_send_it(manifest):
    """Stands in for test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[longdoc-8k], which holds every
    serving mix under 2560 tokens a request (marked xfail, strict, in tests/conftest.py for the mix that runs under
    8192): a request fits the ``max_model_len`` of every cell that sends it, and every seed offers the same load."""
    for w in manifest["workloads"]:
        cell = registry.load_cell(manifest, w["name"])
        if cell["config"]["path"] != "serve":
            continue
        limit, vocab = cell["config"]["deployment"]["engine"]["max_model_len"], cell["config"]["vocab_size"]
        plans = [traffic.schedule(cell["traffic"], seed, 20, vocab) for seed in (1, 2, 2**31 + 99)]
        assert len({traffic.offered_tokens(p) for p in plans}) == 1
        for plan in plans:
            reqs = plan["open"] if "open" in plan else [r for c in plan["closed"] for r in c]
            assert all(len(r["tokens"]) + r["max_new_tokens"] <= limit for r in reqs), w["name"]
    mix = registry.load_cell(manifest, CELL)["traffic"]
    plans = [traffic.schedule(mix, seed, 20, 32000) for seed in (1, 2, 2**31 + 99)]
    flat = lambda plan: [r for c in plan["closed"] for r in c]  # noqa: E731
    assert len({(tuple(sorted(len(r["tokens"]) for r in flat(p))), tuple(sorted(r["max_new_tokens"] for r in flat(p)))) for p in plans}) == 1
    assert all(0 <= t < 32000 for p in plans for r in flat(p) for t in r["tokens"])


def test_the_new_entries_are_appended_behind_what_was_there(manifest):
    """Stands in for the pins on the lists' ENDS of test_bench_delivery.py (``names[-7:]``, seven cells, the last
    configuration), marked xfail (strict) in tests/conftest.py: PR 38's seven still stand together behind PR 35's
    cache pair, Trinity's cell and configuration still come behind the ones before them, and PR 41's entries
    come behind all of those, in the order given."""
    import test_bench_delivery as delivery

    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index("cache_attention_ms")
    assert names[first : first + 9] == ["cache_attention_ms", "cache_attention_roofline", *delivery.SEVEN]
    assert names[first + 9 : first + 13] == list(NEW_METRICS)  # appended, in ISSUE 41's order, behind the seven
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("trinity5.rollout-longctx") + 1 == 7
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(OLMO) == configs.index("trinity-mini-serve5") + 1 == 4
    entry = manifest["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (OLMO, MIX, 1) and len(entry["why"]) <= 200
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better, moves) in NEW_METRICS.items():
        assert declared[name] == dict(name=name, unit=unit, better=better, source="device_trace", layer="model",
                                      moves=moves, workloads=[CELL])
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(CELL, False) == {"ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert want(CELL, True) == want(CELL, False) | SHARED_METRICS | set(NEW_METRICS)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed:  # appended to each list: behind every cell that was there before it
            assert all(listed.index(c) < listed.index(CELL) for c in listed if c in cells[:7]), m["name"]
    # the seven of a token's way back: the two rollout cells' and, appended, this cell's
    for name in delivery.SEVEN:
        assert declared[name]["workloads"] == [*delivery.CELLS, CELL]
    for w in manifest["workloads"]:
        traced = want(w["name"], True)
        assert (set(delivery.SEVEN) <= traced) == (w["name"] in [*delivery.CELLS, CELL])
        assert not set(delivery.SEVEN) & want(w["name"], False)
        assert bool(set(NEW_METRICS) & traced) == (w["name"] == CELL)


def test_trinitys_mix_is_still_the_issues(manifest):
    """Stands in for test_bench_delivery.py::test_trinitys_mix_is_the_issues_with_the_seven_behind_its_cache_pair,
    which pins Trinity's cell and configuration to the lists' ENDS and the cells to seven, and is marked xfail
    (strict) in tests/conftest.py since PR 41 appends a cell and a configuration. Everything else it holds is
    held here; where the cache pair and the seven stand is held by the test above."""
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
    pair = {"cache_attention_ms", "cache_attention_roofline"}
    assert want(trinity, True) == (want(glm, True) - {"latent_attention_ms", "latent_attention_roofline"}) | pair
    # the pair reads the views of full layers: Trinity's, and since PR 41 the four of Olmo-Hybrid's cut
    for w in manifest["workloads"]:
        assert bool(pair & want(w["name"], True)) == (w["name"] in (trinity, CELL))
    entry = next(w for w in manifest["workloads"] if w["name"] == trinity)
    assert entry["chips"] == 1 and entry["config"] == "trinity-mini-serve5"


def test_the_counts_against_hand_arithmetic(manifest):
    costs, m = _costs(), _config(manifest)
    channels = 30 * (96 + 96 + 192)
    assert costs.linear_channels(m) == channels == 11_520
    mixer = 3840 * (2880 + 2880 + 5760 + 5760 + 5760) + 2 * 3840 * 30
    assert costs.linear_mixer_matmul_params(m) == mixer and round((mixer + 4 * channels) / 1e6, 2) == 88.75
    mlp = 3 * 3840 * 11008
    assert costs.mlp_params(m) == mlp and round(mlp / 1e6, 1) == 126.8
    linear = mixer + mlp + 4 * channels + 2 * 30 + 192 + 2 * 3840
    full = 4 * 3840 * 3840 + mlp + 2 * 3840 + 2 * 3840
    assert costs.linear_layer_params(m) == linear and round(linear / 1e6, 1) == 215.6
    assert costs.full_layer_params(m) == full and round(full / 1e6, 1) == 185.8
    total = 12 * linear + 4 * full + 2 * 100352 * 3840 + 3840
    assert costs.n_params(m) == total and round(total / 1e9, 2) == 4.10
    assert costs.weight_bytes(m) == 2 * total + 12 * 2 * 30 * 2 and round(costs.weight_bytes(m) / 1e9, 2) == 8.20
    assert costs.kv_bytes_per_token(m) == 4 * 2 * 30 * 128 * 2 == 61_440
    a_layer = 30 * 96 * 192 * 4 + 3 * 11_520 * 2
    assert a_layer == 2_211_840 + 69_120 == 2_280_960 and costs.state_bytes_per_slot(m) == 12 * a_layer
    assert costs.linear_state_bytes(m, 8) == 8 * 12 * 2 * a_layer and costs.linear_state_bytes(m, 6.5) == 6.5 * 12 * 2 * a_layer
    assert costs.linear_scan_flops(m, 512) == 512 * 12 * 30 * 7 * 96 * 192
    assert costs.linear_scan_bytes(m, 512) == 12 * 512 * ((11_520 + 5760) * 2 + 2 * 30 * 4) + 2 * 12 * a_layer
    assert costs.cache_attention_bytes(m, 50_000, 50_000) == 50_000 * 61_440
    matrices = (12 * (mixer + mlp) + 4 * (4 * 3840 * 3840 + mlp) + 3840 * 100352) * 2
    assert costs.decode_step_bytes(m, 0) == matrices + 8 * 12 * 2 * a_layer
    assert costs.decode_step_bytes(m, 56_000) == matrices + 56_000 * 61_440 + 8 * 12 * 2 * a_layer
    assert 11.2e9 < costs.decode_step_bytes(m, 56_000) < 11.4e9  # ISSUE 41: a step reads ~11.3 GB
    # ISSUE 41's sizing: the pool as the mathematics counts it, and the state group, which does not grow
    assert round(8 * 8192 * 61_440 / 1e9, 2) == 4.03 and round(8 * 12 * a_layer / 1e9, 2) == 0.22


def test_the_counts_are_the_drawn_parameter_trees(manifest):
    """``costs.py`` against what the program draws and holds: leaf for leaf at a toy size, and by shape alone
    (nothing is drawn) at the published widths; the state group's bytes against the pool's leaves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import cache_token_bytes, state_slot_bytes
    from ray_tpu.models.transformer import TransformerConfig, init_params, num_params

    costs = _costs()
    cell = registry.load_cell(manifest, CELL)
    config = registry.load_architecture(cell, "config")
    toy = toy_cell(manifest, CELL)["config"]

    def program_config(m):
        model = config.model_config(m, 256, "bfloat16")
        model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        return TransformerConfig(**model)

    for m in (toy, cell["config"]):
        cfg = program_config(m)
        shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))  # noqa: B023
        assert num_params(shapes) == costs.n_params(m)
        assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) == costs.weight_bytes(m)
        assert cfg.layer_kinds == ("linear", "linear", "linear", "full") * 4
        assert state_slot_bytes(cfg) == costs.state_bytes_per_slot(m)
        assert shapes["linear_layers"]["w_qkv"].shape == (12, m["hidden_size"], costs.linear_channels(m))
    # the pool holds two zero heads beside the thirty (generate._cache_heads): what the mathematics needs is costs.py's
    assert cache_token_bytes(program_config(cell["config"])) == {"full": 4 * 2 * 32 * 128 * 2}
    assert cache_token_bytes(program_config(toy)) == {"full": costs.kv_bytes_per_token(toy)}
    drawn = init_params(jax.random.PRNGKey(0), program_config(toy))
    assert num_params(drawn) == costs.n_params(toy)
    lin = drawn["linear_layers"]
    assert str(lin["A_log"].dtype) == str(lin["dt_bias"].dtype) == "float32" and float(lin["dt_bias"][0, 0]) == -4.0


def test_the_configuration_is_refused_where_the_program_cannot_compute_it(manifest, monkeypatch):
    cell = registry.load_cell(manifest, CELL)
    config = registry.load_architecture(cell, "config")
    model = config.model_config(cell["config"], 8192, "bfloat16")
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"], model["d_ff"]) == (3840, 30, 30, 11008)
    assert model["layer_kinds"] == ["linear", "linear", "linear", "full"] * 4 and "head_dim" not in model
    assert (model["linear_heads"], model["linear_key_dim"], model["linear_value_dim"], model["linear_conv"]) == (30, 96, 192, 4)
    assert model["linear_neg_eigval"] and model["post_norms"] and model["qk_norm_whole"] and not model["pre_norms"]
    assert "rope_theta" not in model and "sliding_window" not in model
    for key, other in (("attention_bias", True), ("hidden_act", "gelu"), ("rope_parameters", {"rope_theta": 500000.0})):
        with pytest.raises(ValueError, match=key):
            config.model_config(dict(cell["config"], **{key: other}), 8192, "bfloat16")
    with pytest.raises(ValueError, match="linear_num_key_heads != linear_num_value_heads"):
        config.model_config(dict(cell["config"], linear_num_key_heads=15), 8192, "bfloat16")
    with pytest.raises(ValueError, match="layer_types names 4 layers"):
        config.model_config(dict(cell["config"], layer_types=[LINEAR] * 4), 8192, "bfloat16")
    with pytest.raises(ValueError, match="sliding_attention"):
        config.model_config(dict(cell["config"], layer_types=[LINEAR] * 15 + ["sliding_attention"]), 8192, "bfloat16")
    # A program from before PR 41 (the parent the driver tries the new cell on) is refused in the
    # driver process, at once, by name of what it lacks.
    before = set(model) - {"linear_heads", "linear_key_dim", "linear_value_dim", "linear_conv", "linear_neg_eigval",
                           "pre_norms", "qk_norm_whole"}
    monkeypatch.setattr(config, "_program_fields", lambda: before)
    with pytest.raises(NotImplementedError, match="no linear_conv, linear_heads, linear_key_dim, linear_neg_eigval, "
                                                   "linear_value_dim, pre_norms, qk_norm_whole: it cannot run linear-attention"):
        config.model_config(cell["config"], 8192, "bfloat16")


def _result(manifest, **over):
    """What the four new readers see of a traced run, by hand."""
    cell = registry.load_cell(manifest, CELL)
    cell["config"]["trace_ops"] = {"linear_state": r"f32\[12,8,30,96,192\]|f32\[8,30,2,192\]", "linear_scan": r"f32\[1,30,8,64,"}
    fields = ["t_start_ns", "rows", "context_tokens", "window_tokens", "llm.iteration"]
    iterations = [[10**9 * (i + 1), 6 + (i % 2), 45_000, 45_000, 10**6] for i in range(4)]
    result = {
        "cell": cell, "seconds": 51.0, "traced": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        "counters": {"spans": {"fields": {"iterations": fields}, "iterations": [x for rec in iterations for x in rec]}},
        "trace": {"devices": [{
            "programs": {"decode": [0.03] * 20, "prefill": [0.05] * 8},
            "ops": [["%fusion.37 fusion f32[12,8,30,96,192]", 0.016], ["%fusion.30 fusion f32[8,30,2,192]", 0.008],
                    ["%fusion.71 fusion f32[1,30,8,64,192]", 0.03], ["%fusion.72 fusion f32[1,30,8,64,64]", 0.01],
                    ["%fusion.9 fusion bf16[8,3840]", 0.5]],
        }]},
    }
    result.update(over)
    return result


def test_the_four_new_readers_on_a_result_written_by_hand(manifest):
    result = _result(manifest)
    read = lambda name: registry.load_metric("per_layer", name)(result)  # noqa: E731
    assert read("linear_state_ms") == pytest.approx(1.2)  # 0.024 s over 20 decode steps
    least = 6.5 * 12 * 2 * 2_280_960 / 819e9
    assert read("linear_state_roofline") == pytest.approx(100 * least / 0.0012)
    assert read("linear_scan_ms") == pytest.approx(5.0)  # 0.04 s over 8 chunks
    flops, moved = 512 * 12 * 30 * 7 * 96 * 192 / 197e12, (12 * 512 * (17_280 * 2 + 240) + 24 * 2_280_960) / 819e9
    assert moved > flops  # the bytes bound the scan on this chip
    assert read("linear_scan_roofline") == pytest.approx(100 * moved / 0.005)
    for name in ("linear_state_roofline", "linear_scan_roofline"):
        assert 0 < read(name) <= 100


@pytest.mark.parametrize("lacking", ["trace_ops", "ops", "prefill", "spans", "costs"])
def test_a_new_reader_that_finds_nothing_to_read_gives_none(manifest, lacking):
    """The parent of PR 41 under PR 41's benchmark files, another architecture's cell, a slice without a chunk."""
    result = _result(manifest)
    silent = set(NEW_METRICS)
    if lacking == "trace_ops":
        result["cell"] = dict(result["cell"], config={k: v for k, v in result["cell"]["config"].items() if k != "trace_ops"})
    elif lacking == "ops":
        result["trace"] = {"devices": [{"programs": {"decode": [0.02], "prefill": [0.05]}, "ops": [["%fusion.1 fusion f32[7]", 1.0]]}]}
    elif lacking == "prefill":
        del result["trace"]["devices"][0]["programs"]["prefill"]
        silent = {"linear_scan_ms", "linear_scan_roofline"}
    elif lacking == "spans":
        result["counters"] = {}
        silent = {"linear_state_roofline"}
    else:  # an architecture whose costs.py knows no linear layers, its configuration naming such operations all the same
        result["cell"] = dict(result["cell"], architecture="MistralForCausalLM")
        silent = {"linear_state_roofline", "linear_scan_roofline"}
    for name in NEW_METRICS:
        value = registry.load_metric("per_layer", name)(result)
        assert (value is None) == (name in silent), (name, value)


def test_the_new_cell_runs_at_a_toy_size_against_its_reference(manifest, fake_chips, tmp_path):
    """``toy_cell`` through ``run.measure`` on the CPU: the replica, the check against the architecture's own
    float32 reference through the state and the cache, the mix, the line. The toy sizes state float32 activations
    over the bfloat16 weights: 64 wide, bfloat16's rounding of a residual that no input norm rescales (its RMS grows
    from 1 to 4 over eight layers of unit branches) moves logits of unit scale by 0.4-0.8, where the published
    width reads 0.05-0.09 on the chip (the configuration's ``logit_gap_tol_why``); in float32 the gap is ~1e-5."""
    cell = toy_cell(manifest, CELL)
    assert cell["config"]["torch_dtype"] == "float32" and cell["config"]["deployment"]["param_dtype"] == "bfloat16"
    cell["traffic"]["arrival"]["clients"] = 4  # the toy engine has 4 slots: as many clients as slots, as in the cell
    result = bench_run.measure(
        cell, seed=2**31 + 41, seconds=3.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    gaps = result["notes"]["reference_gaps"]
    assert len(gaps) == 2 and all(g["finite"] and g["max_gap"] <= cell["config"]["check"]["logit_gap_tol"] for g in gaps)
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, CELL, traced=False, platform="cpu")
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    counters = result["counters"]
    assert counters["kv_pool_not_donated"] == 0 and counters["host_logit_rows"] == 0
    assert counters["decode_steps_with_chunk"] == 0 and counters["decode_steps"] > 0
    toy = cell["config"]
    groups = counters["kv_groups"]
    assert set(groups) == {"full", "state"}
    # float32 activations at the toy size: four bytes a cached value and a carried row's
    assert groups["full"]["kv_token_bytes"] == _costs().kv_bytes_per_token(toy, itemsize=4) == counters["kv_token_bytes"]
    assert groups["state"]["bytes_per_slot"] == _costs().state_bytes_per_slot(toy, itemsize=4) and groups["state"]["num_slots"] == 4
    assert counters["state_resets"] == counters["admitted"] > 0
    assert counters["chunk_tokens_valid"] > 0 and counters["chunk_tokens_padded"] > 0
    assert (counters["prefix_hit_blocks"], counters["prefix_miss_blocks"], counters["cached_blocks"]) == (0, 0, 0)
