"""The eight metrics of a start (ISSUE 52): each reader on a recorded
``get_stats()`` and on a toy run of a kind of cell, a program without the
records read as None, their entries in ``BENCHMARK.json``, and the stand-ins
for the tests of this directory that pin a list's end or a count and that PR 52's
eight appended entries make wrong (marked xfail, strict, in tests/conftest.py).

``benchmarks/fixtures/serve_setup_stages.json`` is what the last ``get_stats()``
of a traced ``trinity5.rollout-longctx`` run on the v5e held of its start (my
chip run, PR 52): every stage record, the five stamps, the totals and the whole
compile ring, with the six newest iterations and four newest requests so that
the readers' window has its two ends. The expected values were counted from
those records without the readers (a scratch script over the raw lists).
"""

import json
import math
import os
import time

import pytest
from bench_toy import toy_cell
from conftest import FIXTURES

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, setup_stages, spans

SERVING = [
    "serve16.chat-open", "serve16.batch-decode", "glm8.rollout-long", "serve16.long-prompt",
    "trinity5.rollout-longctx", "olmo16.longdoc-8k", "nemo14.chat-churn", "xing6.longdoc-12k",
]
TRAINING = ["train2.dense-4k", "train2.dp4-4k", "mellum4.moe-8k"]
# name -> (unit, source, layer, cells), in the order ISSUE 52's table has them
METRICS = {
    "replica_spawn_s": ("s", "program_span", "runtime", SERVING),
    "replica_backend_s": ("s", "program_span", "runtime", SERVING),
    "programs_python_s": ("s", "program_span", "LLM engine", SERVING),
    "programs_python_waiting_pct": ("%", "program_span", "LLM engine", SERVING),
    "programs_compile_s": ("s", "program_counter", "model", SERVING),
    "programs_compiled_afresh": ("count", "program_counter", "model", SERVING),
    "trainer_spawn_s": ("s", "program_span", "Train", TRAINING),
    "trainer_backend_s": ("s", "program_span", "Train", TRAINING),
}
OF_A_REPLICA = [n for n, m in METRICS.items() if m[3] is SERVING]
OF_A_TRAINER = [n for n, m in METRICS.items() if m[3] is TRAINING]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "serve_setup_stages.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def result(recorded):
    """What a reader sees of a serving run: the recorded spans where
    ``serve_cell.run`` puts the last ``get_stats()``."""
    return {"seconds": recorded["seconds"], "counters": {"running": 0, "spans": recorded["spans"]}}


def _read(name, result):
    return registry.load_metric("per_layer", name)(result)


def test_the_fixture_is_a_start_as_get_stats_carries_it(recorded):
    s = recorded["spans"]
    assert {"stages", "setup", "setup_stamps", "compile_totals", "build_threads", "compiles", "fields"} <= set(s)
    fields = s["fields"]["stages"]
    assert fields == ["stage", "program", "thread", "t_start_ns", "t_end_ns", "cpu_ns", "process_cpu_ns", "gc_ns", "gc2"]
    assert all(len(r) == len(fields) for r in s["stages"])
    stages = [dict(zip(fields, r)) for r in s["stages"]]
    assert [r["stage"] for r in stages[:5]] == ["jax_import", "backend", "params", "pool", "jit_build"]
    programs = {r["program"] for r in stages if r["program"]}
    assert len(programs) == recorded["programs"] and all(p.startswith("decode@") for p in programs)  # a pattern pool: no fused step
    for p in programs:
        assert sorted(r["stage"] for r in stages if r["program"] == p) == ["compile", "first_run", "lower", "trace"]
    stamps = s["setup_stamps"]
    order = [stamps[k] for k in ("t_requested_ns", "t_process_ns", "t_actor_ns", "t_callable_ns", "t_ready_ns")]
    assert order == sorted(order) and order[0] > 0
    assert stamps["t_callable_ns"] <= stages[0]["t_start_ns"] and max(r["t_end_ns"] for r in stages) <= stamps["t_ready_ns"]
    assert s["fields"]["compiles"][-1] == "afresh" and len(s["compiles"]) <= 256


def test_the_seconds_of_setup_are_the_sums_of_the_fixtures_records(recorded):
    """``setup`` is computed from the records in the program; the recording holds both."""
    s = recorded["spans"]
    stages = [dict(zip(s["fields"]["stages"], r)) for r in s["stages"]]
    for r in stages:
        if not r["program"]:
            assert s["setup"][r["stage"] + "_s"] == pytest.approx((r["t_end_ns"] - r["t_start_ns"]) / 1e9, abs=1e-9)
    built = [r for r in stages if r["program"]]
    whole = max(r["t_end_ns"] for r in built) - min(r["t_start_ns"] for r in built)
    assert s["setup"]["decode_build_s"] == pytest.approx(whole / 1e9, abs=1e-9) and "fused_build_s" not in s["setup"]
    assert set(s["setup"]) == {"jax_import_s", "backend_s", "params_s", "pool_s", "jit_build_s", "decode_build_s"}


@pytest.mark.parametrize("name", OF_A_REPLICA)
def test_a_reader_gives_the_hand_counted_value_and_it_is_finite(result, recorded, name):
    value = _read(name, result)
    assert isinstance(value, (int, float)) and not isinstance(value, bool) and math.isfinite(value)
    assert value == pytest.approx(recorded["expected"][name], rel=1e-9, abs=1e-12)
    if name.endswith("_pct"):
        assert 0.0 <= value <= 100.0


@pytest.mark.parametrize("name", list(METRICS))
def test_a_reader_finds_nothing_in_a_program_without_the_records_and_does_not_raise(name, fixture_raw):
    """The parent commit: its ``get_stats()["spans"]`` has the rings and the
    seven seconds and no stage record, stamp or total (the recording of PR 24
    is one such); its trainer's reports carry no ``_trainer_start``."""
    read = registry.load_metric("per_layer", name)
    parent = fixture_raw("serve_spans.json")
    assert read({"seconds": parent["seconds"], "counters": {"running": 0, "spans": parent["spans"]}}) is None
    assert read({"seconds": 51.0, "counters": {"running": 3}}) is None
    assert read({"seconds": 51.0, "counters": {}}) is None
    assert read({"seconds": 51.0, "train": {"event": "done", "steps": 3}, "clock": {}}) is None
    assert read({"seconds": 51.0}) is None


def test_a_stamp_not_taken_or_a_stage_not_run_is_no_number(result, recorded):
    s = recorded["spans"]
    foreign = dict(s, setup_stamps=dict(s["setup_stamps"], t_requested_ns=0))  # a controller on another host
    assert _read("replica_spawn_s", {"seconds": 51.0, "counters": {"spans": foreign}}) is None
    no_build = dict(s, stages=[r for r in s["stages"] if not r[1]])  # an engine that built nothing ahead
    assert _read("programs_python_s", {"seconds": 51.0, "counters": {"spans": no_build}}) is None
    assert _read("programs_python_waiting_pct", {"seconds": 51.0, "counters": {"spans": no_build}}) is None
    assert _read("replica_backend_s", {"seconds": 51.0, "counters": {"spans": no_build}}) == recorded["expected"]["replica_backend_s"]
    start = {"t_fit_ns": 0, "t_worker_ns": 5_000_000_000, "t_mesh_ns": 14_500_000_000, "t_loop_ns": 14_600_000_000}
    under_tune = {"seconds": 51.0, "train": {"_trainer_start": start}}
    assert _read("trainer_spawn_s", under_tune) is None and _read("trainer_backend_s", under_tune) == 9.5
    assert _read("trainer_spawn_s", {"seconds": 51.0, "train": {"_trainer_start": dict(start, t_fit_ns=3_750_000_000)}}) == 1.25


def test_compilations_are_counted_before_the_window_whatever_the_ring_still_holds(result, recorded):
    s = recorded["spans"]
    win = spans.window_ns(result)
    compiles = [dict(zip(s["fields"]["compiles"], r)) for r in s["compiles"]]
    assert win is not None and all(r["t_end_ns"] < win[0] for r in compiles)  # warmed up: nothing compiles in the window
    count, ns = setup_stages.compiled_before_window(result, "backend_compile")
    assert [count, ns] == s["compile_totals"]["backend_compile"]
    # A program built inside the window is the window's (`compiles_in_window`), not the start's ...
    late = [win[0] + 1_000_000, 2_000_000_000, "backend_compile", "jit(a_new_width)", 1]
    totals = {k: [v[0] + 1, v[1] + 2_000_000_000] if k != "cache_retrieval" else list(v) for k, v in s["compile_totals"].items()}
    with_late = {"seconds": recorded["seconds"], "counters": {"spans": dict(s, compiles=s["compiles"] + [late], compile_totals=totals)}}
    for name in ("programs_compile_s", "programs_compiled_afresh"):
        assert _read(name, with_late) == pytest.approx(recorded["expected"][name], rel=1e-9)
    assert spans.compiles_in_window(with_late) == 1
    # ... and a ring that has dropped the start's records loses nothing of it: the totals are plain ints.
    dropped = {"seconds": recorded["seconds"], "counters": {"spans": dict(s, compiles=s["compiles"][-3:])}}
    assert _read("programs_compile_s", dropped) == pytest.approx(recorded["expected"]["programs_compile_s"], rel=1e-9)
    assert _read("programs_compiled_afresh", dropped) == recorded["expected"]["programs_compiled_afresh"]


def test_benchmark_json_gains_exactly_the_eight_at_the_end_of_per_layer(manifest):
    tail = manifest["per_layer"][-8:]
    assert [m["name"] for m in tail] == list(METRICS)
    for m in tail:
        unit, source, layer, cells = METRICS[m["name"]]
        assert m == dict(name=m["name"], unit=unit, better="lower", source=source, layer=layer, moves="setup_s", workloads=cells)
        assert os.path.isfile(os.path.join(registry.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    order = [w["name"] for w in manifest["workloads"]]
    assert [c for c in order if c in SERVING] == SERVING and [c for c in order if c in TRAINING] == TRAINING
    assert sorted(SERVING + TRAINING) == sorted(order)  # all eleven cells read their own start
    before = [m["name"] for m in manifest["per_layer"][:-8]]
    assert len(before) == 49 and not set(METRICS) & set(before) and before[-2:] == ["latent_prefill_ms", "latent_prefill_roofline"]
    for w in order:
        assert not set(METRICS) & set(contract.expected_metrics(manifest, w, traced=False))


# -- one toy run a kind of cell, through run.measure ---------------------------


def test_a_toy_serving_cell_reads_its_six(manifest, fake_chips, tmp_path):
    cell = toy_cell(manifest, "serve16.batch-decode")
    result = bench_run.measure(
        cell, seed=2**31 + 52, seconds=2.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0
    got = {name: _read(name, result) for name in OF_A_REPLICA}
    assert all(isinstance(v, (int, float)) and math.isfinite(v) and v >= 0 for v in got.values()), got
    assert 0 < got["replica_spawn_s"] < result["clock"]["replica_ready_s"]
    setup = result["counters"]["spans"]["setup"]
    assert got["replica_backend_s"] == setup["backend_s"]
    # Mistral's shape fuses: three kinds of program, their Python inside the two build seconds
    assert 0 < got["programs_python_s"] < setup["decode_build_s"] + setup["fused_build_s"]
    assert 0.0 <= got["programs_python_waiting_pct"] <= 100.0
    assert got["programs_compile_s"] > 0 and got["programs_compiled_afresh"] == int(got["programs_compiled_afresh"])
    assert all(_read(name, result) is None for name in OF_A_TRAINER)
    # the stages' walls and the spawn lie inside what the harness clocked round serve.run
    walls = sum(r[4] - r[3] for r in result["counters"]["spans"]["stages"] if r[0] != "compile") / 1e9
    assert walls + got["replica_spawn_s"] < result["clock"]["replica_ready_s"]
    line = bench_run.build_line(manifest, result)
    contract.validate(line, manifest, cell["name"], traced=False, platform="cpu")


def test_a_toy_training_cell_reads_its_two(manifest, fake_chips, tmp_path):
    cell = toy_cell(manifest, "train2.dense-4k")
    result = bench_run.measure(
        cell, seed=2**31 + 52, seconds=2.0, traced=False, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["train"]["event"] == "done"  # the user's own keys, as the loop reported them
    start = result["train"]["_trainer_start"]
    assert list(start) == ["t_fit_ns", "t_worker_ns", "t_mesh_ns", "t_loop_ns"] and list(start.values()) == sorted(start.values())
    spawn, backend = _read("trainer_spawn_s", result), _read("trainer_backend_s", result)
    assert spawn > 0 and backend > 0
    # fit() to the loop's first line is what train_cell.py's log calls worker_start_s, and these two are inside it
    assert spawn + backend < result["clock"]["worker_start_s"] + 0.5
    assert all(_read(name, result) is None for name in OF_A_REPLICA)


# -- stand-ins for the pins that eight appended entries break ------------------


def _full_result(manifest, result, fixture_reduced, workload):
    full = dict(
        result, cell=registry.load_cell(manifest, workload), traced=True, correct=True,
        attempted=4, failed=0, trace=fixture_reduced("serve_slice.json", workload),
        client={"attempted": 4, "finished": 3, "ttft_ms": [1.0, 2.0], "itl_ms": [1.0], "tokens_in_window": 50},
        clock={"setup_s": 3.0, "replica_ready_s": 2.0, "serve_path_overhead_ms": 1.0},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
    )
    full["counters"] = dict(full["counters"], running_polls=[3, 4])
    return full


@pytest.mark.parametrize("workload", ["serve16.batch-decode", "serve16.chat-open"])
def test_a_traced_line_carries_the_span_metrics_and_a_start_without_records_leaves_the_six_out(
    manifest, fixture_raw, recorded, fixture_reduced, workload, capsys
):
    """Stands in for test_bench_span_metrics.py::test_a_traced_line_carries_them_and_a_program_without_spans_
    leaves_them_out, which holds a line built from PR 24's recording to carry EVERY declared metric of the
    cell. That recording is a start without stage records: its line carries all but PR 52's six, which are
    named on standard error; with the start's records of PR 52's recording beside it the line carries all."""
    from test_bench_span_metrics import CELLS

    old = fixture_raw("serve_spans.json")
    result = {"seconds": old["seconds"], "counters": {"running": 0, "spans": old["spans"]}}
    full = _full_result(manifest, result, fixture_reduced, workload)
    line = bench_run.build_line(manifest, full)
    contract.validate(line, manifest, workload, traced=True)
    want = set(contract.expected_metrics(manifest, workload, traced=True))
    assert set(line["metrics"]) == want - set(OF_A_REPLICA)
    logged = [ln for ln in capsys.readouterr().err.splitlines() if "left out" in ln]
    assert len(logged) == 1 and logged[0].endswith(": " + ", ".join(OF_A_REPLICA))

    start = {k: recorded["spans"][k] for k in ("stages", "setup_stamps", "compile_totals", "build_threads")}
    both = dict(old["spans"], **start, fields=dict(old["spans"]["fields"], stages=recorded["spans"]["fields"]["stages"]))
    full["counters"] = dict(full["counters"], spans=both)
    line = bench_run.build_line(manifest, full)
    contract.validate(line, manifest, workload, traced=True)
    assert set(line["metrics"]) == want and "left out" not in capsys.readouterr().err

    mine = [n for n, cells in CELLS.items() if workload in cells]
    line = bench_run.build_line(manifest, dict(full, counters={"running_polls": [3, 4]}))
    assert not (set(CELLS) | set(OF_A_REPLICA)) & set(line["metrics"])
    contract.validate(line, manifest, workload, traced=True)
    logged = [ln for ln in capsys.readouterr().err.splitlines() if "left out" in ln]
    assert len(logged) == 1 and logged[0].endswith(": " + ", ".join(mine + OF_A_REPLICA))
    del line["metrics"]["itl_p95_ms"]  # an end-to-end metric is never optional
    with pytest.raises(contract.ContractError, match="'itl_p95_ms' is missing"):
        contract.validate(line, manifest, workload, traced=True)


def test_the_span_metrics_stand_where_they_were_and_a_cells_count_is_its_lists(manifest):
    """Stands in for test_bench_glm.py::test_the_span_metrics_stand_and_new_cells_are_only_appended, which counts
    24 and 18 metrics on the traced lines of the two first cells: the twelve stand behind PR 23's fourteen,
    each lists PR 26's cells first and every list follows the cells' order; a cell's count is what lists it."""
    from test_bench_span_metrics import CELLS

    assert [m["name"] for m in manifest["per_layer"][14:26]] == list(CELLS)
    declared = {m["name"]: m for m in manifest["per_layer"]}
    order = [w["name"] for w in manifest["workloads"]]
    for name, cells in CELLS.items():
        listed = declared[name]["workloads"]
        assert set(listed[: len(cells)]) == cells and listed == sorted(listed, key=order.index)
    for w in order:
        traced = contract.expected_metrics(manifest, w, traced=True)
        untraced = contract.expected_metrics(manifest, w, traced=False)
        for name in CELLS:
            assert (name in traced) == (w in declared[name]["workloads"]) and name not in untraced
        lists_it = [m["name"] for m in manifest["per_layer"] if "workloads" not in m or w in m["workloads"]]
        assert len(traced) == len(untraced) + len(lists_it)
    # what the two first cells' traced lines held until PR 52 (chat-open's less prefill_chunk_ms, which PR 63 took
    # from the cell: its chunks ride inside the decode step since PR 40), and the six of a replica's start behind it
    until_52 = lambda w: [n for n in contract.expected_metrics(manifest, w, traced=True) if n not in METRICS]  # noqa: E731
    assert len(until_52("serve16.chat-open")) == 23 and len(until_52("serve16.batch-decode")) == 18
    assert list(contract.expected_metrics(manifest, "serve16.chat-open", traced=True))[23:] == OF_A_REPLICA


def test_the_training_cells_report_what_they_did_and_the_two_of_their_start(manifest):
    """Stands in for test_bench_mellum.py::test_the_job_is_the_issues_and_nothing_but_files_and_appended_entries_came,
    which pins the SET of Mellum's per-layer metrics to PR 50's five: the job, the entry and the orders as that
    test holds them, and the cell's per-layer metrics are PR 50's five with PR 52's two of a trainer's start
    appended behind them; the same for the two Mistral training cells, in the same order."""
    from test_bench_mellum import CELL, JOB, MELLUM, TRAINING_METRICS

    cell = registry.load_cell(manifest, CELL)
    job = cell["traffic"]
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["report_every"], job["trace_slice_s"]) == ("train", 8192, 2, 4, 3.0)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == dict(name=CELL, config=MELLUM, traffic=JOB, chips=1, why=entry["why"]) and len(entry["why"]) <= 200
    for name in TRAINING:
        layers = [m["name"] for m in registry.cell_metrics(manifest, name, "per_layer")]
        assert set(layers) - set(OF_A_TRAINER) - {"collective_exposed_ms"} == TRAINING_METRICS
        assert layers[-2:] == OF_A_TRAINER and ("collective_exposed_ms" in layers) == (name == "train2.dp4-4k")
        reports = {m["name"] for m in registry.cell_metrics(manifest, name, "end_to_end")}
        assert reports == {"train_tokens_per_s", "setup_s"}
    for m in manifest["per_layer"]:
        assert not m["name"].startswith("moe_train"), m["name"]
        if m["name"] in TRAINING_METRICS | set(OF_A_TRAINER):
            assert m["workloads"].index("train2.dense-4k") < m["workloads"].index(CELL)
            assert m["moves"] in ("train_tokens_per_s", "setup_s")
    throughput = next(m for m in manifest["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert throughput["workloads"].index("train2.dp4-4k") < throughput["workloads"].index(CELL) and throughput["bound"] == 0.01
    names = [c["name"] for c in manifest["configs"]]
    assert names.index(MELLUM) > names.index("xing4.0-29b-a4b-serve6")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("xing6.longdoc-12k") and sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cfg = cell["config"]
    assert cfg["deployment"]["mosaic_kernels"] == ["_flash_kernel", "_flash_bwd", "kernel"]
    assert cfg["trace_programs"] == {"train_step": "^jit_train_step"} and "trace_ops" not in cfg


def test_xings_cell_reports_what_it_did_and_the_six_of_its_start(manifest):
    """Stands in for test_bench_xing.py::test_the_new_entries_are_appended_behind_what_was_there, which pins the SET
    of the cell's traced metrics: the orders, the entry and the lists as that test holds them, and the traced line
    is everything accepted before PR 47 that its kinds of layer give something to read, PR 47's two, and PR 52's
    six of a replica's start appended behind every one of them."""
    from test_bench_xing import (
        CELL, GLM_CELL, MIX, MOVE_WHAT_IT_DOES_NOT_REPORT, NEMO_CELL, NEW_METRICS, NOT_THIS_CELLS, OLMO_CELL, XING,
    )

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
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better, moves) in NEW_METRICS.items():
        assert declared[name] == dict(name=name, unit=unit, better=better, source="device_trace", layer="model",
                                      moves=moves, workloads=[CELL])
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(CELL, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    moved = {m["name"] for m in manifest["per_layer"][: first + 6] if m["moves"] == "ttft_p90_ms"}
    assert MOVE_WHAT_IT_DOES_NOT_REPORT <= moved and not any(CELL in m.get("workloads", ()) for m in manifest["per_layer"] if m["name"] in moved)
    accepted = set(names[: first + 6]) - NOT_THIS_CELLS - moved
    assert want(CELL, True) == want(CELL, False) | accepted | set(NEW_METRICS) | set(OF_A_REPLICA)
    assert list(contract.expected_metrics(manifest, CELL, traced=True))[-6:] == OF_A_REPLICA
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
