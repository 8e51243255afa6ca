"""BENCHMARK.json against the driver's rules, and that the harness is driven
by data: a new cell, mix or metric is new files plus new entries."""

import json
import os
import re
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_the_keys_are_exactly_the_contracts(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert manifest["paths"] == ["benchmarks", "tests/benchmark"]
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(registry.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_name_unit_and_why_uses_only_what_the_driver_allows(manifest):
    names = []
    for cfg in manifest["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        names.append(cfg["name"])
        assert all(NAME.match(k) for k in cfg["reduced"]) and len(cfg["reduced"]) <= 16
        for text in (cfg["why"], cfg["source"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["traffic"]]
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for kind, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in manifest[kind]:
            assert set(m) - {"workloads"} == keys, m["name"]
            names.append(m["name"])
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for name in names:
        assert NAME.match(name), name
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert len({w["name"] for w in manifest["workloads"]}) == len(manifest["workloads"])
    assert len({(w["config"], w["traffic"]) for w in manifest["workloads"]}) == len(manifest["workloads"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_file_the_manifest_names_exists(manifest):
    for cfg in manifest["configs"]:
        path = os.path.join(registry.ROOT, cfg["file"])
        assert os.path.isfile(path) and cfg["file"].startswith("benchmarks/configs/")
        assert any(w["config"] == cfg["name"] for w in manifest["workloads"])
    for w in manifest["workloads"]:
        cell = registry.load_cell(manifest, w["name"])
        assert cell["config"]["path"] in ("serve", "train")
        assert cell["traffic"]["kind"] == cell["config"]["path"]
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            assert callable(registry.load_metric(kind, m["name"]))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric(manifest):
    assert "workloads" not in next(m for m in manifest["end_to_end"] if m["name"] == "setup_s")
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in registry.cell_metrics(manifest, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.cell_metrics(manifest, w["name"], "per_layer")


def test_each_layer_metric_moves_an_end_to_end_metric_all_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = [e["name"] for e in registry.cell_metrics(manifest, cell, "end_to_end")]
            assert m["moves"] in reported, (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_new_cell_mix_and_metric_are_new_files_and_entries_only(manifest, tmp_path):
    """Copy the benchmark, add files and entries, edit nothing: the harness
    finds the new cell, its mix and its metric by name."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(registry.BENCH_DIR, bench, ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "traffic" / "chat-burst.json").write_text(json.dumps(dict(
        json.loads((bench / "traffic" / "chat-open.json").read_text()),
        arrival={"process": "gamma", "rate_per_s": 2.0, "cv": 3.0},
    )))
    (bench / "layer_metrics" / "finished_share_pct.py").write_text(
        "def read(result):\n"
        "    c = result['client']\n"
        "    return 100.0 * c['finished'] / c['attempted']\n"
    )
    new = json.loads(json.dumps(manifest))
    new["workloads"].append({
        "name": "serve16.chat-burst", "config": "mistral-7b-v0.1-serve16",
        "traffic": "chat-burst", "chips": 1, "why": "bursts at the same mean rate",
    })
    new["per_layer"].append({
        "name": "finished_share_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "LLM engine", "moves": "serve_tokens_per_s", "workloads": ["serve16.chat-burst"],
    })
    for m in new["end_to_end"] + new["per_layer"]:
        if "workloads" in m and "serve16.chat-open" in m["workloads"]:
            m["workloads"] = m["workloads"] + ["serve16.chat-burst"]
    cell = registry.load_cell(new, "serve16.chat-burst", bench_dir=str(bench))
    assert cell["traffic"]["arrival"]["process"] == "gamma"
    want = contract.expected_metrics(new, "serve16.chat-burst", traced=True)
    assert "finished_share_pct" in want and "ttft_p90_ms" in want
    result = {
        "cell": cell, "traced": False, "seconds": 10.0, "correct": True, "attempted": 4, "failed": 0,
        "client": {"attempted": 4, "finished": 3, "ttft_ms": [1.0, 2.0], "itl_ms": [1.0], "tokens_in_window": 50},
        "clock": {"setup_s": 3.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
    }
    line = bench_run.build_line(new, result, bench_dir=str(bench))
    contract.validate(line, new, "serve16.chat-burst", traced=False)
    read = registry.load_metric("per_layer", "finished_share_pct", bench_dir=str(bench))
    assert read(result) == 75.0
    assert {p: p.read_bytes() for p in before} == before  # nothing that existed was edited


@pytest.mark.parametrize("kind", ["configs", "traffic"])
def test_data_files_are_json_named_from_allowed_characters(kind):
    for name in os.listdir(os.path.join(registry.BENCH_DIR, kind)):
        assert name.endswith(".json") and NAME.match(name)
        with open(os.path.join(registry.BENCH_DIR, kind, name)) as f:
            assert isinstance(json.load(f), dict)
