"""Everything the harness knows about a cell, found by name.

``BENCHMARK.json`` at the root lists cells, configurations and metrics; what
belongs to one of them sits in a file of its own that is found from its name:

- ``benchmarks/configs/<config>.json``   sizes as run, source, cut, deployment
- ``benchmarks/traffic/<traffic>.json``  the mix or training job, all as data
- ``benchmarks/end_to_end/<metric>.py``, ``benchmarks/layer_metrics/<metric>.py``
  one ``read(result)`` per metric

A later PR adds a cell, a mix, a configuration or a metric as new files plus
new entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str, bench_dir: str) -> dict:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """The cell's entry with its configuration and traffic files read in."""
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}")
    return {
        "name": workload,
        "chips": int(entry["chips"]),
        "config_name": entry["config"],
        "traffic_name": entry["traffic"],
        "config": _load_json("configs", entry["config"], bench_dir),
        "traffic": _load_json("traffic", entry["traffic"], bench_dir),
    }


def cell_metrics(manifest: dict, workload: str, kind: str) -> list[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports: all
    without a ``workloads`` key, and those that list the cell."""
    return [
        m for m in manifest[kind] if "workloads" not in m or workload in m["workloads"]
    ]


METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_metric(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The reader of one metric: ``read(result) -> float | None``, in
    ``benchmarks/end_to_end/<name>.py`` or ``benchmarks/layer_metrics/<name>.py``."""
    path = os.path.join(bench_dir, METRIC_DIRS[kind], f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
