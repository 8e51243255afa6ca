"""The twelve span metrics (ISSUE 24): each reader on a recorded ``get_stats()``,
the windowing, and their entries against the manifest's rules.

``benchmarks/fixtures/serve_spans.json`` is the last ``get_stats()["spans"]`` of
a traced ``serve16.chat-open`` run on the v5e (my chip run, PR 24), trimmed to
its last 40 iterations, the 8 requests that ended last and 6 compilations. The
expected values were counted from those records without the readers (a scratch
script with ``numpy.median`` / ``numpy.percentile`` over the rows inside
``seconds`` of the newest stamp).

The entries live in ``benchmarks/fixtures/span_metric_entries.json`` and not
yet in ``BENCHMARK.json``: the last test says why.
"""

import copy
import json
import math
import os

import pytest
import test_bench_manifest as rules
from conftest import FIXTURES

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry, spans

BOTH = {"serve16.chat-open", "serve16.batch-decode"}
CELLS = {
    "engine_iteration_ms": BOTH, "engine_fetch_ms": BOTH, "engine_sample_ms": BOTH,
    "engine_build_ms": BOTH, "engine_admit_ms": BOTH, "engine_emit_ms": BOTH,
    "decode_rows_mean": BOTH, "serve_ingress_p90_ms": {"serve16.chat-open"},
    "queue_wait_p90_ms": {"serve16.chat-open"}, "prefill_span_p90_ms": {"serve16.chat-open"},
    "compiles_in_window": BOTH, "replica_params_s": BOTH,
}
# Counted on the fixture without the readers, inside its "seconds" (8) of the newest stamp.
EXPECTED = {
    "engine_iteration_ms": 68.680719, "engine_fetch_ms": 32.4567, "engine_sample_ms": 5.347155,
    "engine_build_ms": 3.613945, "engine_admit_ms": 0.015945, "engine_emit_ms": 0.307025,
    "decode_rows_mean": 10.025, "serve_ingress_p90_ms": 2.2837997, "queue_wait_p90_ms": 43.7779033,
    "prefill_span_p90_ms": 883.5597704, "compiles_in_window": 0, "replica_params_s": 46.613534,
}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "serve_spans.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def result(recorded):
    """What a reader sees of a run: the recorded spans where ``serve_cell.run``
    puts the last ``get_stats()``."""
    return {"seconds": recorded["seconds"], "counters": {"running": 0, "spans": recorded["spans"]}}


@pytest.fixture(scope="module")
def with_entries(manifest):
    """BENCHMARK.json with the twelve entries appended, as a benchmark PR will."""
    with open(os.path.join(FIXTURES, "span_metric_entries.json")) as f:
        entries = json.load(f)["per_layer"]
    new = copy.deepcopy(manifest)
    new["per_layer"] += entries
    return new


def test_the_fixture_is_a_trimmed_get_stats(recorded):
    s = recorded["spans"]
    assert set(s) == {"iterations", "requests", "compiles", "setup", "fields"}
    assert len(s["iterations"]) == 40 * len(s["fields"]["iterations"])  # one flat list, row after row
    assert len(s["requests"]) == 8 and len(s["compiles"]) == 6
    assert all(len(r) == len(s["fields"]["requests"]) for r in s["requests"])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_reader_gives_the_hand_counted_value_and_it_is_finite(result, name):
    value = registry.load_metric("per_layer", name)(result)
    assert isinstance(value, (int, float)) and math.isfinite(value)
    assert value == pytest.approx(EXPECTED[name], rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_reader_finds_nothing_in_a_program_without_spans_and_does_not_raise(name):
    """The parent commit: its ``get_stats()`` has no ``"spans"``."""
    read = registry.load_metric("per_layer", name)
    assert read({"seconds": 51.0, "counters": {"running": 3}}) is None
    assert read({"seconds": 51.0, "counters": {}}) is None


def test_the_window_is_the_seconds_that_end_at_the_newest_stamp(result, recorded):
    fields = recorded["spans"]["fields"]
    flat, width = recorded["spans"]["iterations"], len(fields["iterations"])
    its = [dict(zip(fields["iterations"], flat[i:i + width])) for i in range(0, len(flat), width)]
    reqs = [dict(zip(fields["requests"], r)) for r in recorded["spans"]["requests"]]
    newest = max([r["t_start_ns"] + r["llm.iteration"] for r in its] + [r["t_done_ns"] for r in reqs])
    assert newest == 143770734912  # read off the fixture: the end of its last iteration
    lo, hi = spans.window_ns(result)
    assert hi == newest and hi - lo == int(recorded["seconds"] * 1e9)
    # the fixture's 8 s keep every iteration and the four requests submitted in them
    assert len(spans.decode_iterations(result)) == 40
    assert [r["rid"] for r in spans.requests(result)] == ["llm-77", "llm-73", "llm-78", "llm-72"]
    # one second keeps the 15 newest iterations and drops the 25 older, and every request
    short = dict(result, seconds=1.0)
    kept = spans.decode_iterations(short)
    assert len(kept) == 15 and kept == its[-15:]
    assert all(r["t_start_ns"] < newest - 10**9 for r in its[:-15])
    assert spans.requests(short) == []
    assert registry.load_metric("per_layer", "queue_wait_p90_ms")(short) is None
    # an hour keeps everything, the six programs built at start-up too
    whole = dict(result, seconds=3600.0)
    assert len(spans.requests(whole)) == 8
    assert spans.compiles_in_window(whole) == 6 and spans.compiles_in_window(result) == 0


@pytest.mark.parametrize(
    "rule",
    [
        rules.test_every_name_unit_and_why_uses_only_what_the_driver_allows,
        rules.test_every_file_the_manifest_names_exists,
        rules.test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric,
        rules.test_each_layer_metric_moves_an_end_to_end_metric_all_its_cells_report,
    ],
    ids=lambda f: f.__name__[5:],
)
def test_the_manifest_with_the_entries_still_passes(with_entries, rule):
    rule(with_entries)
    assert len(json.dumps(with_entries, indent=1)) <= 64 * 1024


def test_expected_metrics_lists_each_new_metric_for_exactly_its_cells(with_entries, manifest):
    assert [m["name"] for m in with_entries["per_layer"][-12:]] == list(CELLS)
    for w in with_entries["workloads"]:
        want = contract.expected_metrics(with_entries, w["name"], traced=True)
        for name, cells in CELLS.items():
            assert (name in want) == (w["name"] in cells), (name, w["name"])
        assert not set(CELLS) & set(contract.expected_metrics(with_entries, w["name"], traced=False))
    assert with_entries["per_layer"][:-12] == manifest["per_layer"]  # appended, nothing else


@pytest.mark.parametrize("workload", sorted(BOTH))
def test_a_traced_line_with_the_entries_passes_the_contract(with_entries, result, fixture_reduced, workload):
    full = dict(
        result, cell=registry.load_cell(with_entries, workload), traced=True, correct=True,
        attempted=4, failed=0, trace=fixture_reduced("serve_slice.json", workload),
        client={"attempted": 4, "finished": 3, "ttft_ms": [1.0, 2.0], "itl_ms": [1.0], "tokens_in_window": 50},
        clock={"setup_s": 3.0, "replica_ready_s": 2.0, "serve_path_overhead_ms": 1.0},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
    )
    full["counters"] = dict(full["counters"], running_polls=[3, 4])
    line = bench_run.build_line(with_entries, full)
    contract.validate(line, with_entries, workload, traced=True)
    assert {n for n, cells in CELLS.items() if workload in cells} <= set(line["metrics"])

    # Why BENCHMARK.json does not carry the entries yet: the same run of a
    # program without spans (the parent commit, over which the driver lays this
    # PR's benchmark files for its traced runs) leaves the metrics out, and
    # ``contract.validate`` refuses a traced line that lacks a declared metric.
    parent = dict(full, counters={"running_polls": [3, 4]})
    line = bench_run.build_line(with_entries, parent)
    assert not set(CELLS) & set(line["metrics"])
    with pytest.raises(contract.ContractError, match="engine_iteration_ms"):
        contract.validate(line, with_entries, workload, traced=True)


def test_benchmark_json_does_not_declare_them_until_validate_accepts_their_absence(manifest):
    assert not set(CELLS) & {m["name"] for m in manifest["per_layer"]}
