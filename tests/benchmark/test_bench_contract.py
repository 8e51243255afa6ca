"""The last line of a run: ``run.py``'s own functions at a toy width on
``platform="cpu"`` must produce a line ``contract.validate`` accepts, for
``--trace 0`` and (with the recorded fixture trace standing in for the device
the CPU has not got) for ``--trace 1``; and ``validate`` must refuse what the
driver refuses."""

import copy
import glob
import json
import os
import subprocess
import sys
import time

import pytest

from bench_toy import REPO, toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry

# The recorded slice that stands in for the device in a traced toy run, by the
# kind of cell and its chips: no cell is named, a later one finds its slice too.
SLICES = {("serve", 1): "serve_slice.json", ("train", 1): "train_slice.json", ("train", 4): "train_dp4_slice.json"}
CELLS = sorted(w["name"] for w in registry.load_manifest()["workloads"])


def _as_the_chip(line):
    """What a CPU stand-in cannot report, set to what the chip reports."""
    line = copy.deepcopy(line)
    line["device"].update(platform="tpu", kind="TPU v5 lite")
    return line


@pytest.fixture(scope="module")
def results(fake_chips, manifest, tmp_path_factory):
    """One untraced and one traced run of each kind of cell, at the toy width."""
    out = {"scratch": {}}
    for workload, traced in (
        ("serve16.chat-open", False), ("serve16.chat-open", True), ("serve16.batch-decode", True),
        ("train2.dense-4k", False), ("train2.dense-4k", True), ("train2.dp4-4k", True),
    ):
        scratch = out["scratch"][workload, traced] = str(tmp_path_factory.mktemp("scratch"))
        out[workload, traced] = bench_run.measure(
            toy_cell(manifest, workload), seed=2**31 + 11, seconds=3.0, traced=traced,
            t_process=time.monotonic(), scratch=scratch, platform="cpu",
        )
    return out


@pytest.mark.parametrize("workload", ["serve16.chat-open", "train2.dense-4k"])
def test_an_untraced_toy_run_gives_a_line_the_contract_accepts(results, manifest, workload):
    result = results[workload, False]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    line = bench_run.build_line(manifest, result)
    assert set(line) == set(contract.KEYS)
    assert set(line["metrics"]) == set(contract.expected_metrics(manifest, workload, False))
    contract.validate(line, manifest, workload, traced=False, platform="cpu")
    contract.validate_stdout("earlier line\n" + json.dumps(line) + "\n", manifest, workload, False, "cpu")
    # The same line claims no chip: the driver's platform check refuses it.
    with pytest.raises(contract.ContractError, match="platform"):
        contract.validate(line, manifest, workload, traced=False)


@pytest.mark.parametrize(
    "workload", ["serve16.chat-open", "serve16.batch-decode", "train2.dense-4k", "train2.dp4-4k"]
)
def test_a_traced_toy_run_with_the_recorded_trace_gives_a_line_the_contract_accepts(
    results, manifest, fixture_reduced, workload
):
    """Every metric declared for the cell is on the line, the span metrics too:
    the program has its spans on the CPU as on the chip."""
    result = dict(results[workload, True])
    # The CPU run traced, and found no TPU plane to reduce.
    assert result["trace"]["devices"] == [] and result["trace"]["busy_s"] == 0.0
    cell = result["cell"]
    result["trace"] = fixture_reduced(SLICES[cell["config"]["path"], cell["chips"]], workload)
    result["device"] = dict(result["device"], kind="TPU v5 lite", count=len(result["trace"]["devices"]))
    line = bench_run.build_line(manifest, result)
    assert set(line["metrics"]) == set(contract.expected_metrics(manifest, workload, True))
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    contract.validate(line, manifest, workload, traced=True, platform="cpu")
    json.loads(json.dumps(line))  # nothing on it that JSON cannot carry


@pytest.mark.parametrize("key", [("train2.dense-4k", False), ("train2.dp4-4k", True)])
def test_a_training_run_keeps_its_run_directory_under_its_own_scratch(results, key):
    """The trainer's default is a fixed /tmp/ray_tpu_results, where the parent's
    and the change's runs would meet; the cell puts it under the scratch that
    ``run.py`` makes under TMPDIR and removes."""
    made = glob.glob(os.path.join(results["scratch"][key], "results", "JaxTrainer_*"))
    assert len(made) == 1 and os.path.isdir(made[0])


def test_the_serving_reference_runs_after_the_window_and_after_the_peak_is_read(results):
    """Its float32 layer sits beside the model on the chip: the peak a run
    reports is read before it."""
    result = results["serve16.chat-open", False]
    assert len(result["notes"]["reference_gaps"]) == 2 and result["clock"]["reference_s"] > 0
    assert list(result["clock"]).index("setup_s") < list(result["clock"]).index("reference_s")


def test_a_traced_cpu_run_alone_is_refused_for_its_missing_device_time(results, manifest):
    line = bench_run.build_line(manifest, results["serve16.batch-decode", True])
    with pytest.raises(contract.ContractError):
        contract.validate(line, manifest, "serve16.batch-decode", traced=True, platform="cpu")


# -- what validate refuses ----------------------------------------------------

GOOD = {
    "correct": True, "attempted": 400, "failed": 0,
    "metrics": {
        "itl_p95_ms": {"value": 31.5, "unit": "ms"},
        "serve_tokens_per_s": {"value": 480.25, "unit": "tokens/s"},
        "setup_s": {"value": 40.1, "unit": "s"},
    },
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 13958643712},
}
TRACED = {
    "engine_host_gap_ms": "ms", "slot_occupancy_pct": "%", "decode_step_ms": "ms",
    "decode_mfu_roofline": "%", "replica_ready_s": "s", "device_idle_pct.serve": "%",
}


def _traced():
    line = copy.deepcopy(GOOD)
    for name, unit in TRACED.items():
        line["metrics"][name] = {"value": 12.5, "unit": unit}
    line["device"].update(busy_s=2.4, window_s=3.0)
    line["breakdown"] = {"device_ops": [["fusion.1", 1.2]], "idle_gaps": [["host between decode->decode", 0.4]]}
    return line


def test_the_good_lines_pass(manifest):
    contract.validate(GOOD, manifest, "serve16.batch-decode", traced=False)
    contract.validate(_traced(), manifest, "serve16.batch-decode", traced=True)


def _edit(line, path, value="__delete__"):
    line = copy.deepcopy(line)
    node = line
    for key in path[:-1]:
        node = node[key]
    if value == "__delete__":
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return line


@pytest.mark.parametrize(
    "traced, path, value, why",
    [
        (True, ("device", "busy_s"), "__delete__", "busy_s"),
        (True, ("device", "busy_s"), 0, "0 < busy_s"),
        (True, ("device", "busy_s"), 3.5, "busy_s <= window_s"),
        (True, ("device", "window_s"), None, "window_s"),
        (True, ("metrics", "decode_step_ms", "value"), float("nan"), "value"),
        (True, ("metrics", "itl_p95_ms"), "__delete__", "missing"),
        (True, ("metrics", "decode_step_ms"), None, "not {value, unit}"),
        (True, ("metrics", "decode_mfu_roofline", "value"), 131.0, "share of a peak"),
        (True, ("breakdown", "device_ops"), [["op", 1.0]] * 11, "at most 10"),
        (False, ("metrics", "itl_p95_ms", "value"), float("inf"), "value"),
        (False, ("metrics", "itl_p95_ms", "value"), None, "value"),
        (False, ("metrics", "itl_p95_ms"), 31.5, "not {value, unit}"),
        (False, ("metrics", "itl_p95_ms", "unit"), "s", "unit"),
        (False, ("metrics", "ttft_p90_ms"), {"value": 1.0, "unit": "ms"}, "not declared"),
        (False, ("metrics", "made_up"), {"value": 1.0, "unit": "ms"}, "not declared"),
        (False, ("metrics", "setup_s"), "__delete__", "missing"),
        (False, ("device",), "__delete__", "'device' is missing"),
        (False, ("device", "memory_peak_bytes"), None, "memory_peak_bytes"),
        (False, ("device", "memory_peak_bytes"), [1, 2], "memory_peak_bytes"),
        (False, ("device", "count"), 4, "count"),
        (False, ("device", "platform"), "cpu", "platform"),
        (False, ("device", "kind"), "", "kind"),
        (False, ("correct",), "yes", "correct"),
        (False, ("attempted",), 0, "attempted"),
        (False, ("failed",), 401, "failed"),
        (False, ("breakdown",), {}, "do not belong"),
        (False, ("notes",), "anything", "do not belong"),
    ],
)
def test_validate_refuses(manifest, traced, path, value, why):
    line = _edit(_traced() if traced else GOOD, path, value)
    with pytest.raises(contract.ContractError, match=why.replace("{", r"\{").replace("}", r"\}")):
        contract.validate(line, manifest, "serve16.batch-decode", traced=traced)


def test_a_traced_line_may_leave_out_per_layer_metrics_but_not_all_of_them(manifest):
    """A reader that finds nothing to read leaves its metric out (a parent
    without the span a PR adds); a line with no per-layer metric at all is a
    traced run that read nothing."""
    line = _traced()
    for name in list(TRACED)[:-1]:
        del line["metrics"][name]
        contract.validate(line, manifest, "serve16.batch-decode", traced=True)
    del line["metrics"][list(TRACED)[-1]]
    with pytest.raises(contract.ContractError, match="no per-layer metric"):
        contract.validate(line, manifest, "serve16.batch-decode", traced=True)


@pytest.mark.parametrize(
    "stdout, why",
    [
        (json.dumps(GOOD) + "\nworker 1f2e died: worker process exited with code 0\n", "not JSON"),
        (json.dumps(GOOD) + "\n\n", "not JSON"),
        ("", "nothing was printed"),
        ("[1, 2]\n", "not a JSON object"),
    ],
)
def test_a_line_after_the_json_or_none_at_all_is_refused(manifest, stdout, why):
    with pytest.raises(contract.ContractError, match=why):
        contract.validate_stdout(stdout, manifest, "serve16.batch-decode", False)
    assert contract.validate_stdout("log\n" + json.dumps(GOOD) + "\n", manifest, "serve16.batch-decode", False) == GOOD


# -- no chip, no line ----------------------------------------------------------


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_without_a_chip_the_command_exits_nonzero_and_prints_no_line(manifest, workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload", workload, "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert r.stdout == ""
    assert "need 'tpu'" in r.stderr


def test_with_too_few_chips_the_command_exits_nonzero(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "1")
    with pytest.raises(SystemExit) as e:
        bench_run.preflight(4)
    assert "has 1 TPU chips, the cell asks for 4" in str(e.value.code)
    assert bench_run.preflight(1) == 1


_WAITS = """
import ctypes, os, subprocess, sys, time
sys.path.insert(0, sys.argv[1])
from benchmarks import run as bench_run
ctypes.CDLL(None).prctl(36, 1, 0, 0, 0)  # as main does
nap = [sys.executable, "-c", "import time; time.sleep(%s)"]
quick = subprocess.Popen([a % 0.5 if "%s" in a else a for a in nap])
stuck = subprocess.Popen([a % 600 if "%s" in a else a for a in nap])
# A worker whose parent goes first: it is handed to this process, not to init.
parent = subprocess.Popen([sys.executable, "-c",
    "import subprocess, sys; print(subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(600)']).pid, flush=True)"],
    stdout=subprocess.PIPE, text=True)
orphan = int(parent.stdout.readline())
t0 = time.monotonic()
reaped = bench_run.wait_for_children(timeout_s=1.5)
print(reaped, bench_run.children(), os.path.exists(f"/proc/{orphan}"), round(time.monotonic() - t0, 1))
"""


def test_the_line_waits_until_every_process_of_the_run_has_ended():
    """Every process of a run is a child of ``run.py`` or is handed to it when
    its parent goes; it waits for all of them, and kills what will not go."""
    out = subprocess.run([sys.executable, "-c", _WAITS, REPO], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    reaped, left, orphan_alive, took = out.stdout.split("\n")[-2].replace("[]", "none").split()
    assert (int(reaped), left, orphan_alive) == (4, "none", "False")
    assert 1.5 <= float(took) < 10
    assert "still alive" in out.stderr and "all 4 processes of the run gone" in out.stderr
