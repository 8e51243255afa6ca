"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` in a
traced run). It is validated against ``harness/contract.py`` and printed only
after Serve and the cluster have shut down; everything else goes to standard
error. Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no such line: there is no CPU fallback. This process never
imports jax: the chip belongs to the replica or the train worker.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import contract, registry  # noqa: E402
from benchmarks.harness.common import log  # noqa: E402


def preflight(chips: int) -> int:
    """Chips on this host, found without importing jax."""
    from ray_tpu._private.node import detect_tpu_chips, pinned_jax_platform

    pinned = pinned_jax_platform()
    if pinned not in ("", "tpu"):
        sys.exit(f"benchmark: JAX_PLATFORMS puts workers on platform {pinned!r}; need 'tpu'")
    found = detect_tpu_chips()
    if found < chips:
        sys.exit(f"benchmark: this host has {found} TPU chips, the cell asks for {chips}")
    return found


def children() -> list[int]:
    """Processes whose parent is this one, dead-but-unreaped ones included."""
    me, found = os.getpid(), []
    for pid in os.listdir("/proc"):
        if pid.isdigit():
            try:
                with open(f"/proc/{pid}/stat") as f:
                    # "pid (comm) state ppid ...": comm may hold spaces and brackets
                    if int(f.read().rsplit(")", 1)[1].split()[1]) == me:
                        found.append(int(pid))
            except (OSError, IndexError, ValueError):
                pass  # gone meanwhile
    return found


def wait_for_children(timeout_s: float = 60.0) -> int:
    """Shutdown kills workers without waiting for them, and a replica whose
    main thread is gone still closes the TPU runtime for seconds (a dead thread
    group leader with live threads: no signal and no /proc entry says so, only
    ``waitpid`` does). ``main`` made this process the reaper of its orphaned
    descendants, so every process of the run is, or becomes, a child: wait for
    all of them, so that none is left when the result line is printed, not
    even as a dead entry where init does not reap. Returns how many it reaped."""
    t0, reaped = time.monotonic(), 0
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            break  # no child left
        if pid:
            reaped += 1
            continue
        waited = time.monotonic() - t0
        if waited > timeout_s + 10.0:
            sys.exit(f"benchmark: processes {children()} outlive the run and a SIGKILL")
        if waited > timeout_s:
            log(f"processes {children()} still alive {waited:.0f}s after shutdown: killed")
            for child in children():
                try:
                    os.kill(child, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            time.sleep(0.5)
        time.sleep(0.05)
    log(f"all {reaped} processes of the run gone {time.monotonic() - t0:.2f}s after shutdown")
    return reaped


def measure(cell: dict, *, seed: int, seconds: float, traced: bool, t_process: float,
            scratch: str, platform: str = "tpu") -> dict:
    """Runs the cell on the cluster that is up; returns the raw result."""
    from benchmarks.harness import serve_cell, train_cell

    runner = {"serve": serve_cell.run, "train": train_cell.run}[cell["config"]["path"]]
    result = runner(
        cell, seed=seed, seconds=seconds, traced=traced, t_process=t_process,
        scratch=scratch, platform=platform,
    )
    result.update(cell=cell, seconds=seconds, traced=traced)
    return result


def build_line(manifest: dict, result: dict, bench_dir: str = registry.BENCH_DIR) -> dict:
    """The result line from a run's raw result: each metric the manifest
    declares for the cell, read by the file that bears its name."""
    workload, traced = result["cell"]["name"], result["traced"]
    metrics = {}
    kinds = ["end_to_end"] + (["per_layer"] if traced else [])
    for kind in kinds:
        for entry in registry.cell_metrics(manifest, workload, kind):
            value = registry.load_metric(kind, entry["name"], bench_dir)(result)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = dict(result["device"])
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        from benchmarks.harness import trace

        device["busy_s"] = result["trace"]["busy_s"]
        device["window_s"] = result["trace"]["window_s"]
        line["breakdown"] = trace.breakdown(result["trace"])
    return line


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    manifest = registry.load_manifest()
    cell = registry.load_cell(manifest, args.workload)

    # Workers' output is forwarded to this process's stdout, and libraries
    # print at exit: from here on file descriptor 1 is stderr, and the one
    # line the driver reads goes to the real stdout, last, by itself.
    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)
    # Orphans of this run are handed to this process, not to init: wait_for_children.
    ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)  # PR_SET_CHILD_SUBREAPER

    from ray_tpu.util.compile_cache import export_compile_cache_dir

    cache_dir = export_compile_cache_dir(__file__)  # benchmarks/.jax_cache unless the caller set one
    n_chips = preflight(cell["chips"])
    # Sessions, results and the trace go under TMPDIR, which the driver gives
    # each side for itself; the runtime's default is a fixed /tmp/ray_tpu.
    scratch = tempfile.mkdtemp(prefix="ray_tpu_bench_")
    os.environ["RAY_TPU_SESSION_DIR_ROOT"] = os.path.join(scratch, "sessions")

    import ray_tpu

    try:
        ray_tpu.init(num_tpus=n_chips)
        try:
            log(f"workload={args.workload} seed={args.seed} chips={n_chips} cache={cache_dir}")
            result = measure(
                cell, seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
                t_process=T_PROCESS, scratch=scratch,
            )
        finally:
            ray_tpu.shutdown()
            wait_for_children()
        line = build_line(manifest, result)
        log(f"notes: {json.dumps(result.get('notes'), default=str)}")
        try:
            contract.validate(line, manifest, args.workload, bool(args.trace))
        except contract.ContractError as e:
            log(f"line: {json.dumps(line, default=str)[:4000]}")
            sys.exit(f"benchmark: the result line breaks the contract: {e}")
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    sys.stdout.flush()
    sys.stderr.flush()
    os.write(real_stdout, (json.dumps(line) + "\n").encode())
    os.close(real_stdout)


if __name__ == "__main__":
    main()
