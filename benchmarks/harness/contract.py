"""The shape of the last line a run prints, as the driver's check words it:

    the last line the benchmark printed is a JSON object with the keys
    correct, attempted, failed, metrics and device, where metrics gives each
    metric of this workload as its value and unit, and device gives platform,
    kind, count, memory_peak_bytes and, in a traced run, window_s and busy_s
    (above 0, at most window_s); other keys are ignored.

``run.py`` validates its own line before printing it and exits non-zero with
the reason instead of printing one the driver cannot read (PR 22 was thrown
away for exactly that). A ``--trace 0`` line carries the cell's end-to-end
metrics; a ``--trace 1`` line its per-layer metrics and, beside them, the
end-to-end metrics as that run measured them.
"""

from __future__ import annotations

import json
import math

from benchmarks.harness.registry import cell_metrics

KEYS = ("correct", "attempted", "failed", "metrics", "device")
PLATFORM = "tpu"


class ContractError(ValueError):
    pass


def expected_metrics(manifest: dict, workload: str, traced: bool) -> dict:
    """name -> unit of every metric the line of such a run must carry."""
    entries = cell_metrics(manifest, workload, "end_to_end")
    if traced:
        entries = entries + cell_metrics(manifest, workload, "per_layer")
    return {m["name"]: m["unit"] for m in entries}


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def validate(
    line: dict, manifest: dict, workload: str, traced: bool, platform: str = PLATFORM
) -> None:
    """Raises ContractError naming the first fault of ``line``."""
    if not isinstance(line, dict):
        raise ContractError("the line is not a JSON object")
    for key in KEYS:
        if key not in line:
            raise ContractError(f"key {key!r} is missing")
    extra = set(line) - set(KEYS) - ({"breakdown"} if traced else set())
    if extra:
        raise ContractError(f"keys {sorted(extra)} do not belong on the line")
    if not isinstance(line["correct"], bool):
        raise ContractError("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) or line[key] < 0:
            raise ContractError(f"{key} is not a count")
    if line["attempted"] < 1 or line["failed"] > line["attempted"]:
        raise ContractError(f"attempted {line['attempted']}, failed {line['failed']}")

    chips = next(w["chips"] for w in manifest["workloads"] if w["name"] == workload)
    want = expected_metrics(manifest, workload, traced)
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        raise ContractError("metrics is not an object")
    for name in metrics:
        if name not in want:
            raise ContractError(f"metric {name!r} is not declared for {workload}")
    for name, unit in want.items():
        got = metrics.get(name)
        if not isinstance(got, dict) or set(got) != {"value", "unit"}:
            raise ContractError(f"metric {name!r} is missing or not {{value, unit}}")
        if not _number(got["value"]):
            raise ContractError(f"metric {name!r} has value {got['value']!r}")
        if got["unit"] != unit:
            raise ContractError(f"metric {name!r} has unit {got['unit']!r}, declared {unit!r}")
        if name.endswith("_roofline") or "mfu" in name:
            if not 0 < got["value"] <= 105:
                raise ContractError(f"{name} = {got['value']} is not a share of a peak")

    device = line["device"]
    if not isinstance(device, dict):
        raise ContractError("device is not an object")
    if device.get("platform") != platform:
        raise ContractError(f"device.platform is {device.get('platform')!r}, need {platform!r}")
    if not isinstance(device.get("kind"), str) or not device["kind"]:
        raise ContractError("device.kind is not a name")
    if platform == PLATFORM and device.get("count") != chips:  # a CPU stand-in sees the host's devices
        raise ContractError(f"device.count is {device.get('count')!r}, the cell asks for {chips}")
    peak = device.get("memory_peak_bytes")
    if not isinstance(peak, int) or isinstance(peak, bool) or peak <= 0:
        raise ContractError(f"device.memory_peak_bytes is {peak!r}")
    if traced:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not _number(busy) or not _number(window):
            raise ContractError(f"traced run: busy_s {busy!r}, window_s {window!r}")
        if not 0 < busy <= window:
            raise ContractError(f"traced run: need 0 < busy_s <= window_s, got {busy} and {window}")
        breakdown = line.get("breakdown")
        if breakdown is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = breakdown.get(key)
                if not isinstance(rows, list) or len(rows) > 10:
                    raise ContractError(f"breakdown.{key} is not a list of at most 10")
                for row in rows:
                    if not (len(row) == 2 and isinstance(row[0], str) and _number(row[1])):
                        raise ContractError(f"breakdown.{key} row {row!r} is not [name, seconds]")


def validate_stdout(
    stdout: str, manifest: dict, workload: str, traced: bool, platform: str = PLATFORM
) -> dict:
    """The driver reads the last line of standard output: it has to be the
    JSON object, with nothing after it. Returns the parsed line."""
    lines = stdout.split("\n")
    if lines and lines[-1] == "":
        lines.pop()  # the newline that ends the last line
    if not lines:
        raise ContractError("nothing was printed")
    try:
        line = json.loads(lines[-1])
    except ValueError:
        raise ContractError(f"the last line is not JSON: {lines[-1][:120]!r}") from None
    validate(line, manifest, workload, traced, platform)
    return line
