"""From a profiler trace (``.xplane.pb``) to numbers.

Two steps, so that the second can be checked on a small recorded slice:

``read_xplane(path)`` keeps, of every device plane, the two lines the
reduction reads, as plain lists ``[name, start_ns, duration_ns]``:

- ``XLA Modules``: one event per execution of a compiled program (a jitted
  function), named ``jit_<function>(<fingerprint>)``;
- ``XLA Ops``: one event per operation inside it, named by its whole HLO
  instruction, shortened here to ``%fusion.12 fusion f32[512]``. A Mosaic call
  is named by its instruction too (``%checkpoint.24 custom-call ...``), not by
  its kernel, and carries no statistic that names the kernel (v5e, PR 23): a
  kernel's time cannot be read until the program gives it a stable scope.

``reduce(raw, programs)`` gives, for each device plane:

- ``window_s``: the traced slice on that device, first operation's start to the
  last one's end;
- ``busy_s``: the *union* of the operation intervals inside it. Not a sum: a
  plane has several lines that cover the same time (a program, its operations,
  a step), and operations may nest;
- per program: the device seconds of each execution;
- the idle gaps between successive programs, each named by the programs on
  either side (the only thing the trace says about what the host was doing:
  the program carries no host spans yet);
- collective seconds, and the part of them in which no other operation ran.
"""

from __future__ import annotations

import glob
import os
import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")
CONTAINERS = ("while", "conditional", "call")  # their events span the operations inside them


def short_name(full: str) -> str:
    """An operation's event is named by its whole HLO instruction, often
    kilobytes long: keep ``<name> <opcode> <first result shape>``, as in
    ``%fusion.407 fusion f32[512]``."""
    head, _, rest = full.partition(" = ")
    if not rest:
        return full[:120]
    opcode = _OPCODE.search(" " + rest)
    shape = _SHAPE.search(rest)
    return " ".join(x for x in (head, opcode.group(1) if opcode else "", shape.group(0) if shape else "") if x)


def opcode(short: str) -> str:
    parts = short.split(" ")
    return parts[1] if len(parts) > 1 else ""


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (MODULES_LINE, OPS_LINE):
                lines[line.name] = [
                    [short_name(e.name), int(e.start_ns), int(e.duration_ns)] for e in line.events
                ]
        planes.append({"plane": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals: list) -> int:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    total, cur_lo, cur_hi = 0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _subtract_ns(intervals: list, others: list) -> int:
    """Length of ``intervals`` (disjoint or not) not covered by ``others``."""
    both = union_ns(intervals + others)
    return both - union_ns(others)


def _key(name: str, patterns: dict):
    for key, pattern in patterns.items():
        if re.search(pattern, name):
            return key
    return None


def reduce_plane(plane: dict, programs: dict) -> dict:
    modules = sorted(plane["lines"].get(MODULES_LINE, []), key=lambda e: e[1])
    ops = plane["lines"].get(OPS_LINE, [])
    spans = [(s, s + d) for _, s, d in ops if d > 0]
    if not spans:
        return {"plane": plane["plane"], "window_s": 0.0, "busy_s": 0.0}
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)

    by_program: dict = {}
    for name, _, d in modules:
        key = _key(name, programs) or name
        by_program.setdefault(key, []).append(d / 1e9)
    leaves = [(n, s0, d) for n, s0, d in ops if d > 0 and opcode(n) not in CONTAINERS]
    by_op: dict = {}
    for name, _, d in leaves:
        by_op[name] = by_op.get(name, 0) + d

    # Gaps between the programs the configuration names: the small unnamed
    # ones between them (dtype conversions of the inputs) belong to the host's
    # turn and would otherwise cut every gap in pieces.
    named = [(k, s0, d) for k, s0, d in ((_key(n, programs), s0, d) for n, s0, d in modules) if k]
    gaps = []
    for (a, a_s, a_d), (b, b_s, _) in zip(named, named[1:]):
        gap = b_s - (a_s + a_d)
        if gap > 0:
            gaps.append([a_s + a_d - lo, gap, f"{a}->{b}"])

    coll = [(s0, s0 + d) for name, s0, d in leaves if COLLECTIVE.match(opcode(name))]
    rest = [(s0, s0 + d) for name, s0, d in leaves if not COLLECTIVE.match(opcode(name))]
    return {
        "plane": plane["plane"],
        "window_s": (hi - lo) / 1e9,
        "busy_s": union_ns(spans) / 1e9,
        "programs": by_program,
        "ops": sorted(([n, d / 1e9] for n, d in by_op.items()), key=lambda r: -r[1])[:40],
        "gaps": [[s / 1e9, g / 1e9, label] for s, g, label in gaps],
        "collective_s": union_ns(coll) / 1e9,
        "collective_exposed_s": _subtract_ns(coll, rest) / 1e9 if coll else 0.0,
    }


def reduce(raw: dict, programs: dict) -> dict:
    """``programs`` maps a stable key to a regular expression over the names the
    trace gives the compiled programs; it comes from the configuration's file."""
    devices = [reduce_plane(p, programs) for p in raw["planes"]]
    devices = [d for d in devices if d["window_s"] > 0]
    if not devices:
        return {"devices": [], "window_s": 0.0, "busy_s": 0.0}
    n = len(devices)
    return {
        "devices": devices,
        # Averaged over the chips used, as the driver's idle share wants.
        "window_s": sum(d["window_s"] for d in devices) / n,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
    }


def reduce_file(log_dir: str, programs: dict) -> dict:
    """The newest trace under ``log_dir``, reduced."""
    return reduce(read_xplane(find_xplane(log_dir)), programs)


def breakdown(reduced: dict) -> dict:
    """The ten operations that took most device time and the ten longest idle
    gaps by the programs around them, of the first device."""
    if not reduced["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    dev = reduced["devices"][0]
    by_label: dict = {}
    for _, g, label in dev["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + g
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[n, s] for n, s in dev["ops"][:10]],
        "idle_gaps": [[f"host between {label}", s] for label, s in gaps],
    }
