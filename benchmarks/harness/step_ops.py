"""What the readers of a kernel's time and of the expert counters share.

A configuration may name, under ``trace_ops``, regular expressions over the
short names the reduced trace gives a device's operations (``%name opcode
shape``, ``harness/trace.py``): the operations of one kernel inside the decode
program, shapes included, so that the prefill program's are not taken with
them. The program also scopes these regions (``moe_experts``,
``cache_attention``: ``jax.named_scope``), which would survive a change of
kernel or of shapes; the reduction keeps nothing of an operation but name,
opcode and shape, so a reader cannot match a scope until ``trace.py`` does
(PERF.md section 7). ``get_stats()["moe"]`` holds the expert counters the
programs keep on the device (OBSERVABILITY.md, "serve.llm spans"). A
configuration without the key, a program without the counters and a trace
without such operations give every reader here ``None``.
"""

from __future__ import annotations

import re

from benchmarks.harness import registry
from benchmarks.harness.readers import device0


def ops_ms_per_decode_step(result: dict, key: str):
    """Device milliseconds a decode step spends in the operations that the
    configuration's ``trace_ops[key]`` names: their seconds over the traced
    slice (of the forty longest operations the reduction keeps) over the
    decode program's executions in it."""
    pattern = (result["cell"]["config"].get("trace_ops") or {}).get(key)
    dev = device0(result)
    steps = len(dev["programs"].get("decode") or ()) if dev else 0
    if not pattern or not steps:
        return None
    seconds = sum(s for name, s in dev["ops"] if re.search(pattern, name))
    return 1000.0 * seconds / steps if seconds else None


def moe_decode_mean(result: dict, column: str):
    """Mean over the expert layers and over the run's decode steps under
    traffic of one summed column of the expert counters (``experts_touched``,
    ``fullest_expert_load``). The counters run from the replica's start and are
    read once: the single-row steps before the traffic (the check's, the
    probes'), which the cell's architecture counts from its configuration
    (``costs.moe_steps_alone``), are taken out; the pre-roll's steps stay."""
    moe = ((result.get("counters") or {}).get("moe") or {}).get("decode")
    if not moe or not moe.get("steps") or not moe.get(column):
        return None
    cell = result["cell"]
    alone = registry.load_architecture(cell, "costs").moe_steps_alone(cell["config"], bool(result.get("trace")))
    steps = moe["steps"] - alone["steps"]
    if steps <= 0:
        return None
    return (sum(moe[column]) / len(moe[column]) - alone["steps"] * alone[column]) / steps
