"""Distributed tracing.

Analog of the reference's util/tracing/tracing_helper.py (560 LoC of OTel
wrapping): opt-in span propagation across task/actor boundaries. Instead of
requiring OpenTelemetry, span context (trace id, span id, parent id) rides
inside every TaskSpec, each task execution records its span into the task
event log, and ``export_spans()`` reconstructs the trace tree from the GCS —
the same data also renders causally in ``ray_tpu timeline``. An OTel exporter
can be layered on top by walking ``export_spans()``.

Enable with ``RAY_TPU_TRACING=1`` (or ``enable_tracing()`` before submitting).
"""

from __future__ import annotations

import contextvars
import os
import uuid

_enabled: bool | None = None
# (trace_id, span_id) of the currently-executing task in this process.
_current: contextvars.ContextVar = contextvars.ContextVar("ray_tpu_trace", default=None)


def tracing_enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get("RAY_TPU_TRACING", "0") == "1"
    return _enabled


def enable_tracing():
    """Enable tracing cluster-wide. The flag is stored in the GCS KV so
    workers on EVERY node pick it up at startup (a plain env var would only
    reach workers forked by a same-process raylet)."""
    global _enabled
    _enabled = True
    os.environ["RAY_TPU_TRACING"] = "1"
    _publish_flag_if_connected()


def _publish_flag_if_connected():
    from ray_tpu._private import worker_context

    cw = worker_context.get_core_worker_if_initialized()
    if cw is None:
        return
    try:
        cw.gcs.call("kv_put", {"key": "tracing:enabled", "value": b"1", "overwrite": True})
    except Exception:
        pass


def get_current_span_context() -> dict | None:
    """(driver or inside a task) the active span context, if tracing."""
    cur = _current.get()
    if cur is None:
        return None
    return {"trace_id": cur[0], "span_id": cur[1]}


def child_span_context() -> dict:
    """Build the span context to attach to an outgoing task submission."""
    cur = _current.get()
    if cur is None:
        # Root: new trace originating at this driver/task.
        return {"trace_id": uuid.uuid4().hex, "span_id": uuid.uuid4().hex[:16], "parent_id": ""}
    return {"trace_id": cur[0], "span_id": uuid.uuid4().hex[:16], "parent_id": cur[1]}


def set_task_context(trace_ctx: dict | None):
    """Called by the worker as a task starts executing. Always sets (clearing
    for untraced tasks so a reused worker can't leak the previous task's
    span); returns a token for contextvars reset."""
    if trace_ctx:
        return _current.set((trace_ctx.get("trace_id"), trace_ctx.get("span_id")))
    return _current.set(None)


def reset_task_context(token):
    _current.reset(token)


# ---------------------------------------------------------------------------
# Hop-level dispatch budget (config.hop_timing / RAY_TPU_HOP_TIMING=1)
# ---------------------------------------------------------------------------
#
# Each completed dispatch leaves a record of monotonic stage timestamps on
# the owner (CoreWorker.hop_records()); the stages chain differently per
# transport path. summarize_hop_records() turns the raw records into a
# per-path, per-hop latency budget.

# Ordered stage transitions per path. A "hop" that crosses a process
# boundary is a wire frame; the rest are in-process thread/loop handoffs.
_HOP_CHAINS = {
    # Warm-lease / steady-state normal task: owner ships worker-direct, the
    # worker replies owner-direct — the raylet is not on the path at all.
    "lease": [
        ("submit", "ship"),          # user thread -> owner IO loop + stage
        ("ship", "worker_recv"),     # WIRE owner -> worker
        ("worker_recv", "exec_start"),  # worker loop -> main-thread exec queue
        ("exec_start", "exec_end"),  # user code
        ("exec_end", "reply"),       # worker main thread -> worker IO loop
        ("reply", "owner_recv"),     # WIRE worker -> owner
        ("owner_recv", "wake"),      # owner IO loop -> blocked getter thread
    ],
    "actor": [
        ("submit", "ship"),
        ("ship", "worker_recv"),
        ("worker_recv", "exec_start"),
        ("exec_start", "exec_end"),
        ("exec_end", "reply"),
        ("reply", "owner_recv"),
        ("owner_recv", "wake"),
    ],
    # Classic raylet-queued path (PG / SPREAD / affinity / streaming): two
    # extra raylet stages on the way in, plus the task_finished frame.
    "classic": [
        ("submit", "ship"),
        ("ship", "raylet_recv"),         # WIRE owner -> raylet
        ("raylet_recv", "raylet_dispatch"),  # raylet queue + grant
        ("raylet_dispatch", "worker_recv"),  # WIRE raylet -> worker
        ("worker_recv", "exec_start"),
        ("exec_start", "exec_end"),
        ("exec_end", "reply"),
        ("reply", "owner_recv"),         # WIRE worker -> owner
        ("owner_recv", "wake"),
    ],
}

# Serial wire frames (process boundary crossings) on each path's critical
# path. The warm-lease fast path is 2 — matching the reference's steady
# state (owner->worker push, worker->owner reply); classic is 4 (submit,
# dispatch, task_done, piggybacked task_finished push).
_SERIAL_PROCESS_HOPS = {"lease": 2, "actor": 2, "classic": 4, "compiled": 0}
_RAYLET_RPCS = {"lease": 0, "actor": 0, "classic": 2, "compiled": 0}


def _pctl(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


def _compiled_transitions(recs: list[dict]) -> tuple[dict, list[float]]:
    """Per-record dynamic chains for compiled-graph iterations: the stage
    set depends on the DAG (``s{i}_recv``/``s{i}_exec`` per stage), so the
    chain is derived from each record's monotonic stamps sorted by time —
    and the very absence of any ``raylet_*`` stamp is the recorded evidence
    that compiled dispatch issues zero raylet RPCs per iteration."""
    trans: dict[str, list[float]] = {}
    totals: list[float] = []
    for rec in recs:
        stamps = sorted((v, k) for k, v in rec.items() if isinstance(v, float))
        for (va, ka), (vb, kb) in zip(stamps, stamps[1:]):
            trans.setdefault(f"{ka}->{kb}", []).append((vb - va) * 1e6)
        if len(stamps) >= 2:
            totals.append((stamps[-1][0] - stamps[0][0]) * 1e6)
    return trans, totals


def summarize_hop_records(records: list[dict]) -> dict:
    """Aggregate raw hop records into a per-path, per-stage µs budget."""
    by_path: dict[str, list[dict]] = {}
    for rec in records:
        by_path.setdefault(rec.get("path", "classic"), []).append(rec)
    out: dict = {}
    for path, recs in by_path.items():
        stages: dict[str, dict] = {}
        totals: list[float] = []
        if path == "compiled":
            trans, totals = _compiled_transitions(recs)
            for key in trans:
                deltas = sorted(trans[key])
                stages[key] = {
                    "p50_us": round(_pctl(deltas, 0.5), 1),
                    "p90_us": round(_pctl(deltas, 0.9), 1),
                    "n": len(deltas),
                }
            totals.sort()
            out[path] = {
                "count": len(recs),
                "stages_us": stages,
                "total_p50_us": round(_pctl(totals, 0.5), 1) if totals else None,
                "total_p90_us": round(_pctl(totals, 0.9), 1) if totals else None,
                "serial_process_hops": _SERIAL_PROCESS_HOPS.get(path),
                "raylet_rpcs_per_call": _RAYLET_RPCS.get(path),
            }
            continue
        chain = _HOP_CHAINS.get(path, _HOP_CHAINS["classic"])
        for a, b in chain:
            deltas = sorted(
                (rec[b] - rec[a]) * 1e6
                for rec in recs
                if a in rec and b in rec and rec[b] >= rec[a]
            )
            if deltas:
                stages[f"{a}->{b}"] = {
                    "p50_us": round(_pctl(deltas, 0.5), 1),
                    "p90_us": round(_pctl(deltas, 0.9), 1),
                    "n": len(deltas),
                }
        for rec in recs:
            first, last = chain[0][0], chain[-1][1]
            if first in rec and last in rec:
                totals.append((rec[last] - rec[first]) * 1e6)
        totals.sort()
        out[path] = {
            "count": len(recs),
            "stages_us": stages,
            "total_p50_us": round(_pctl(totals, 0.5), 1) if totals else None,
            "total_p90_us": round(_pctl(totals, 0.9), 1) if totals else None,
            "serial_process_hops": _SERIAL_PROCESS_HOPS.get(path),
            "raylet_rpcs_per_call": _RAYLET_RPCS.get(path),
        }
    return out


def format_hop_table(summary: dict) -> str:
    """Human-readable per-hop µs table from summarize_hop_records output."""
    lines = []
    for path, info in summary.items():
        lines.append(
            f"[{path}] n={info['count']}  total p50={info['total_p50_us']}us "
            f"p90={info['total_p90_us']}us  serial process hops="
            f"{info['serial_process_hops']}  raylet rpcs/call={info['raylet_rpcs_per_call']}"
        )
        lines.append(f"  {'stage':<30} {'p50 us':>10} {'p90 us':>10} {'n':>6}")
        for stage, s in info["stages_us"].items():
            lines.append(f"  {stage:<30} {s['p50_us']:>10.1f} {s['p90_us']:>10.1f} {s['n']:>6}")
    return "\n".join(lines)


def drain_hop_records() -> list[dict]:
    """Hop records from the connected core worker (empty when hop timing is
    off or nothing has completed), cleared as they are read — use between
    measurement phases so an earlier phase's records can't be evicted from
    the bounded ring buffer by a later, faster phase."""
    from ray_tpu._private import worker_context

    cw = worker_context.get_core_worker_if_initialized()
    if cw is None:
        return []
    return cw.drain_hop_records()


def hop_trace_events(records: list[dict], mono_to_wall: float | None = None) -> list[dict]:
    """Convert hop records into Chrome-trace events that render causally
    next to task rows: per-stage ``X`` slices on a ``hop:<path>`` track plus
    a flow arrow (``s``/``f``) from submit to wake, so a dispatch's wire
    hops line up under the task that caused them.

    ``mono_to_wall`` converts monotonic stamps onto the wall-clock axis the
    task events use; stamps from every process on a host share
    CLOCK_MONOTONIC, so one offset suffices. Records whose stamps span an
    impossible interval are dropped: a record mixing stamps from hosts with
    different monotonic epochs (multi-node classic dispatch) would sort its
    stages by boot-time delta, not causality, and render garbage."""
    import time as _time

    if mono_to_wall is None:
        mono_to_wall = _time.time() - _time.monotonic()
    events: list[dict] = []
    for n, rec in enumerate(records):
        stamps = sorted((v, k) for k, v in rec.items() if isinstance(v, float))
        if len(stamps) < 2:
            continue
        if stamps[-1][0] - stamps[0][0] > 600.0:
            continue  # cross-host monotonic epochs — not renderable
        path = rec.get("path", "classic")
        pid = f"hop:{path}"
        tid = rec.get("name", "dispatch")
        flow_id = (hash(rec.get("task_id") or f"{tid}:{n}") & 0x7FFFFFFF) or 1
        for (va, ka), (vb, kb) in zip(stamps, stamps[1:]):
            events.append(
                {
                    "name": f"{ka}->{kb}",
                    "cat": "hop",
                    "ph": "X",
                    "ts": (va + mono_to_wall) * 1e6,
                    "dur": max(vb - va, 0) * 1e6,
                    "pid": pid,
                    "tid": tid,
                    "args": {"task_id": rec.get("task_id"), "path": path},
                }
            )
        first_ts = (stamps[0][0] + mono_to_wall) * 1e6
        last_ts = (stamps[-1][0] + mono_to_wall) * 1e6
        events.append(
            {"name": "dispatch", "cat": "hop", "ph": "s", "id": flow_id,
             "ts": first_ts, "pid": pid, "tid": tid}
        )
        events.append(
            {"name": "dispatch", "cat": "hop", "ph": "f", "bp": "e", "id": flow_id,
             "ts": last_ts, "pid": pid, "tid": tid}
        )
    return events


def export_spans(address=None) -> list[dict]:
    """Reconstruct spans from the task-event log: one span per task with
    trace/span/parent ids, name, timestamps, and status."""
    from ray_tpu.util.state import list_tasks

    spans = []
    for row in list_tasks(address=address):
        ctx = row.get("trace_ctx") or {}
        if not ctx.get("trace_id"):
            continue
        spans.append(
            {
                "trace_id": ctx["trace_id"],
                "span_id": ctx.get("span_id"),
                "parent_id": ctx.get("parent_id") or None,
                "name": row.get("name"),
                "task_id": row.get("task_id"),
                "start_time": row.get("start_time"),
                "end_time": row.get("end_time"),
                "status": row.get("state"),
                "node_id": row.get("node_id"),
            }
        )
    return spans
