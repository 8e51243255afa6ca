"""Reading a start's own records (ISSUE 52): where ``setup_s`` goes before the
harness's first request.

A replica writes one record a stage of its start (``ray_tpu/serve/llm/stats.py``,
``STAGE_FIELDS``: the deployment's ``jax_import``, ``backend``, ``params``, the
engine's ``pool``, ``jit_build`` and, a program of ``_build_programs``,
``trace``, ``lower``, ``compile``, ``first_run``), five stamps of its own way from
the controller's decision to its first health check (``setup_stamps``), and
plain-int totals of the compile listener's events (``compile_totals``) beside the
ring; all leave in ``get_stats()["spans"]`` and so in ``result["counters"]``. A
trainer's worker stamps its start four times, and every report's metrics carry
them under ``_trainer_start``: ``train_cell.run`` keeps the last report whole as
``result["train"]``.

All of it is written before the replica is ready, so before the window. The
compile ring keeps running: "before the window" is before
``spans.window_ns(result)[0]``. A program without these records (a parent
commit) gives every reader ``None``.
"""

from __future__ import annotations

from benchmarks.harness.spans import _rows, window_ns


def _spans(result: dict, key: str):
    return ((result.get("counters") or {}).get("spans") or {}).get(key)


def stages(result: dict, *names: str):
    """The start's stage records of the named stages, or None without records."""
    recs = _rows(result, "stages")
    if not recs:
        return None
    return [r for r in recs if r["stage"] in names]


def wall_s(recs) -> float:
    return sum(r["t_end_ns"] - r["t_start_ns"] for r in recs) / 1e9


def stage_wall_s(result: dict, *names: str):
    """Seconds the named stages took, one after another; None where none was recorded."""
    recs = stages(result, *names)
    return wall_s(recs) if recs else None


def not_running_pct(result: dict, *names: str):
    """The share of the named stages' wall in which their thread did not run:
    it waited for the interpreter, for I/O or for the device."""
    recs = stages(result, *names)
    if not recs or not wall_s(recs):
        return None
    return 100.0 * max(0.0, 1.0 - sum(r["cpu_ns"] for r in recs) / 1e9 / wall_s(recs))


def stamp_span_s(stamps, start: str, end: str):
    """``end - start`` of two stamps of one clock, both taken (0 = not taken)."""
    if not stamps or not stamps.get(start) or not stamps.get(end):
        return None
    return (stamps[end] - stamps[start]) / 1e9


def replica_stamp_span_s(result: dict, start: str, end: str):
    return stamp_span_s(_spans(result, "setup_stamps"), start, end)


def trainer_stamp_span_s(result: dict, start: str, end: str):
    return stamp_span_s((result.get("train") or {}).get("_trainer_start"), start, end)


def compiled_before_window(result: dict, total: str):
    """[count, nanoseconds] of the listener's ``total`` (``backend_compile``,
    ``cache_retrieval``, ``compiled_afresh``) before the window: the plain-int
    totals, which no start overflows, less what the ring holds from the
    window's opening on (there should be nothing)."""
    totals, ring = _spans(result, "compile_totals"), _rows(result, "compiles")
    if not totals or ring is None:
        return None
    win = window_ns(result)
    event = "cache_retrieval" if total == "cache_retrieval" else "backend_compile"
    later = [] if win is None else [
        r for r in ring
        if r["t_end_ns"] >= win[0] and r["event"] == event and (total != "compiled_afresh" or r.get("afresh"))
    ]
    return totals[total][0] - len(later), totals[total][1] - sum(r["duration_ns"] for r in later)
