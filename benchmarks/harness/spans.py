"""Reading the program's own records: ``result["counters"]["spans"]``.

``serve.llm`` keeps, always on, a ring of iteration records, a ring of the
requests that ended and a ring of compilations (``ray_tpu/serve/llm/stats.py``;
OBSERVABILITY.md, "serve.llm spans"). ``LLMDeployment.get_stats()`` carries
them under ``"spans"``, and ``serve_cell.run`` stores the whole of the last
``get_stats()`` of a run as ``result["counters"]``, after the window.

``result`` carries no stamp of the window's opening, so the readers window by
the records' own stamps: the ``result["seconds"]`` seconds that end at the
newest stamp in the records. The client cuts the streams at most ``grace_s``
(5 s) after the window closes and the pre-roll offers the same mix, so this is
the window shifted by at most that much.

A program without these records (a parent commit) gives every reader ``None``.
"""

from __future__ import annotations

from benchmarks.harness.client import median, percentile



def _rows(result: dict, ring: str):
    """The ring's records as dicts by column name, or None without spans."""
    spans = (result.get("counters") or {}).get("spans")
    if not spans or ring not in spans:
        return None
    names, recs = spans["fields"][ring], spans[ring]
    if recs and not isinstance(recs[0], list):  # the iteration ring: one flat list, row after row
        recs = [recs[i:i + len(names)] for i in range(0, len(recs), len(names))]
    return [dict(zip(names, rec)) for rec in recs]


def window_ns(result: dict):
    """(first, last) nanosecond of the readers' window, or None."""
    newest = 0
    for rec in _rows(result, "iterations") or ():
        newest = max(newest, rec["t_start_ns"] + rec["llm.iteration"])
    for rec in _rows(result, "requests") or ():
        newest = max(newest, rec["t_done_ns"])
    if not newest:
        return None
    return newest - int(float(result["seconds"]) * 1e9), newest


def decode_iterations(result: dict):
    """Records of the window's iterations that ran a decode step."""
    recs, win = _rows(result, "iterations"), window_ns(result)
    if recs is None or win is None:
        return None
    return [r for r in recs if r["rows"] > 0 and win[0] <= r["t_start_ns"] <= win[1]]


def span_median_ms(result: dict, *names: str):
    """Median over the window's decode iterations of the named spans' sum."""
    recs = decode_iterations(result)
    if not recs:
        return None
    return median([sum(r[n] for n in names) for r in recs]) / 1e6


def requests(result: dict):
    """Records of the requests submitted inside the window."""
    recs, win = _rows(result, "requests"), window_ns(result)
    if recs is None or win is None:
        return None
    return [r for r in recs if win[0] <= r["t_submit_ns"] <= win[1]]


def stage_p90_ms(result: dict, start: str, end: str):
    """p90 over the window's requests of ``end - start``, both stamps reached."""
    recs = requests(result)
    if recs is None:
        return None
    spans = [r[end] - r[start] for r in recs if r[start] > 0 and r[end] > 0]
    return percentile(spans, 90.0) / 1e6 if spans else None


def compiles_in_window(result: dict):
    """Programs the backend built inside the window (0 is right): every
    shape is warmed up before it opens."""
    recs, win = _rows(result, "compiles"), window_ns(result)
    if recs is None or win is None:
        return None
    return sum(r["event"] == "backend_compile" and win[0] <= r["t_end_ns"] <= win[1] for r in recs)


def setup_s(result: dict, stage: str):
    spans = (result.get("counters") or {}).get("spans")
    return spans["setup"].get(stage) if spans else None
