"""Reading a token's way back: ``result["counters"]["spans"]["deliveries"]``
and ``["gc"]``.

``serve.llm`` keeps one record a streamed token (``ray_tpu/serve/llm/stats.py``,
``DELIVERY_FIELDS``; OBSERVABILITY.md, "serve.llm spans"): the request's number,
the token's index in its stream and seven ``CLOCK_MONOTONIC`` stamps from the
scheduler's ``llm.emit`` to the proxy's write to the socket, 0 where one was not
taken (the proxy's two last stamps of a stream's last batch). The ring leaves
the replica as packed int64, row after row. Beside it a ring of the process's
generation-2 collections.

The window is ``spans.window_ns``; a token counts if its ``t_emit_ns`` lies in
it and the ring still holds it (32768 records: the last 20 s of a cell that
streams 1600 tokens a second). A program without the ring (a parent commit)
gives every reader ``None``.
"""

from __future__ import annotations

import array
import collections

from benchmarks.harness.client import percentile
from benchmarks.harness.spans import window_ns


def _spans(result: dict, ring: str):
    spans = (result.get("counters") or {}).get("spans")
    return spans if spans and ring in spans else None


def records(result: dict):
    """(every record the ring holds as a tuple, oldest first; column by name),
    or None without the ring."""
    spans = _spans(result, "deliveries")
    if spans is None:
        return None
    names = spans["fields"]["deliveries"]
    flat = array.array("q")
    flat.frombytes(spans["deliveries"])
    width = len(names)
    recs = [tuple(flat[i:i + width]) for i in range(0, len(flat), width)]
    return recs, {name: i for i, name in enumerate(names)}


def hop_p95_ms(result: dict, start: str, end: str):
    """p95 over the window's tokens of ``end - start``, both stamps taken."""
    held, win = records(result), window_ns(result)
    if held is None or win is None:
        return None
    recs, col = held
    emit, a, b = col["t_emit_ns"], col[start], col[end]
    hops = [r[b] - r[a] for r in recs if win[0] <= r[emit] <= win[1] and r[a] > 0 and r[b] > 0]
    return percentile(hops, 95.0) / 1e6 if hops else None


def gap_p95_ms(result: dict, stamp: str):
    """p95 of the gaps between a stream's successive tokens at ``stamp``, pooled
    over the streams as the client pools the gaps between its arrivals; a gap
    counts where its later token is one of the window's and both were stamped."""
    held, win = records(result), window_ns(result)
    if held is None or win is None:
        return None
    recs, col = held
    rid, index, emit, at = col["rid"], col["index"], col["t_emit_ns"], col[stamp]
    streams = collections.defaultdict(list)
    for r in recs:
        streams[r[rid]].append(r)
    gaps = []
    for stream in streams.values():
        stream.sort(key=lambda r: r[index])
        gaps += [
            b[at] - a[at] for a, b in zip(stream, stream[1:])
            if b[index] == a[index] + 1 and a[at] > 0 and b[at] > 0 and win[0] <= b[emit] <= win[1]
        ]
    return percentile(gaps, 95.0) / 1e6 if gaps else None


def gc_pause_ms_per_s(result: dict):
    """Milliseconds of the replica's generation-2 collections that started
    inside the window, over the window's seconds."""
    spans, win = _spans(result, "gc"), window_ns(result)
    if spans is None or win is None:
        return None
    names = spans["fields"]["gc"]
    start, duration = names.index("t_start_ns"), names.index("duration_ns")
    paused = sum(r[duration] for r in spans["gc"] if win[0] <= r[start] <= win[1])
    return paused / 1e6 / float(result["seconds"])
