"""LLM engine: passes of the window that ran a prefill chunk (``prefill_tokens``
> 0 in the iteration ring: as a program of its own or inside the decode step)
over all its passes, per cent (program_span). In a full house whose slots turn
over all the time it says how often a decode step waits behind a chunk: the
gap between two tokens of a stream is a step, or a step and a chunk."""

from benchmarks.harness import spans


def read(result):
    recs, win = spans._rows(result, "iterations"), spans.window_ns(result)
    if not recs or win is None:
        return None
    passes = [r for r in recs if win[0] <= r["t_start_ns"] <= win[1]]
    return 100.0 * sum(r["prefill_tokens"] > 0 for r in passes) / len(passes) if passes else None
