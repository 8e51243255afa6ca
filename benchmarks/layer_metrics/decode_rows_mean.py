"""LLM engine: mean ``rows`` of the window's decode steps (program_counter): the
batch size a step really had, from every step and not a 1 Hz poll."""

from benchmarks.harness.spans import decode_iterations


def read(result):
    recs = decode_iterations(result)
    return sum(r["rows"] for r in recs) / len(recs) if recs else None
