"""LLM engine: the share of tracing and lowering in which the building thread
did not run, 100 x (1 - its CPU time / the stages' wall) (program_span)."""

from benchmarks.harness.setup_stages import not_running_pct


def read(result):
    return not_running_pct(result, "trace", "lower")
