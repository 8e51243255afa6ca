"""LLM engine: median ``llm.admit`` per iteration with a decode step
(program_span): the sweep of cancelled requests, the reaper and admission."""

from benchmarks.harness.spans import span_median_ms


def read(result):
    return span_median_ms(result, "llm.admit")
