"""LLM engine: median ``llm.iteration`` over the window's iterations that ran a
decode step (program_span; one pass of the scheduler loop, device wait included)."""

from benchmarks.harness.spans import span_median_ms


def read(result):
    return span_median_ms(result, "llm.iteration")
