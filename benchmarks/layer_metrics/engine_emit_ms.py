"""LLM engine: median ``llm.emit`` per iteration with a decode step
(program_span): the token queues, finishing requests, releasing blocks."""

from benchmarks.harness.spans import span_median_ms


def read(result):
    return span_median_ms(result, "llm.emit")
