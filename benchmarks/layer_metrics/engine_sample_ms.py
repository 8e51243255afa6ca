"""LLM engine: median ``llm.sample`` per iteration with a decode step
(program_span): every row's draw on the host, the chip idle meanwhile."""

from benchmarks.harness.spans import span_median_ms


def read(result):
    return span_median_ms(result, "llm.sample")
