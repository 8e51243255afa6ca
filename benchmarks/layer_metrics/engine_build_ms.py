"""LLM engine: median ``llm.decode.build`` + ``llm.prefill.build`` per iteration
with a decode step (program_span): block allocation, the NumPy tables and
their way onto the device."""

from benchmarks.harness.spans import span_median_ms


def read(result):
    return span_median_ms(result, "llm.decode.build", "llm.prefill.build")
