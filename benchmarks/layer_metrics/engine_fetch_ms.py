"""LLM engine: median ``llm.decode.fetch`` (program_span): the wait for the
device and the copy of the logits to the host; less ``decode_step_ms`` it is
the wake-up and the copy."""

from benchmarks.harness.spans import span_median_ms


def read(result):
    return span_median_ms(result, "llm.decode.fetch")
