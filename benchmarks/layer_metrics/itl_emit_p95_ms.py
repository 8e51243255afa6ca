"""LLM engine: p95 of the gaps between a stream's successive ``t_emit_ns``, pooled
over the streams (program_span): the engine's own ``itl_p95_ms``, before any
delivery."""

from benchmarks.harness.deliveries import gap_p95_ms


def read(result):
    return gap_p95_ms(result, "t_emit_ns")
