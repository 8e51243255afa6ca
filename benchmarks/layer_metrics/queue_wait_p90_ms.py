"""LLM engine: p90 of ``t_admit - t_submit`` over the window's requests
(program_span): the wait for a slot and blocks."""

from benchmarks.harness.spans import stage_p90_ms


def read(result):
    return stage_p90_ms(result, "t_submit_ns", "t_admit_ns")
