"""LLM engine: p90 of ``t_first - t_admit`` over the window's requests
(program_span): the prompt's chunks, one an iteration between everybody's
decode steps. Engine-side TTFT is this plus the queue wait."""

from benchmarks.harness.spans import stage_p90_ms


def read(result):
    return stage_p90_ms(result, "t_admit_ns", "t_first_ns")
