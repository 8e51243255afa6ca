"""Serve: p95 of ``t_wrote - t_got`` over the window's tokens (program_span): the
proxy's loop from holding the batch to the return of the chunk's ``send``."""

from benchmarks.harness.deliveries import hop_p95_ms


def read(result):
    return hop_p95_ms(result, "t_got_ns", "t_wrote_ns")
