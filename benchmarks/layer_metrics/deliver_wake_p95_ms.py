"""Serve: p95 of ``t_yield - t_emit`` over the window's tokens (program_span): the
stream's pump thread wakes on the request's queue and encodes the event."""

from benchmarks.harness.deliveries import hop_p95_ms


def read(result):
    return hop_p95_ms(result, "t_emit_ns", "t_yield_ns")
