"""Serve: p95 of ``t_sweep - t_yield`` over the window's tokens (program_span): the
event lay ready until a poll took it. The proxy had not asked yet, the call
waited for one of the actor's places, or the call's own way in: ``t_asked_ns``
and ``t_enter_ns`` split it by hand."""

from benchmarks.harness.deliveries import hop_p95_ms


def read(result):
    return hop_p95_ms(result, "t_yield_ns", "t_sweep_ns")
