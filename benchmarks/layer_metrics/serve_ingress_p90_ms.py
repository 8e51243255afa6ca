"""Serve: p90 of ``t_submit - t_recv`` over the window's requests (program_span):
from the proxy holding the whole request to ``engine.submit`` in the replica,
under load."""

from benchmarks.harness.spans import stage_p90_ms


def read(result):
    return stage_p90_ms(result, "t_recv_ns", "t_submit_ns")
