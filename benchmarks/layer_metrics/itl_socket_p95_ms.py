"""Serve: p95 of the gaps between a stream's successive ``t_wrote_ns``, pooled over
the streams (program_span): what the program hands the network. ``itl_p95_ms``
less this is the loopback and the harness's client loop."""

from benchmarks.harness.deliveries import gap_p95_ms


def read(result):
    return gap_p95_ms(result, "t_wrote_ns")
