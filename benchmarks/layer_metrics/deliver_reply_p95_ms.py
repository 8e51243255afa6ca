"""Serve: p95 of ``t_got - t_sweep`` over the window's tokens (program_span): the
reply's way from the replica's call to the proxy's loop (pickle, transport,
``ray_tpu.get``, executor to loop)."""

from benchmarks.harness.deliveries import hop_p95_ms


def read(result):
    return hop_p95_ms(result, "t_sweep_ns", "t_got_ns")
