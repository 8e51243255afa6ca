"""model: device time a pass over blocks spends behind its layers: the head
over every position of every row's block, the draw with each position's own
noise, the confidence and the transfer, the operations that the
configuration's ``trace_ops.block_draw`` names (device_trace; the program
scopes them ``block_draw``). It UNDER-READS by about a fifth in
``sdar6.rollout-block`` (4.55 ms read of ~5.7): the draw's row maximum bears
the short name of a prefill chunk's norms, the reduced trace sums an operation
by name over both programs, and the pattern leaves it out rather than mix two
programs (the configuration's ``trace_ops.why``)."""

from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    return ops_ms_per_decode_step(result, "block_draw")
