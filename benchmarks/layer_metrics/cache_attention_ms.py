"""cache attention: device time a decode step spends gathering the running
rows' views of the two groups of the paged cache (the full layers' block
tables, the window layers' rings) and attending over them, all layers together:
the operations that the configuration's ``trace_ops.cache_attention`` names
(device_trace)."""

from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    return ops_ms_per_decode_step(result, "cache_attention")
