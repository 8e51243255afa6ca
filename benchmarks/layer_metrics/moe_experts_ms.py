"""routed experts: device time a decode step spends in the grouped matmuls over
the experts, all expert layers together: the operations that the
configuration's ``trace_ops.moe_experts`` names (device_trace)."""

from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    return ops_ms_per_decode_step(result, "moe_experts")
