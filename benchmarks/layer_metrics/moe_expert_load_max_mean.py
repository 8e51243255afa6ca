"""routed experts: tokens on the fullest expert of a layer in a decode step,
mean over layers and steps (program_counter, kept on the device): the longest
group of the grouped matmuls, and how uneven the routing is (rows x k / E if even)."""

from benchmarks.harness.step_ops import moe_decode_mean


def read(result):
    return moe_decode_mean(result, "fullest_expert_load")
