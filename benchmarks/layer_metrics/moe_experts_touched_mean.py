"""routed experts: distinct experts that a decode step's tokens reach in one
expert layer, mean over layers and steps (program_counter, kept on the
device): what a step must read of a layer's experts is this many of them."""

from benchmarks.harness.step_ops import moe_decode_mean


def read(result):
    return moe_decode_mean(result, "experts_touched")
