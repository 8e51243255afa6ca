"""short convolution: device time a decode step spends in the conv layers'
mixers, all conv layers together: the input projection, the two gates, the
filter over the carried rows, their write-back and the output projection, the
operations that the configuration's ``trace_ops.short_conv`` names
(device_trace; the program scopes them ``short_conv_step``)."""

from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    return ops_ms_per_decode_step(result, "short_conv")
