"""linear attention: device time a decode step spends moving the running rows'
recurrent state on by one token, all linear layers together: the convolution
over the carried rows, the gated delta rule's step and the write-back, the
operations that the configuration's ``trace_ops.linear_state`` names
(device_trace; the program scopes them ``linear_attention_step``)."""

from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    return ops_ms_per_decode_step(result, "linear_state")
