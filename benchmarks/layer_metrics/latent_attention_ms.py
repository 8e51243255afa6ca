"""latent attention: device time a decode step spends gathering the running
rows' view of the latent pool and attending over it, all layers together: the
operations that the configuration's ``trace_ops.latent_attention`` names
(device_trace)."""

from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    return ops_ms_per_decode_step(result, "latent_attention")
