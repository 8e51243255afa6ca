"""model: operations the forward and backward of one step need (matmuls, head,
causal attention; no recompute, no embedding lookup) over the median device
time of the train-step program in the traced slice, over chips times the bf16
peak. From the trace alone: the host's clock and the profiler's own cost do not
enter it."""

from benchmarks.harness import costs
from benchmarks.harness.peaks import peaks
from benchmarks.harness.readers import program_median_ms


def read(result):
    step_ms = program_median_ms(result, "train_step")
    if not step_ms:
        return None
    seq = result["cell"]["traffic"]["seq_len"]
    flops = costs.train_step_flops(result["cell"]["config"], seq, result["train"]["batch"])
    peak = peaks(result["device"]["kind"])["bf16_flops_per_s"] * result["device"]["count"]
    return 100.0 * flops / (step_ms / 1000.0) / peak
