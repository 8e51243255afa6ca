"""model: the least time a decode step could take on this chip over the median
time it took. The least: every matmul weight read once plus the cached keys and
values of the tokens in context, at the HBM peak (memory bounds a decode step:
2 operations a weight byte). Context is taken as the mean running slots times
the mean of prompt plus half the output of the mix, from the mix's own file.

It is the share of the HBM roofline that the WHOLE decode step reaches, the
serving cells' counterpart of the training cells' ``mfu_pct`` (hence ``mfu`` in
its name): the bytes are the step's work (``costs.decode_step_bytes``), whatever
implements it, so this is the bound that stays when a kernel replaces the
operations that a per-kernel roofline names and that roofline falls silent."""

from benchmarks.harness import registry
from benchmarks.harness.peaks import peaks
from benchmarks.harness.readers import program_median_ms
from benchmarks.harness.traffic import mean_length


def read(result):
    step_ms = program_median_ms(result, "decode")
    polls = result["counters"].get("running_polls")
    if not step_ms or not polls:
        return None
    cell = result["cell"]
    mix = cell["traffic"]
    per_row = mean_length(mix["prompt_len"]) + mean_length(mix["output_len"]) / 2
    context = sum(polls) / len(polls) * per_row
    costs = registry.load_architecture(cell, "costs")
    least = costs.decode_step_bytes(cell["config"], int(context)) / peaks(result["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms / 1000.0)
