"""linear attention: the least time a decode step's state update could take on
this chip over the time it took (``linear_state_ms``). The least: the state and
the convolution's carried rows of each running row (the mean ``rows`` of the
window's decode iterations; program_counter), read once and written once a
linear layer at the HBM peak. The program reads a state twice and writes it
once a step and moves every slot's, running or not: the share says what a
kernel that reads it once, and only the running rows', would save."""

from benchmarks.harness import registry
from benchmarks.harness.peaks import peaks
from benchmarks.harness.spans import decode_iterations
from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    took_ms = ops_ms_per_decode_step(result, "linear_state")
    recs = decode_iterations(result)
    costs = registry.load_architecture(result["cell"], "costs")
    if not took_ms or not recs or not hasattr(costs, "linear_state_bytes"):
        return None
    rows = sum(r["rows"] for r in recs) / len(recs)
    least = costs.linear_state_bytes(result["cell"]["config"], rows)
    return 100.0 * least / peaks(result["device"]["kind"])["hbm_bytes_per_s"] / (took_ms / 1000.0)
