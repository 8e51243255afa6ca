"""short convolution: the least time a decode step's conv mixers could take on
this chip over the time they took (``short_conv_ms``). The least: the two
projections and the filter of every conv layer read once, and the carried rows
of each running row (the mean ``rows`` of the window's decode iterations;
program_counter) read once and written once, at the HBM peak: memory bounds
them (a weight byte meets at most the step's rows)."""

from benchmarks.harness import registry
from benchmarks.harness.peaks import peaks
from benchmarks.harness.spans import decode_iterations
from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    took_ms = ops_ms_per_decode_step(result, "short_conv")
    recs = decode_iterations(result)
    costs = registry.load_architecture(result["cell"], "costs")
    if not took_ms or not recs or not hasattr(costs, "short_conv_step_bytes"):
        return None
    rows = sum(r["rows"] for r in recs) / len(recs)
    least = costs.short_conv_step_bytes(result["cell"]["config"], rows)
    return 100.0 * least / peaks(result["device"]["kind"])["hbm_bytes_per_s"] / (took_ms / 1000.0)
