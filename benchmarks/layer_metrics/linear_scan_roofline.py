"""linear attention: the least time a prefill chunk's scan could take on this
chip over the time it took (``linear_scan_ms``). The least: the larger of the
recurrence's operations over a chunk's tokens at the bf16 matrix peak and the
bytes it must move (q, k, v, g, beta in, o out, the state once in and once
out) at the HBM peak, every linear layer (costs.py: what the mathematics needs,
whatever implements it). The chunked form does more operations than the
recurrence, in float32, as many small matmuls: the share is the room a kernel has."""

from benchmarks.harness import registry
from benchmarks.harness.peaks import peaks


def read(result):
    cell = result["cell"]
    took_ms = registry.load_metric("per_layer", "linear_scan_ms", cell["bench_dir"])(result)
    costs = registry.load_architecture(cell, "costs")
    if not took_ms or not hasattr(costs, "linear_scan_flops"):
        return None
    tokens = cell["config"]["deployment"]["engine"]["prefill_chunk"]
    peak = peaks(result["device"]["kind"])
    least = max(costs.linear_scan_flops(cell["config"], tokens) / peak["bf16_flops_per_s"],
                costs.linear_scan_bytes(cell["config"], tokens) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms / 1000.0)
