"""routed experts: the least time the grouped matmuls of a decode step could
take on this chip over the time they took (``moe_experts_ms``). The least: the
three matrices of every expert a layer touched, by the device's own counter
(``moe_experts_touched_mean``), read once at the HBM peak; memory bounds them
(a weight byte meets a handful of tokens)."""

from benchmarks.harness import registry
from benchmarks.harness.peaks import peaks
from benchmarks.harness.step_ops import moe_decode_mean, ops_ms_per_decode_step


def read(result):
    took_ms = ops_ms_per_decode_step(result, "moe_experts")
    touched = moe_decode_mean(result, "experts_touched")
    if not took_ms or not touched:
        return None
    cell = result["cell"]
    least = registry.load_architecture(cell, "costs").moe_experts_bytes(cell["config"], touched)
    return 100.0 * least / peaks(result["device"]["kind"])["hbm_bytes_per_s"] / (took_ms / 1000.0)
