"""latent attention: the least time a prefill chunk's attention could take on
this chip over the time it took (``latent_prefill_ms``). The least: the
operations of the cheaper of the absorbed and the expanded form, causal, over
the context the chunk's row really holds (the mean ``chunk_context_tokens`` of
the window's passes that ran a chunk, program_span: not the width of the view,
which is the table's) at the bf16 matrix peak, or the bytes it must move at the
HBM peak, whichever is more (costs.py: what the mathematics needs, whatever
implements it). The view path attends in absorbed form over ``max_model_len``
keys with float32 scores: the share is the room a kernel, or an expanded-form
prefill, has."""

from benchmarks.harness import registry, spans
from benchmarks.harness.peaks import peaks


def read(result):
    cell = result["cell"]
    took_ms = registry.load_metric("per_layer", "latent_prefill_ms", cell["bench_dir"])(result)
    costs = registry.load_architecture(cell, "costs")
    recs, win = spans._rows(result, "iterations"), spans.window_ns(result)
    if not took_ms or not recs or win is None or not hasattr(costs, "latent_prefill_flops"):
        return None
    held = [r["chunk_context_tokens"] for r in recs
            if r["prefill_tokens"] > 0 and win[0] <= r["t_start_ns"] <= win[1] and "chunk_context_tokens" in r]
    if not held:
        return None
    tokens, context = cell["config"]["deployment"]["engine"]["prefill_chunk"], sum(held) / len(held)
    peak = peaks(result["device"]["kind"])
    least = max(costs.latent_prefill_flops(cell["config"], tokens, context) / peak["bf16_flops_per_s"],
                costs.latent_prefill_bytes(cell["config"], tokens, context) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms / 1000.0)
