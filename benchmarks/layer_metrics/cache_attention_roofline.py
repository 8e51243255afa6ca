"""cache attention: the least time a decode step's attention could take on
this chip over the time it took (``cache_attention_ms``). The least: the cached
keys and values the running rows' queries may read, once a layer at the HBM
peak: in a full layer every token a row holds (the mean ``context_tokens`` of
the window's decode iterations), in a window layer no more than the window a
row (their mean ``window_tokens``; both program_counter). The views are
gathered at the rung's and the ring's width for every slot, so the share says
what a kernel that walks the tables would save."""

from benchmarks.harness import registry
from benchmarks.harness.peaks import peaks
from benchmarks.harness.spans import decode_iterations
from benchmarks.harness.step_ops import ops_ms_per_decode_step


def read(result):
    took_ms = ops_ms_per_decode_step(result, "cache_attention")
    recs = [r for r in decode_iterations(result) or () if r.get("context_tokens") and r.get("window_tokens")]
    if not took_ms or not recs:
        return None
    context = sum(r["context_tokens"] for r in recs) / len(recs)
    window = sum(r["window_tokens"] for r in recs) / len(recs)
    cell = result["cell"]
    least = registry.load_architecture(cell, "costs").cache_attention_bytes(cell["config"], context, window)
    return 100.0 * least / peaks(result["device"]["kind"])["hbm_bytes_per_s"] / (took_ms / 1000.0)
