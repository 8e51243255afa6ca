"""latent attention: device time a prefill chunk spends gathering its row's
view of the latent pool and attending over it (scores, softmax, the weighted
sum over the latents), all layers together, not the projections around it: the
operations that the configuration's ``trace_ops.latent_prefill`` names, their
seconds over the traced slice (of the forty longest operations the reduction
keeps) over the prefill program's executions in it (device_trace; the program
scopes them ``cache_attention``). Nothing to read without the pattern, without
a prefill program in the slice or without such operations."""

import re

from benchmarks.harness.readers import device0


def read(result):
    pattern = (result["cell"]["config"].get("trace_ops") or {}).get("latent_prefill")
    dev = device0(result)
    chunks = len(dev["programs"].get("prefill") or ()) if dev else 0
    if not pattern or not chunks:
        return None
    seconds = sum(s for name, s in dev["ops"] if re.search(pattern, name))
    return 1000.0 * seconds / chunks if seconds else None
