"""LLM engine: positions a pass over blocks transferred, over the rows it fed,
summed over the window's passes (program_counter: ``tokens_unmasked`` and ``rows``
of the iteration ring). Every transferred position is a token of its stream,
emitted when its block commits (but for the tail of a request's last block,
~3 in a thousand): what a row's place in a pass yields. 1 for an
autoregressive step; 4/3 under 2 denoising passes of 2 positions each and a
commit pass a block of 4. A program whose ring lacks the field gives None."""

from benchmarks.harness.spans import decode_iterations


def read(result):
    recs = [r for r in decode_iterations(result) or () if "tokens_unmasked" in r]
    rows = sum(r["rows"] for r in recs)
    return sum(r["tokens_unmasked"] for r in recs) / rows if rows else None
