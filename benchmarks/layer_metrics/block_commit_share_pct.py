"""LLM engine: rows of the window's passes over blocks that COMMITTED their
block (fed its final ids once more so that its keys and values become the
cache's, and drew nothing), over the rows fed (program_counter:
``block_commits`` and ``rows`` of the iteration ring): what folding the commit
into the next block's first pass would take out. A third under 2 denoising
passes a block. A program whose ring lacks the field gives None."""

from benchmarks.harness.spans import decode_iterations


def read(result):
    recs = [r for r in decode_iterations(result) or () if "block_commits" in r]
    rows = sum(r["rows"] for r in recs)
    return 100.0 * sum(r["block_commits"] for r in recs) / rows if rows else None
