"""95th percentile of the gaps between successive token events of a stream,
pooled over every gap that ended inside the window."""

from benchmarks.harness.client import percentile


def read(result):
    return percentile(result["client"]["itl_ms"], 95.0)
