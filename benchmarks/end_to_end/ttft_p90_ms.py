"""90th percentile, over the requests due in the window, of the first token
event minus the time the request was due; a failed request counts as never."""

from benchmarks.harness.client import percentile


def read(result):
    return percentile(result["client"]["ttft_ms"], 90.0)
