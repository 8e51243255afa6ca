"""device: 1 - busy union / traced slice, mean over the cell's devices."""

from benchmarks.harness.readers import idle_pct as read  # noqa: F401
