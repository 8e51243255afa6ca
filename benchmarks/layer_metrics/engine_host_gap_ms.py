"""LLM engine: median idle gap on the device between successive programs of
the traced slice (the host's turn: sampling, admission, building inputs)."""

from benchmarks.harness.readers import gap_median_ms


def read(result):
    return gap_median_ms(result)
