"""model: median device time of the decode program in the traced slice."""

from benchmarks.harness.readers import program_median_ms


def read(result):
    return program_median_ms(result, "decode")
