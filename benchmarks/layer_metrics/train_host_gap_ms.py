"""Train: median idle gap on the device between successive train steps."""

from benchmarks.harness.readers import gap_median_ms


def read(result):
    return gap_median_ms(result, "train_step->train_step")
