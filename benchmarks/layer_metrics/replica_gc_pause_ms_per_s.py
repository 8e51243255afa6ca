"""LLM engine: milliseconds a second of the window that the replica's process
spent in generation-2 collections (program_counter): each holds the GIL and
stalls the scheduler and every stream's delivery at once."""

from benchmarks.harness.deliveries import gc_pause_ms_per_s


def read(result):
    return gc_pause_ms_per_s(result)
