"""model: programs the backend built inside the window (program_counter);
0 is right."""

from benchmarks.harness.spans import compiles_in_window


def read(result):
    return compiles_in_window(result)
