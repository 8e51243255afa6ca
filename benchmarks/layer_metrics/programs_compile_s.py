"""model: seconds of the backend's compilations that ended before the window,
each a read of the persistent cache or a compile, summed over the threads that
ran them (program_counter: ``compile_totals``)."""

from benchmarks.harness.setup_stages import compiled_before_window


def read(result):
    found = compiled_before_window(result, "backend_compile")
    return found[1] / 1e9 if found else None
