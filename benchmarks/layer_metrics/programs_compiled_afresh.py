"""model: programs before the window that the backend compiled and wrote to the
persistent cache, entries a warm start would have read (program_counter); 0 is
right on a warm run."""

from benchmarks.harness.setup_stages import compiled_before_window


def read(result):
    found = compiled_before_window(result, "compiled_afresh")
    return found[0] if found else None
