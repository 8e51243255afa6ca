"""LLM engine: seconds the replica traced and lowered its programs, one after
another on the building thread (program_span: the ``trace`` and ``lower`` stage
records of ``_build_programs``)."""

from benchmarks.harness.setup_stages import stage_wall_s


def read(result):
    return stage_wall_s(result, "trace", "lower")
