"""runtime: seconds of the replica's ``backend`` stage, the TPU backend's start
under ``jax.devices()`` (program_span: a stage record)."""

from benchmarks.harness.setup_stages import stage_wall_s


def read(result):
    return stage_wall_s(result, "backend")
