"""Train: seconds from the worker's first line to its mesh: the jax import and
the backend's start over the worker's chips (program_span: ``_trainer_start``)."""

from benchmarks.harness.setup_stages import trainer_stamp_span_s


def read(result):
    return trainer_stamp_span_s(result, "t_worker_ns", "t_mesh_ns")
