"""Train: seconds from the entry of ``fit()`` to the first line of the
worker's ``__init__``: the gang's resources, the worker process, the actor
(program_span: ``_trainer_start``)."""

from benchmarks.harness.setup_stages import trainer_stamp_span_s


def read(result):
    return trainer_stamp_span_s(result, "t_fit_ns", "t_worker_ns")
