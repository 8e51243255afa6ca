"""runtime: seconds from the controller's request for the replica to the line
before the replica calls the deployment's class: the actor manager, the worker
process, the ``Replica`` actor (program_span: ``setup_stamps``)."""

from benchmarks.harness.setup_stages import replica_stamp_span_s


def read(result):
    return replica_stamp_span_s(result, "t_requested_ns", "t_callable_ns")
