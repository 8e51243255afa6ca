"""runtime: seconds of the replica's parameter draw, ``init_params`` to
``block_until_ready`` (program_span: a set-up stamp)."""

from benchmarks.harness.spans import setup_s


def read(result):
    return setup_s(result, "params_s")
