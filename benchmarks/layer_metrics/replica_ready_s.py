"""runtime: seconds ``serve.run`` took until the replica was ready."""


def read(result):
    return result["clock"].get("replica_ready_s")
