"""routed experts of which the program holds a share: assignments of decode
rows to the experts HELD over all the assignments the router made, per cent
(program_counter: ``get_stats()["moe"]["decode"]``, ``assignments`` and
``assignments_all``, kept on the device from the replica's start; the check's
single-row steps before the traffic are in both sums, a five-hundredth of
them). 50 where the share is the deployment's (half the experts) and the
routing even; what the grouped matmuls of this chip compute of the block's
routed work. Nothing to read from a program that holds all its experts or
counts no such column."""


def read(result):
    moe = ((result.get("counters") or {}).get("moe") or {}).get("decode")
    if not moe or "assignments_all" not in moe:
        return None
    routed = sum(moe["assignments_all"])
    return 100.0 * sum(map(sum, moe["assignments"])) / routed if routed else None
