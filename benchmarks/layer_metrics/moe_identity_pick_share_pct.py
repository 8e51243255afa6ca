"""a router with identity experts: picks of decode rows that were IDENTITIES
over all the picks the router made, per cent (program_counter:
``get_stats()["moe"]["decode"]``, ``picks_identity`` and ``assignments_all``,
kept on the device from the replica's start; the check's single-row steps
before the traffic are in both sums). A third where the routing is even over
512 experts and 256 identities; such a pick reads no weight and costs a row's
width of multiplies. Nothing to read from a program that counts no such picks."""


def read(result):
    moe = ((result.get("counters") or {}).get("moe") or {}).get("decode")
    if not moe or "picks_identity" not in moe:
        return None
    picks = sum(moe["assignments_all"])
    return 100.0 * sum(moe["picks_identity"]) / picks if picks else None
