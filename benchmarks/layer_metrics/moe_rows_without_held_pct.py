"""a router with identity experts, of whose experts the program holds a share:
decode rows of a layer NONE of whose picks was an expert held here, over the
rows routed, per cent (program_counter: ``get_stats()["moe"]["decode"]``,
``rows_without_held`` over ``assignments_all`` divided by the configuration's
``moe_topk`` picks a row, summed over the layers and the steps from the
replica's start). Such a row's branch costs this program its identity term
at most; the rest of the rows is what the grouped matmuls compute for. Nothing
to read from a program that counts no such rows."""


def read(result):
    moe = ((result.get("counters") or {}).get("moe") or {}).get("decode")
    if not moe or "rows_without_held" not in moe:
        return None
    picks = sum(moe["assignments_all"])
    return 100.0 * sum(moe["rows_without_held"]) * result["cell"]["config"]["moe_topk"] / picks if picks else None
