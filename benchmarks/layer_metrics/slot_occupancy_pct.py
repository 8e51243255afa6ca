"""LLM engine: ``get_stats()["running"]`` polled once a second through the
window, mean over ``num_slots``."""


def read(result):
    polls = result["counters"].get("running_polls")
    if not polls:
        return None
    slots = result["cell"]["config"]["deployment"]["engine"]["num_slots"]
    return 100.0 * sum(polls) / len(polls) / slots
