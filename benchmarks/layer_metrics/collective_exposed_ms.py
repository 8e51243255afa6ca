"""parallelism: per train step, collective time during which no other
operation ran on that device, mean over the devices."""


def read(result):
    devices = (result.get("trace") or {}).get("devices", [])
    per_step = []
    for d in devices:
        steps = len(d["programs"].get("train_step", ()))
        if steps:
            per_step.append(d["collective_exposed_s"] / steps)
    return 1000.0 * sum(per_step) / len(per_step) if per_step else None
