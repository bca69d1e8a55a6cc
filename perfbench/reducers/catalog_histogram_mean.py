"""Mean of a histogram of the program's metrics catalog, as this process
holds it (all tag sets together): sum / count x ``scale``.  None where
the program has no such histogram or never observed into it."""


def reduce(facts: dict, params: dict):
    from ray_tpu.util import metrics
    entry = metrics.registry_snapshot().get(params["histogram"])
    if not entry:
        return None
    series = [s["value"] for s in entry["series"]]
    count = sum(v["count"] for v in series)
    if not count:
        return None
    return sum(v["sum"] for v in series) / count * params.get("scale", 1.0)
