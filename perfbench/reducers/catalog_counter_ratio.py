"""One counter of the program's metrics catalog over another, as this
process holds them (all tag sets together), x ``scale``.  None where the
program has no such counter or the denominator never moved."""


def _total(name: str):
    from ray_tpu.util import metrics
    entry = metrics.registry_snapshot().get(name)
    if not entry:
        return None
    return sum(s["value"] for s in entry["series"])


def reduce(facts: dict, params: dict):
    over = _total(params["denominator"])
    value = _total(params["numerator"])
    if not over or value is None:
        return None
    return value / over * params.get("scale", 1.0)
