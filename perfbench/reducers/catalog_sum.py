"""Sum over the series of one name in the program's metrics catalog, as
this process holds them, whose tags match ``where`` and do not match
``without`` (each a dict tag -> value; a series matches when every pair
is its own), x ``scale``: a histogram's ``sum``, a counter's value.  None
where the catalog holds no series of that name at all (a program that
never told it: the parent of the PR that added the series, a run that made
no such event); 0 where it holds some and none matches.

A per-layer reading, so it belongs to the traced run's line: from a job
that made no capture (``facts["trace"]`` is None or absent: ``--trace 0``,
a test that calls a job for its end-to-end numbers) it reads nothing,
whatever the process has compiled by then."""


def _matches(tags: dict, pairs: dict) -> bool:
    return all(tags.get(key) == value for key, value in pairs.items())


def reduce(facts: dict, params: dict):
    if facts.get("trace") is None:
        return None
    from ray_tpu.util import metrics
    entry = metrics.registry_snapshot().get(params["series"])
    if not entry or not entry["series"]:
        return None
    where, without = params.get("where", {}), params.get("without")
    total = 0.0
    for series in entry["series"]:
        tags, value = series["tags"], series["value"]
        if _matches(tags, where) and not (without and _matches(tags, without)):
            total += value["sum"] if isinstance(value, dict) else value
    return total * params.get("scale", 1.0)
