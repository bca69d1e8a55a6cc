"""A statistic of a series the job recorded: a percentile (``q``) or the
mean (``stat: mean``) of all its values in the window."""

from perfbench import stats


def reduce(facts: dict, params: dict):
    values = facts.get(params["series"])
    if not values:
        return None
    if params.get("stat") == "mean":
        return stats.mean(values)
    return stats.percentile(values, params["q"])
