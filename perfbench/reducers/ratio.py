"""facts[numerator] / facts[denominator] (/ facts[per]) x scale: a rate,
or a time per step, over all the work and all the time of the window."""


def reduce(facts: dict, params: dict):
    over = [params["denominator"]] + ([params["per"]] if "per" in params else [])
    value = facts.get(params["numerator"])
    if value is None or not all(facts.get(k) for k in over):
        return None
    for key in over:
        value /= facts[key]
    return value * params.get("scale", 1.0)
