"""A number the job measured or counted itself: facts[key] x scale."""


def reduce(facts: dict, params: dict):
    value = facts.get(params["key"])
    return None if value is None else value * params.get("scale", 1.0)
