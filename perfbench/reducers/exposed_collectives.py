"""Collective time that no compute hides, per step: the traced window's
exposed seconds over the steps it held (window / seconds per step)."""

from perfbench import trace


def reduce(facts: dict, params: dict):
    traced = facts.get("trace")
    if not traced or not traced["device"] or not facts.get("steps"):
        return None
    start, end = traced["window"]
    steps = (end - start) / (facts["window_s"] / facts["steps"])
    return 1e3 * trace.exposed_collectives(traced)["exposed_s"] / steps
