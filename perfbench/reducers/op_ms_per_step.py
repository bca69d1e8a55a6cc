"""Device time of the operations whose name holds ``substring`` in one
step, in milliseconds: their share of the traced window x the step time
of the whole window.  The trace covers part of the window and cuts steps
at its edges, so steps are not counted in it."""

from perfbench import trace


def reduce(facts: dict, params: dict):
    traced = facts.get("trace")
    if not traced or not traced["device"] or not facts.get("steps"):
        return None
    start, end = traced["window"]
    share = trace.op_seconds(traced, params["substring"]) / (end - start)
    return share * 1e3 * facts["window_s"] / facts["steps"]
