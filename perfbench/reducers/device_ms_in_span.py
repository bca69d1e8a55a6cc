"""Device busy time inside the host spans named ``span``, per span, in
milliseconds."""

from perfbench import trace


def reduce(facts: dict, params: dict):
    traced = facts.get("trace")
    if not traced or not traced["device"]:
        return None
    seconds, count = trace.device_seconds_in_spans(traced, params["span"])
    return 1e3 * seconds / count if count else None
