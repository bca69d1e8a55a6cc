"""1 - union of the device's operations / traced window, in percent."""

from perfbench import trace


def reduce(facts: dict, params: dict):
    traced = facts.get("trace")
    if not traced or not traced["device"]:
        return None
    return 100.0 * trace.idle_share(traced)
