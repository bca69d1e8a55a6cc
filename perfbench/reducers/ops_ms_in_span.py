"""Device time, inside the host spans named ``span`` and per such span, of
the operations whose trace name holds one of ``names`` and one of
``shapes``, in milliseconds (first device).  The v5e's trace names an
operation by its HLO instruction and result shape (``trace.short_name``),
so a kind of operation is known by both, as ``kernel_ms_per_step`` knows
its kernels; this one counts by span and not by step of a train loop.
None where no such operation ran: a program without them (the parent of
the PR that added them, the CPU's rehearsal) has nothing to report."""

from perfbench import trace


def matching(traced: dict, params: dict) -> dict:
    """The trace with its device events cut to the matching operations."""
    planes = {
        plane: [e for e in events
                if any(n in e[0] for n in params["names"])
                and any(shape in e[0] for shape in params["shapes"])
                and not trace.is_wrapper(e[0])]
        for plane, events in traced["device"].items()}
    return {**traced, "device": planes}


def reduce(facts: dict, params: dict):
    traced = facts.get("trace")
    if not traced or not traced["device"]:
        return None
    seconds, count = trace.device_seconds_in_spans(
        matching(traced, params), params["span"])
    return 1e3 * seconds / count if count and seconds else None
