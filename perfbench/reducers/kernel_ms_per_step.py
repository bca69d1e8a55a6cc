"""Device time in one step of the kernels whose trace name holds one of
``names`` and one of ``shapes``, in milliseconds: their share of the traced
window x the step time of the whole window (as op_ms_per_step, which takes
one substring).  The v5e's trace calls a Pallas kernel by its own name or
``tpu_custom_call.<n>`` (both occur in one program), followed by its
result shape, so a kind of kernel is known by its names and result shapes.
None where no such operation ran: a program without the kernel has nothing
to report."""

from perfbench import trace


def seconds(traced: dict, params: dict) -> float:
    """Device seconds of the matching operations, averaged over devices."""
    planes = traced["device"]
    hit = sum(d for events in planes.values() for name, _, d in events
              if any(n in name for n in params["names"])
              and any(shape in name for shape in params["shapes"])
              and not trace.is_wrapper(name))
    return hit / max(1, len(planes))


def reduce(facts: dict, params: dict):
    traced = facts.get("trace")
    if not traced or not traced["device"] or not facts.get("steps"):
        return None
    start, end = traced["window"]
    share = seconds(traced, params) / (end - start)
    return share * 1e3 * facts["window_s"] / facts["steps"] or None
