"""Device time of the operations of ``program`` that its op map puts under
one of ``scopes`` (and, where given, whose instruction name holds one of
``names`` and none of ``without``), over the traced window, per span named
``span`` that ENDS inside the window, in milliseconds (first device).

For work that the program enqueues in one span and the device runs later:
a decode step is read where ``llm.decode.pull`` ends, a chunk of a prompt
is enqueued by one ``llm.prefill.chunk`` span and runs while the host is in
the next iteration, so neither side is cut to the span's own interval: the
window's operations over the window's count of spans.  What runs at the
window's edges belongs to a span outside it, and the reverse: one part in
the number of spans.  None where there is no capture, no op map, no such
operation or no such span (a program without them: the parent of the PR
that added them)."""

from perfbench import op_scopes, program_trace
from perfbench.reducers import scope_ms_by_name


def selected_seconds(joined: dict, params: dict) -> float:
    """Device seconds, over the whole window, of the first device's
    operations that ``program``, ``scopes``, ``names`` and ``without``
    keep."""
    return sum(e[3] for e in scope_ms_by_name.named(
        next(iter(joined["events"].values())), params)
        if op_scopes.selects(e, params))


def seconds_and_spans(facts: dict, params: dict):
    """(device seconds of the selected operations, spans ended in the
    window), or None where either cannot be read."""
    joined = op_scopes.of_run(facts)
    ptrace = program_trace.of_run(facts)
    if joined is None or ptrace is None or not joined["events"]:
        return None
    start, end = joined["window"]
    spans = sum(1 for name, s, d in program_trace.loop_spans(ptrace)
                if name == params["span"] and start < s + d <= end)
    seconds = selected_seconds(joined, params)
    if not spans or not seconds:
        return None
    return seconds, spans


def reduce(facts: dict, params: dict):
    read = seconds_and_spans(facts, params)
    return None if read is None else 1e3 * read[0] / read[1]
