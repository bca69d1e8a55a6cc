"""Device time of EVERY operation of ``program`` over the traced window
(first device), whatever its scope, per span named ``span`` that ENDS
inside the window, in milliseconds: a step program's own device time a
step, for work the program enqueues in one span and the device runs later
(``scope_ms_per_span`` says why neither side is cut to the span's own
interval).  None where there is no capture, no op map, no such operation or
no such span."""

from perfbench import op_scopes, program_trace


def reduce(facts: dict, params: dict):
    joined = op_scopes.of_run(facts)
    ptrace = program_trace.of_run(facts)
    if joined is None or ptrace is None or not joined["events"]:
        return None
    start, end = joined["window"]
    spans = sum(1 for name, s, d in program_trace.loop_spans(ptrace)
                if name == params["span"] and start < s + d <= end)
    seconds = sum(e[3] for e in next(iter(joined["events"].values()))
                  if e[0] and op_scopes.is_program(e[0], params["program"]))
    if not spans or not seconds:
        return None
    return 1e3 * seconds / spans
