"""Device idle time of the traced window while the program's innermost
span on the loop's thread was ``span``, per ``per`` span the window holds
(a decode step), in milliseconds."""

from perfbench import program_trace


def reduce(facts: dict, params: dict):
    ptrace = program_trace.of_run(facts)
    if ptrace is None or not ptrace["ops"]:
        return None
    spans = program_trace.loop_spans(ptrace)
    steps = sum(1 for name, _, _ in spans if name == params["per"])
    if not steps or not any(name == params["span"] for name, _, _ in spans):
        return None
    idle = program_trace.idle_seconds_by_span(ptrace)
    return 1e3 * idle.get(params["span"], 0.0) / steps
