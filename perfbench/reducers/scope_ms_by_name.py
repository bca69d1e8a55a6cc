"""``scope_ms_in_program_span`` over a part of the scopes' operations, told
by instruction name: device time, inside the program's own spans named
``span`` and per such span, of the operations of ``program`` that its op
map puts under one of ``scopes`` and whose instruction name holds one of
``names`` (where given) and none of ``without`` (where given), in
milliseconds (first device).

For a kernel the op map cannot place: a compiler expansion (``ragged_dot``
-> ``ragged-dot-none.<n>``) carries no scope path of its own and takes its
users', and the user of an expert layer's last matmul is the combine, so
the scope ``moe_experts`` holds two of a layer's three kernels and
``moe_combine`` the third (PERF.md section 7: a ``tracing`` PR's to mend,
and this reducer goes with it).  Keyed on the program and the name, not on
a shape: a prefill's kernels of the same name are another program's, and
another decode bucket's are still counted.  None as its parent returns."""

from perfbench import op_scopes, program_trace
from perfbench.reducers import scope_ms_in_program_span


def named(events, params: dict) -> list:
    """The events whose instruction name ``names`` / ``without`` keep."""
    names, without = params.get("names"), params.get("without", ())
    return [e for e in events
            if (names is None or any(n in e[1] for n in names))
            and not any(n in e[1] for n in without)]


def reduce(facts: dict, params: dict):
    joined = op_scopes.of_run(facts)
    ptrace = program_trace.of_run(facts)
    if joined is None or ptrace is None or not joined["events"]:
        return None
    spans = [(s, s + d) for name, s, d in program_trace.whole(
        program_trace.loop_spans(ptrace), joined["window"])
        if name == params["span"]]
    if not spans:
        return None
    events = named(next(iter(joined["events"].values())), params)
    return 1e3 * scope_ms_in_program_span.seconds_in_spans(
        events, spans, params) / len(spans)
