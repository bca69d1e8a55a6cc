"""Device time, inside the program's own spans named ``span`` and per such
span, of the operations of ``program`` that its op map
(``ray_tpu.util.tracing.op_maps``) puts under one of ``scopes``, in
milliseconds (first device).

``span``: an ``llm.*`` span of the engine's loop thread (``llm.decode``
ends when the step's results are on the host, so a decode step's
operations lie inside it); only spans the window holds whole count.
``program``: the registered name without its bucket (``llm.decode``
counts ``llm.decode.8``): an operation of the prefill or of the scatter
program that runs inside a decode span, or shares an instruction name
with one of the decode program, is not counted.  ``scopes`` / ``pass`` as
in scope_ms_per_step.  None where there is no map or no such span."""

from perfbench import op_scopes, program_trace


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
    events = next(iter(joined["events"].values()))
    return 1e3 * seconds_in_spans(events, spans, params) / len(spans)


def seconds_in_spans(events, spans, params) -> float:
    """Seconds of the selected events that lie inside the spans."""
    spans = sorted(spans)
    inside, i = 0.0, 0
    for event in events:                        # sorted by start
        _, _, s, d, _ = event
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        if i == len(spans):
            break
        if op_scopes.selects(event, params):
            inside += max(0.0, min(s + d, spans[i][1]) - max(s, spans[i][0]))
    return inside
