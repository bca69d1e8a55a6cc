"""Milliseconds of the program's own spans (``llm.*``, on the loop's
thread) inside one ``parent`` span: what the spans named ``spans`` cover
there, or with ``rest`` what is left of the parent outside them; over
the parents the traced window holds whole (and, with ``having``, only
those that hold such a span), the ``stat`` (median or mean)."""

from perfbench import program_trace


def reduce(facts: dict, params: dict):
    ptrace = program_trace.of_run(facts)
    if ptrace is None:
        return None
    seconds = program_trace.per_parent(
        program_trace.loop_spans(ptrace), ptrace["window"], params["parent"],
        params["spans"], params.get("having", ""), params.get("rest", False))
    value = program_trace.stat(seconds, params["stat"])
    return None if value is None else 1e3 * value
