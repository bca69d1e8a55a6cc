"""The device's idle time split by why the engine's loop left it idle, as
a share of the traced window in percent: the idle seconds whose innermost
loop span is ``label`` (``llm.idle[empty]``: nothing was offered), or with
``rest`` every other idle second (under any other ``llm.*`` span, under a
wait with work pending, under no span of the loop's line).  The two sum to
the run's idle share by construction (``token_trace.idle_by_label``).  None
on a capture with no device plane or without the spans.  With ``--notes``
the run's notes keep the idle seconds under every label (``idle_by_label``)
and where the loop's line spent the window (``loop_line_s``)."""

from perfbench import token_trace


def reduce(facts: dict, params: dict):
    ttrace = token_trace.of_run(facts)
    idle = token_trace.idle_by_label(ttrace) if ttrace else None
    if idle is None:
        return None
    if isinstance(facts.get("notes"), dict):
        facts["notes"]["idle_by_label"] = dict(
            sorted(idle.items(), key=lambda kv: -kv[1]))
        facts["notes"]["loop_line_s"] = token_trace.loop_line_seconds(ttrace)
    start, end = ttrace["window"]
    mine = idle.get(params["label"], 0.0)
    if params.get("rest"):
        mine = sum(idle.values()) - mine
    return 100.0 * mine / (end - start)
