"""What a prompt's arrival costs the device: idle milliseconds of the
traced window whose innermost loop span is one of ``spans`` (the admission
and the prefill's own) or a drain of cause ``drain`` with what lies inside
it (the step in flight read with nothing behind it, because a prompt
came), per ``per`` span that starts in the window
(``token_trace.idle_by_label``).  None on a capture with no device plane,
without the spans, or of a window that holds no ``per`` span."""

from perfbench import token_trace


def reduce(facts: dict, params: dict):
    ttrace = token_trace.of_run(facts)
    idle = token_trace.idle_by_label(ttrace) if ttrace else None
    if idle is None:
        return None
    arrivals = token_trace.count_starting_in_window(ttrace, params["per"])
    if not arrivals:
        return None
    drain = f"llm.decode.drain[{params['drain']}]"
    seconds = sum(s for label, s in idle.items() if label in params["spans"]
                  or label == drain or label.startswith(drain + "/"))
    return 1e3 * seconds / arrivals
