"""How many spans named ``span`` start in the traced window, on any
thread (``llm.compile``: 0 in a sound run).  None where the program does
not cover its loop's thread (the parent of the PR that added
``llm.idle``): an absent span is then no finding."""

from perfbench import token_trace


def reduce(facts: dict, params: dict):
    ttrace = token_trace.of_run(facts)
    if ttrace is None or not token_trace.covered(ttrace):
        return None
    return token_trace.count_starting_in_window(ttrace, params["span"])
