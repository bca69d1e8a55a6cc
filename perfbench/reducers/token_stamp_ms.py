"""A percentile (``q``) of what the program's own token stamps say, in
milliseconds (``perfbench/token_trace.py``; None without the spans).

``of: token_gap``: the gap between two tokens of one sequence: per
sequence, between the ENDS of consecutive commits that name it
(``llm.prefill.commit``, then each ``llm.decode.commit`` whose ``seqs`` holds
it), over the gaps that end in the traced window.
``of: first_token``: ``llm.submit`` START to ``llm.prefill.commit`` END of the
same sequence, over the requests submitted in the traced window."""

from perfbench import stats, token_trace

SERIES = {"token_gap": token_trace.token_gaps,
          "first_token": token_trace.first_token_seconds}


def reduce(facts: dict, params: dict):
    ttrace = token_trace.of_run(facts)
    seconds = SERIES[params["of"]](ttrace) if ttrace else []
    return 1e3 * stats.percentile(seconds, params["q"]) if seconds else None
