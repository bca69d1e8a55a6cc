"""The program's ``llm.*`` spans WITH their attributes: when a token was put
on its stream, and why the engine's loop waited.

``program_trace.py`` keeps a span's name and times; the spans read here
also say whom they served (``seq``, ``seqs``) and why (``cause``).  This
module reads the run's capture once more, while it is still on disk, as
``reducers/decode_expert_hbm_share.summed`` does for one attribute.  A
token trace is a program trace whose spans have a fourth element::

    {"window": [start_s, end_s],
     "spans": {"<thread line>": [[name, start_s, dur_s, {attribute: value}],
                                 ...]},
     "ops":   {"<device plane>": [[op name, start_s, dur_s], ...]}}

The contract it reads (PERF.md, section 3): **a token is on its stream at
the END of the ``llm.decode.commit`` or ``llm.prefill.commit`` that names its
sequence; a request arrived at the START of the ``llm.submit`` that names
it**; every wait of the loop's thread lies in an ``llm.idle`` that says its
``cause``.  A program without those spans (the parent of the PR that added
them) is not ``covered``, and every reducer built on this returns None.

A capture hands an attribute back as a number where its text parses as
one, and drops an empty one: ids are compared as ``str``, and a commit
that names nobody has no ``seqs``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from perfbench import program_trace

COMMITS = ("llm.prefill.commit", "llm.decode.commit")
# what only a program that covers its loop's thread records: a serving
# window holds an arrival, so one of the two is there
COVERED_BY = ("llm.idle", "llm.prefill.commit")

_LOADED: Dict[str, dict] = {}        # capture path -> token trace


# ------------------------------------------------------------------ loading
def of_run(facts: dict) -> Optional[dict]:
    """The token trace of this run, parsed once per process; None when the
    run was not traced or the capture is gone."""
    if not facts.get("trace"):
        return None
    path = program_trace.capture_of(facts)
    if path is None:
        return None
    if path not in _LOADED:
        from jax.profiler import ProfileData
        _LOADED[path] = from_planes(list(ProfileData.from_file(path).planes))
    return _LOADED[path]


def from_planes(planes: Sequence) -> dict:
    """Planes as a token trace: window and operations as
    ``program_trace.from_planes`` cuts them, the spans that reach into the
    window kept whole, each with its attributes."""
    plain = program_trace.from_planes(planes)
    start, end = plain["window"]
    spans: Dict[str, list] = {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            mine = [[ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9,
                     dict(ev.stats)] for ev in ln.events
                    if ev.name.startswith(program_trace.SPAN_PREFIX)
                    and ev.start_ns / 1e9 < end
                    and (ev.start_ns + ev.duration_ns) / 1e9 > start]
            if mine:
                spans[f"{ln.name}#{i}"] = sorted(mine, key=lambda e: e[1])
    return {**plain, "spans": spans}


def loop_spans(ttrace: dict, marker: str = "llm.step") -> List[list]:
    """The loop thread's spans, attributes and all: the line that holds
    the ``marker`` spans (as ``program_trace.loop_spans``)."""
    lines = [evs for evs in ttrace["spans"].values()
             if any(e[0] == marker for e in evs)]
    return max(lines, key=len) if lines else []


def covered(ttrace: dict) -> bool:
    """Whether the program names its loop's waits and its first tokens."""
    return any(e[0] in COVERED_BY for e in loop_spans(ttrace))


def in_window(t: float, window: Sequence[float]) -> bool:
    return window[0] < t <= window[1]


def end_of(span: Sequence) -> float:
    return span[1] + span[2]


# ------------------------------------------------------------------- tokens
def members(span: Sequence) -> List[str]:
    """The sequences a commit put a token on the stream of."""
    attrs = span[3]
    if span[0] == "llm.prefill.commit":
        return [str(attrs["seq"])] if "seq" in attrs else []
    return [m for m in str(attrs.get("seqs", "")).split("|") if m]


def token_gaps(ttrace: dict) -> List[float]:
    """Seconds between the ENDS of consecutive commits that name one
    sequence, over the gaps that end in the window.  A first token
    (``llm.prefill.commit``) ends no gap: a sequence preempted and
    prefilled again starts a new run of gaps."""
    last: Dict[str, float] = {}
    gaps = []
    commits = sorted((e for e in loop_spans(ttrace) if e[0] in COMMITS),
                     key=end_of)
    for span in commits:
        at = end_of(span)
        for seq in members(span):
            if span[0] == "llm.decode.commit" and seq in last \
                    and in_window(at, ttrace["window"]):
                gaps.append(at - last[seq])
            last[seq] = at
    return gaps


def first_token_seconds(ttrace: dict) -> List[float]:
    """``llm.submit`` START to ``llm.prefill.commit`` END of the same
    sequence, over the requests submitted in the window whose first token
    the capture holds.  A sequence prefilled again after a preemption is
    counted once, at its first commit."""
    first: Dict[str, float] = {}
    for span in loop_spans(ttrace):
        if span[0] == "llm.prefill.commit" and "seq" in span[3]:
            first.setdefault(str(span[3]["seq"]), end_of(span))
    out = []
    for evs in ttrace["spans"].values():
        for span in evs:
            seq = str(span[3].get("seq", ""))
            if span[0] == "llm.submit" and seq in first \
                    and in_window(span[1], ttrace["window"]):
                out.append(first[seq] - span[1])
    return out


def count_starting_in_window(ttrace: dict, name: str) -> int:
    """Spans of that name, on any thread, that start in the window."""
    return sum(1 for evs in ttrace["spans"].values() for e in evs
               if e[0] == name and in_window(e[1], ttrace["window"]))


# --------------------------------------------------------------------- idle
def labelled(spans: Sequence[Sequence]) -> List[list]:
    """The loop's spans under names that say their cause:
    ``llm.idle[empty]``, ``llm.decode.drain[admit]``; and a span inside a
    drain under the drain's: ``llm.decode.drain[admit]/llm.decode.pull``."""
    drains = [(e[1], end_of(e) + 1e-9, f"{e[0]}[{e[3].get('cause', '')}]")
              for e in spans if e[0] == "llm.decode.drain"]
    out = []
    for name, start, dur, attrs in spans:
        if "cause" in attrs:
            name = f"{name}[{attrs['cause']}]"
        else:
            name = next((f"{label}/{name}" for s, e, label in drains
                         if s <= start and start + dur <= e), name)
        out.append([name, start, dur])
    return out


def idle_by_label(ttrace: dict) -> Optional[Dict[str, float]]:
    """Device idle seconds of the window under each innermost loop span,
    by its ``labelled`` name (``_no_span_`` where the loop's line holds
    none), through ``program_trace.idle_seconds_by_span``: the values sum to
    the window's idle time.  None on a capture with no device plane (a CPU
    rehearsal) or of a program that does not cover its loop."""
    if not ttrace["ops"] or not covered(ttrace):
        return None
    if "idle_by_label" not in ttrace:       # once a trace: three metrics ask
        ttrace["idle_by_label"] = program_trace.idle_seconds_by_span(
            {**ttrace, "spans": {"loop": labelled(loop_spans(ttrace))}})
    return ttrace["idle_by_label"]


def loop_line_seconds(ttrace: dict) -> Dict[str, float]:
    """Seconds of the window that the loop's line spends inside
    ``llm.step``, inside ``llm.idle`` by cause, and in ``neither``: the
    caller's wrapper round a step and the loop's own few lines."""
    start, end = ttrace["window"]
    out: Dict[str, float] = {}
    for name, s, d in labelled([e for e in loop_spans(ttrace)
                                if e[0] in ("llm.step", "llm.idle")]):
        lap = min(s + d, end) - max(s, start)
        if lap > 0:
            out[name] = out.get(name, 0.0) + lap
    out["neither"] = (end - start) - sum(out.values())
    return out
