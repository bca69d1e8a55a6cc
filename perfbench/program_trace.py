"""What the program says about itself in a capture: its own ``llm.*`` host
spans (``ray_tpu.util.tracing.hot_span``), on the clock of the device's
operations.

``trace.py`` keeps the harness's ``pb.*`` spans; this module reads the same
``.xplane.pb`` a second time, while it is still on disk (``run.py`` removes
the capture after the metrics loop), and keeps the program's spans by the
thread that recorded them.  A program trace is a dict::

    {"window": [start_s, end_s],            # the pb.window span
     "spans": {"<thread line>": [[span name, start_s, dur_s], ...]},
     "ops":   {"<device plane>": [[op name, start_s, dur_s], ...]}}

Operations are cut to the window with ``trace.clip_to_window``; spans that
reach into it are kept whole.  The reductions work on these lists, so tests
feed them small recorded or made-up traces.
A program without the spans (the parent of the PR that added them) gives
empty lists, and every reducer built on this returns None.

The ``jax.named_scope`` path of an operation is not read here: as this
repository configures JAX (``jax_include_full_tracebacks_in_locations``
off, for a compile-cache key that does not depend on the call path), it
does not reach the v5e trace (PERF.md, section 7).
"""

from __future__ import annotations

import glob
import os
from statistics import median
from typing import Dict, List, Optional, Sequence

from perfbench import manifest, trace

SPAN_PREFIX = "llm."
SCRATCH = manifest.ROOT / ".perfbench_scratch"

_LOADED: Dict[str, dict] = {}        # capture path -> program trace


# ------------------------------------------------------------------ loading
def newest_capture(scratch=SCRATCH) -> Optional[str]:
    """The newest capture file under one run's trace directory, or under
    the scratch directory that holds every run's."""
    tail = ("plugins", "profile", "*", "*.xplane.pb")
    files = glob.glob(os.path.join(str(scratch), *tail)) \
        or glob.glob(os.path.join(str(scratch), "trace-*", *tail))
    return max(files, key=os.path.getmtime) if files else None


def capture_of(facts: dict) -> Optional[str]:
    """This run's capture file: under the directory its trace names."""
    traced = facts["trace"]
    where = traced.get("capture_dir") if isinstance(traced, dict) else None
    return newest_capture(where or SCRATCH)


def of_run(facts: dict) -> Optional[dict]:
    """The program trace of this run: the newest capture under the
    scratch directory, parsed once per process.  None when the run was
    not traced or the capture is gone."""
    if not facts.get("trace"):
        return None
    path = capture_of(facts)
    if path is None:
        return None
    if path not in _LOADED:
        from jax.profiler import ProfileData
        _LOADED[path] = from_planes(ProfileData.from_file(path).planes)
    return _LOADED[path]


def from_planes(planes) -> dict:
    """Planes (name, lines of named events with start_ns, duration_ns) as
    a program trace, cut to the last ``pb.window`` span."""
    spans: Dict[str, list] = {}
    ops: Dict[str, list] = {}
    windows = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            events = [[trace.short_name(ev.name), ev.start_ns / 1e9,
                       ev.duration_ns / 1e9]
                      for ln in plane.lines
                      if ln.name.strip().lower() == "xla ops"
                      for ev in ln.events if ev.duration_ns > 0]
            if events:
                ops[plane.name] = sorted(events, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                mine = []
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        mine.append([ev.name, ev.start_ns / 1e9,
                                     ev.duration_ns / 1e9])
                    elif ev.name == trace.WINDOW_SPAN:
                        windows.append((ev.start_ns / 1e9,
                                        (ev.start_ns + ev.duration_ns) / 1e9))
                if mine:
                    spans[f"{ln.name}#{i}"] = sorted(mine,
                                                     key=lambda e: e[1])
    if not windows:
        raise RuntimeError(f"the trace holds no {trace.WINDOW_SPAN} span")
    return clip(spans, ops, *windows[-1])


def clip(spans: Dict[str, list], ops: Dict[str, list], start_s: float,
         end_s: float) -> dict:
    """Operations are cut at the window's edges.  Spans that reach into
    the window are kept whole: cut, a step and the spans inside it would
    all start at the edge, and which of them is the innermost would be
    lost."""
    cut = trace.clip_to_window({"device": ops, "host": []}, start_s, end_s)
    kept = {line: [e for e in evs if e[1] < end_s and e[1] + e[2] > start_s]
            for line, evs in spans.items()}
    return {"window": [start_s, end_s], "ops": cut["device"],
            "spans": {line: evs for line, evs in kept.items() if evs}}


# --------------------------------------------------------------- host spans
def loop_spans(ptrace: dict, marker: str = "llm.step") -> List[list]:
    """The spans of the thread that runs the engine's loop: the line that
    holds the ``marker`` spans (``llm.submit`` runs on callers' threads and
    must not be nested into the loop's spans by time)."""
    lines = [evs for evs in ptrace["spans"].values()
             if any(name == marker for name, _, _ in evs)]
    return max(lines, key=len) if lines else []


def whole(spans: Sequence[Sequence], window: Sequence[float]) -> List[list]:
    """The spans that lie inside the window."""
    start, end = window
    return [e for e in spans if e[1] > start and e[1] + e[2] < end]


def inside(spans: Sequence[Sequence], outer: Sequence) -> List[list]:
    """The spans that lie within ``outer`` in time (``outer`` itself not)."""
    s, e = outer[1], outer[1] + outer[2] + 1e-9     # a nanosecond of slack
    return [k for k in spans
            if s <= k[1] and k[1] + k[2] <= e and list(k) != list(outer)]


def covered_seconds(spans: Sequence[Sequence]) -> float:
    """Seconds covered by at least one of the spans."""
    return sum(e - s for s, e in
               trace.merged((s, s + d) for _, s, d in spans))


def self_seconds(spans: Sequence[Sequence], outer: Sequence) -> float:
    """A span's self time: its duration minus the part of it that the
    spans inside it cover (choosing-metrics, section 4)."""
    return outer[2] - covered_seconds(inside(spans, outer))


def per_parent(spans: Sequence[Sequence], window: Sequence[float],
               parent: str, names: Sequence[str], having: str = "",
               rest: bool = False) -> List[float]:
    """For every whole ``parent`` span (that holds a ``having`` span, if
    one is named): the seconds the spans called ``names`` cover inside it,
    or with ``rest`` the parent's other seconds."""
    out = []
    for p in whole(spans, window):
        if p[0] != parent:
            continue
        kids = inside(spans, p)
        if having and not any(k[0] == having for k in kids):
            continue
        hit = covered_seconds([k for k in kids if k[0] in names])
        out.append(p[2] - hit if rest else hit)
    return out


def idle_seconds_by_span(ptrace: dict) -> Dict[str, float]:
    """Device idle seconds of the window under each innermost loop span
    (first device), by ``trace.idle_gaps``."""
    if not ptrace["ops"]:
        return {}
    as_trace = {"window": ptrace["window"], "device": ptrace["ops"],
                "host": loop_spans(ptrace)}
    return dict(trace.idle_gaps(as_trace, n=1 << 30))


def stat(values: Sequence[float], which: str) -> Optional[float]:
    if not values:
        return None
    return median(values) if which == "median" else sum(values) / len(values)
