"""Profiler capture and the reduction from a trace to numbers.

The capture writes JAX's ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` into plain lists, and every reduction below
works on those lists, so that tests can feed them a small recorded trace.

A trace here is a dict::

    {"device": {"<plane name>": [[op name, start_s, dur_s], ...]},
     "device_async": {...},    # the same planes' line of asynchronous operations
     "host":   [[span name, start_s, dur_s], ...],     # the harness's pb.* spans
     "window": [start_s, end_s]}

All times are seconds on the profiler's one clock.  ``_merged_busy`` and
``exposed_collectives`` are copies of ``bench._merged_busy_us`` and
``bench._overlap_breakdown`` (PERF.md, Open questions, lists the originals
for deletion).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "pb."
WINDOW_SPAN = "pb.window"       # marks the traced window on the trace's clock
# bench.py's table: the first substring that hits names the kind.
_COLLECTIVE_KINDS = (
    ("reduce-scatter", "reduce_scatter"),
    ("all-reduce", "psum"),
    ("psum", "psum"),
    ("all-gather", "all_gather"),
    ("collective-permute", "ppermute"),
    ("ppermute", "ppermute"),
    ("all-to-all", "all_to_all"),
)

Interval = Tuple[float, float]          # (start_s, end_s)
# Operations that only wrap others (a scan's loop, a branch): the device is
# busy while they run, but their time is their children's, listed besides.
_WRAPPERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\(?([a-z]+[0-9]*\[[0-9,]*\])")


def short_name(raw: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` (the profiler
    names a TPU operation by its whole HLO line) -> ``fusion.3 bf16[8,128]``."""
    head, sep, rest = raw.partition(" = ")
    name = head.strip().lstrip("%")
    shape = _SHAPE.match(rest) if sep else None
    return f"{name} {shape.group(1)}" if shape else name


def is_wrapper(name: str) -> bool:
    return name.split(".")[0].split(" ")[0] in _WRAPPERS


def span(name: str):
    """A host span on the profiler's clock (a no-op when none is running)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class Capture:
    """A profiler capture started and stopped by hand (the window decides),
    without the Python tracer: it would record every call of the engine's
    host loop and slow the very step measured.  A ``pb.window`` span marks
    the traced window on the trace's own clock."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.started = False
        self._window = None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self._window = span(WINDOW_SPAN)
        self._window.__enter__()
        self.started = True

    def stop(self) -> None:
        """From the thread that started it."""
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()


def load(out_dir: str) -> dict:
    """The newest capture under ``out_dir`` as a trace dict."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return from_planes(ProfileData.from_file(files[-1]).planes)


def from_planes(planes) -> dict:
    """Planes (name, lines of named events with start_ns, duration_ns) as
    a trace dict: the operations of each device, the harness's spans."""
    device: Dict[str, list] = {}
    device_async: Dict[str, list] = {}
    host: List[list] = []
    layout: Dict[str, list] = {}
    for plane in planes:
        lines = list(plane.lines)
        layout[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            events = _events(_op_lines(lines))
            if events:
                device[plane.name] = events
                device_async[plane.name] = _events(
                    [ln for ln in lines if _line(ln) == "async xla ops"])
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([ev.name, ev.start_ns / 1e9,
                                     ev.duration_ns / 1e9])
    host.sort(key=lambda e: e[1])
    return {"device": device, "device_async": device_async, "host": host,
            "layout": layout}


def load_window(cap: "Capture") -> dict:
    """The capture cut to its window span, which is then dropped."""
    full = load(cap.out_dir)
    marks = [(s, s + d) for name, s, d in full["host"] if name == WINDOW_SPAN]
    if not marks:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
    full["host"] = [e for e in full["host"] if e[0] != WINDOW_SPAN]
    # where the capture lies: the readers of the program's spans and of the
    # op map open THIS run's file, not the newest under the scratch
    # directory, which another run in the same checkout may be writing
    return {**clip_to_window(full, *marks[-1]), "capture_dir": cap.out_dir}


def _line(ln) -> str:
    return ln.name.strip().lower()


def _events(lines: list) -> list:
    events = [[short_name(ev.name), ev.start_ns / 1e9, ev.duration_ns / 1e9]
              for ln in lines for ev in ln.events if ev.duration_ns > 0]
    return sorted(events, key=lambda e: e[1])


def _op_lines(lines: list) -> list:
    """The line of single operations.  A device plane's other lines (Steps,
    XLA Modules) repeat it at a coarser grain: counting them would make the
    device look busy for a whole step."""
    return [ln for ln in lines if _line(ln) == "xla ops"]


def clip_to_window(trace: dict, start_s: float, end_s: float) -> dict:
    """Keep what lies inside [start_s, end_s], cutting events at the edges."""
    def cut(events):
        out = []
        for name, s, d in events:
            a, b = max(s, start_s), min(s + d, end_s)
            if b > a:
                out.append([name, a, b - a])
        return out
    return {"device": {k: cut(v) for k, v in trace["device"].items()},
            "device_async": {k: cut(v) for k, v in
                             trace.get("device_async", {}).items()},
            "host": cut(trace["host"]), "window": [start_s, end_s],
            "layout": trace.get("layout", {})}


# ------------------------------------------------------------- reductions
def merged(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of the intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _merged_busy(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merged(intervals))


def _ivs(events: Sequence[Sequence]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran on the device: the union of the
    device events, averaged over the devices in the trace."""
    planes = trace["device"]
    if not planes:
        return 0.0
    return sum(_merged_busy(_ivs(ev)) for ev in planes.values()) / len(planes)


def idle_share(trace: dict) -> float:
    start, end = trace["window"]
    return 1.0 - busy_seconds(trace) / (end - start)


def top_ops(trace: dict, n: int = 10) -> List[list]:
    """The device operations that took most time, summed by name and
    averaged over the devices."""
    total: Dict[str, float] = {}
    for events in trace["device"].values():
        for name, _, d in events:
            if not is_wrapper(name):
                total[name] = total.get(name, 0.0) + d
    k = max(1, len(trace["device"]))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def op_seconds(trace: dict, substring: str) -> float:
    """Device seconds of the operations whose name holds ``substring``,
    averaged over the devices."""
    planes = trace["device"]
    if not planes:
        return 0.0
    hit = sum(d for ev in planes.values() for name, _, d in ev
              if substring in name.lower() and not is_wrapper(name))
    return hit / len(planes)


def collective_kind(name: str):
    for pat, kind in _COLLECTIVE_KINDS:
        if pat in name:
            return kind
    return None


def exposed_collectives(trace: dict) -> dict:
    """Collective busy time that no compute hides, in seconds over the
    window, per device (averaged) and per kind of collective."""
    exposed, coll_busy, comp_busy = [], [], []
    by_kind: Dict[str, List[float]] = {}
    for plane, events in trace["device"].items():
        coll, comp, kinds = [], [], {}
        later = trace.get("device_async", {}).get(plane, [])
        for name, s, d in list(events) + list(later):
            if is_wrapper(name):
                continue
            kind = collective_kind(name.lower())
            if kind is None:
                comp.append((s, s + d))
            else:
                coll.append((s, s + d))
                kinds.setdefault(kind, []).append((s, s + d))
        c, m = _merged_busy(coll), _merged_busy(comp)
        hidden = max(0.0, c + m - _merged_busy(coll + comp))
        exposed.append(c - hidden)
        coll_busy.append(c)
        comp_busy.append(m)
        for kind, ivs in kinds.items():
            k = _merged_busy(ivs)
            k_hidden = max(0.0, k + m - _merged_busy(ivs + comp))
            by_kind.setdefault(kind, []).append(k - k_hidden)
    n = max(1, len(trace["device"]))
    return {"exposed_s": sum(exposed) / n,
            "collective_s": sum(coll_busy) / n,
            "compute_s": sum(comp_busy) / n,
            "exposed_s_by_kind": {k: sum(v) / n
                                  for k, v in sorted(by_kind.items())}}


def host_timeline(spans: Sequence[Sequence]) -> List[list]:
    """Flatten nested or overlapping spans into disjoint segments
    ``[start, end, name]``; where several cover a moment, the one that
    started last (the innermost) names it."""
    cuts = sorted({t for _, s, d in spans for t in (s, s + d)})
    out: List[list] = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        covering = [(s, name) for name, s, d in spans if s <= mid < s + d]
        if not covering:
            continue
        name = max(covering)[1]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """The device's idle time inside the window by what the host was
    doing: seconds of idleness under each pb.* span, ``_no_span_`` for the
    rest, on the first device of the trace."""
    start, end = trace["window"]
    planes = trace["device"]
    busy = merged(_ivs(next(iter(planes.values())))) if planes else []
    gaps, at = [], start
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, end)))
        at = max(at, e)
    if at < end:
        gaps.append((at, end))
    timeline = host_timeline(trace["host"])
    total: Dict[str, float] = {}
    i = 0
    for gs, ge in gaps:
        while i > 0 and timeline[i - 1][1] > gs:
            i -= 1
        covered, j = 0.0, i
        while j < len(timeline) and timeline[j][0] < ge:
            a, b, name = timeline[j]
            lap = min(b, ge) - max(a, gs)
            if lap > 0:
                total[name] = total.get(name, 0.0) + lap
                covered += lap
            j += 1
        i = max(0, j - 1)
        rest = (ge - gs) - covered
        if rest > 0:
            total["_no_span_"] = total.get("_no_span_", 0.0) + rest
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def longest_gaps(trace: dict, n: int = 5) -> List[list]:
    """(builder) The first device's n longest idle stretches as
    [seconds into the window, seconds long]."""
    start, end = trace["window"]
    planes = trace["device"]
    busy = merged(_ivs(next(iter(planes.values())))) if planes else []
    edges = [start] + [t for iv in busy for t in iv] + [end]
    gaps = [[a - start, b - a] for a, b in zip(edges[::2], edges[1::2])
            if b > a]
    return sorted(gaps, key=lambda g: -g[1])[:n]


def device_seconds_in_spans(trace: dict, span_name: str) -> Tuple[float, int]:
    """Device busy seconds inside the host spans of that name (first
    device), and how many such spans the window holds."""
    planes = trace["device"]
    spans = [(s, s + d) for name, s, d in trace["host"] if name == span_name]
    if not planes or not spans:
        return 0.0, len(spans)
    busy = merged(_ivs(next(iter(planes.values()))))
    inside, i = 0.0, 0
    for ss, se in sorted(spans):
        while i > 0 and busy[i - 1][1] > ss:
            i -= 1
        j = i
        while j < len(busy) and busy[j][0] < se:
            inside += max(0.0, min(busy[j][1], se) - max(busy[j][0], ss))
            j += 1
        i = max(0, j - 1)
    return inside, len(spans)


def rename_by_child(trace: dict, outer: str, children: Dict[str, str],
                    otherwise: str) -> dict:
    """Name each ``outer`` span after the child span it holds: a step of
    the engine's loop is known as a prefill or a decode only once it has
    called the model."""
    kids = [(s, name) for name, s, _ in trace["host"] if name in children]
    kids.sort()
    host = []
    for name, s, d in trace["host"]:
        if name == outer:
            name = next((children[k] for ks, k in kids if s <= ks < s + d),
                        otherwise)
        host.append([name, s, d])
    return {**trace, "host": host}
