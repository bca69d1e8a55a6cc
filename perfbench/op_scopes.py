"""Device time by what the program says each operation is.

The trace names a device operation by its HLO instruction (``fusion.414``);
the program that compiled the step can say what that instruction is
(``ray_tpu.util.tracing.op_maps``: instruction -> named-scope path, pass,
primitive, source line, read from the compiled module's own text).  This
module joins the two: it reads the run's capture once more, as
``program_trace.py`` does and while it is still on disk, keeps every
operation with the module that was running when it ran, matches each
module to the program whose map knows its operations, and hands the
reducers ``scope_ms_per_step`` and ``scope_ms_in_program_span`` events that
carry their map entry.  A joined trace is a dict::

    {"window": [start_s, end_s],
     "events": {"<device plane>": [[program, instruction, start_s, dur_s,
                                    entry or None], ...]},
     "modules": {"<module event name>": program or ""},   # as seen
     "maps_s": seconds that tracing.op_maps() took}

``program`` is the name the program registered under (``train.step``,
``llm.decode.8``), ``""`` for an operation of a module nobody registered.
A serving capture holds several modules and ``fusion.3`` exists in more
than one, so an operation is looked up in the map of ITS module: the
``XLA Modules`` line of the plane says which module ran when, and where
several registered programs share a module name (the prefill buckets),
the one whose map knows most of that module's operations, by name and
result shape, is taken.

Nothing is read where there is nothing to read: an untraced run, a
program without ``tracing.op_maps`` (the parent of the PR that added it),
a capture without a device plane (the CPU's rehearsal) give None, and
every reducer built on this returns None.
"""

from __future__ import annotations

import bisect
import re
import time
from typing import Dict, List, Optional, Sequence

from perfbench import program_trace, trace

_MODULE_ID = re.compile(r"\(\d+\)$")
_LOADED: Dict[str, Optional[dict]] = {}     # capture path -> joined trace


# ------------------------------------------------------------------ loading
def program_maps() -> Optional[Dict[str, dict]]:
    """``tracing.op_maps()`` of this process, or None where the program
    has no such thing or has registered nothing."""
    from ray_tpu.util import tracing
    op_maps = getattr(tracing, "op_maps", None)
    return (op_maps() or None) if op_maps else None


def of_run(facts: dict) -> Optional[dict]:
    """The joined trace of this run: the newest capture under the scratch
    directory against the maps of the programs this process registered,
    built once per process.  Leaves a summary in the run's notes
    (``facts["notes"]["op_scopes"]``: device time by scope and pass, the
    share of it the maps name, the largest operations they do not)."""
    if not facts.get("trace"):
        return None
    path = program_trace.capture_of(facts)
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED[path] = None
        t0 = time.perf_counter()
        maps = program_maps()
        maps_s = time.perf_counter() - t0
        if maps:
            from jax.profiler import ProfileData
            joined = join(raw_planes(ProfileData.from_file(path).planes),
                          maps)
            if joined is not None:
                joined["maps_s"] = maps_s
                if isinstance(facts.get("notes"), dict):
                    facts["notes"]["op_scopes"] = summary(joined, facts)
            _LOADED[path] = joined
    return _LOADED[path]


def raw_planes(planes) -> dict:
    """Planes (name, lines of named events) as plain lists: per device
    plane its operations ``[instruction, shape, start_s, dur_s]`` and its
    module runs ``[name, start_s, dur_s]``; the ``pb.window`` span."""
    out: Dict[str, dict] = {}
    window = None
    for plane in planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for ln in plane.lines:
                kind = ln.name.strip().lower()
                if kind == "xla ops":
                    for ev in ln.events:
                        if ev.duration_ns > 0:
                            name, _, shape = trace.short_name(
                                ev.name).partition(" ")
                            ops.append([name, shape, ev.start_ns / 1e9,
                                        ev.duration_ns / 1e9])
                elif kind == "xla modules":
                    modules += [[ev.name, ev.start_ns / 1e9,
                                 ev.duration_ns / 1e9] for ev in ln.events]
            if ops:
                out[plane.name] = {"ops": sorted(ops, key=lambda e: e[2]),
                                   "modules": sorted(modules,
                                                     key=lambda e: e[1])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == trace.WINDOW_SPAN:
                        window = [ev.start_ns / 1e9,
                                  (ev.start_ns + ev.duration_ns) / 1e9]
    return {"planes": out, "window": window}


# --------------------------------------------------------------------- join
def module_of(name: str) -> str:
    """``jit__step(1234)`` -> ``jit__step``."""
    return _MODULE_ID.sub("", name.strip())


def running_module(modules: Sequence[Sequence], starts: Sequence[float],
                   at: float) -> str:
    """The module run (of ``modules``, which start at ``starts``) that
    holds the moment ``at`` ("" for none)."""
    i = bisect.bisect_right(starts, at) - 1
    if i >= 0 and at < modules[i][1] + modules[i][2] + 1e-9:
        return modules[i][0]
    return ""


def same_shape(entry: dict, shape: str) -> bool:
    """A trace that prints no result shape is matched by name alone."""
    return not shape or entry.get("shape", "") == shape


def choose_program(ops: Sequence[Sequence], candidates: Dict[str, dict]
                   ) -> str:
    """Of the programs that share a module's name, the one whose map
    knows most of these operations by name and result shape."""
    def known(program):
        table = candidates[program]["ops"]
        return sum(1 for name, shape, *_ in ops
                   if name in table and same_shape(table[name], shape))
    best = max(sorted(candidates), key=known, default="")
    return best if best and known(best) else ""


def join(raw: dict, maps: Dict[str, dict]) -> Optional[dict]:
    """Every operation of the window with its program and map entry."""
    if not raw["planes"] or raw["window"] is None:
        return None
    start, end = raw["window"]
    by_module: Dict[str, Dict[str, dict]] = {}
    for program, m in maps.items():
        by_module.setdefault(m["module"], {})[program] = m
    events: Dict[str, list] = {}
    seen: Dict[str, str] = {}
    for plane, lines in raw["planes"].items():
        groups: Dict[str, list] = {}
        starts = [m[1] for m in lines["modules"]]
        for op in lines["ops"]:
            groups.setdefault(
                running_module(lines["modules"], starts, op[2]),
                []).append(op)
        mine = []
        for module, ops in groups.items():
            # without a modules line every registered program is a
            # candidate for the plane's operations
            candidates = by_module.get(module_of(module), {}) if module \
                else maps
            program = seen.setdefault(module,
                                      choose_program(ops, candidates))
            table = maps[program]["ops"] if program else {}
            for name, shape, s, d in ops:
                a, b = max(s, start), min(s + d, end)
                if b > a and not trace.is_wrapper(name):
                    entry = table.get(name)
                    if entry is not None and not same_shape(entry, shape):
                        entry = None
                    mine.append([program, name, a, b - a, entry])
        events[plane] = sorted(mine, key=lambda e: e[2])
    return {"window": [start, end], "events": events, "modules": seen}


# ---------------------------------------------------------------- selecting
def is_program(registered: str, wanted: str) -> bool:
    """``llm.decode`` names ``llm.decode`` and ``llm.decode.<bucket>``."""
    head, _, bucket = registered.rpartition(".")
    return registered == wanted or (head == wanted and bucket.isdigit())


def components(entry: Optional[dict]) -> List[str]:
    return entry["scope"].split("/") if entry and entry["scope"] else []


def selects(event: Sequence, params: dict) -> bool:
    """Whether the metric described by ``params`` counts this event:
    ``program`` (a registered name, or its buckets), then either
    ``scopes`` (exact components of the scope; ``pass`` optional) or
    ``unscoped`` (no component but those in ``ignore``, or no entry)."""
    program, _, _, _, entry = event
    if params.get("unscoped"):
        if program and not is_program(program, params["program"]):
            return False
        return not set(components(entry)) - set(params.get("ignore", ()))
    if not is_program(program, params["program"]) or entry is None:
        return False
    if params.get("pass") and entry["pass"] != params["pass"]:
        return False
    return bool(set(components(entry)) & set(params["scopes"]))


def seconds(joined: dict, params: dict) -> float:
    """Device seconds of the selected events, averaged over the planes."""
    planes = joined["events"]
    return sum(e[3] for evs in planes.values() for e in evs
               if selects(e, params)) / max(1, len(planes))


# ------------------------------------------------------------------ summary
def summary(joined: dict, facts: dict, top: int = 10) -> dict:
    """(builder) Device seconds of the traced window by program, leaf
    scope and pass, the share of the busy time the maps name, and the
    largest operations without a scope, on the first device."""
    events = next(iter(joined["events"].values()))
    total = sum(e[3] for e in events)
    by: Dict[str, float] = {}
    loose: Dict[str, list] = {}
    matched = 0.0
    for program, name, _, d, entry in events:
        matched += d if entry is not None else 0.0
        scope = "/".join(c for c in components(entry)
                         if c not in ("grads", "grad_accum"))
        key = "|".join((program, scope, entry["pass"] if entry else ""))
        by[key] = by.get(key, 0.0) + d
        if not scope:
            row = loose.setdefault(f"{program}|{name}", [0.0, entry])
            row[0] += d
    ranked = sorted(loose.items(), key=lambda kv: -kv[1][0])[:top]
    start, end = joined["window"]
    return {
        "traced_s": end - start, "busy_event_s": total,
        "matched_share": matched / total if total else None,
        "maps_s": joined.get("maps_s"), "modules": joined["modules"],
        "steps": facts.get("steps"), "window_s": facts.get("window_s"),
        "seconds_by_program_scope_pass": dict(
            sorted(by.items(), key=lambda kv: -kv[1])),
        "largest_unscoped": [
            [key, secs, (entry or {}).get("shape", ""),
             (entry or {}).get("src", ""), (entry or {}).get("prim", ""),
             (entry or {}).get("path", "")]
            for key, (secs, entry) in ranked]}
