"""Cluster-wide request tracing + device-trace merge onto the timeline.

Reference: ``python/ray/util/tracing/`` (SURVEY.md §5.1) — OpenTelemetry
span context rides task/actor metadata so a request's causal tree spans
processes; and ``ray timeline`` renders host-side Chrome trace events.
TPU-native addition (§5.1 rebuild note): ``jax.profiler`` device traces
are merged ONTO THE SAME CLOCK as the host spans, so one
``ray_tpu.timeline()`` dump shows a train step's host dispatch span above
the XLA ops it ran.

Since the Dapper-style tracing overhaul, span context also rides the wire
protocol itself (the compact optional ``trace`` frame field,
``wire.TRACE_FIELD``, attached only on connections that negotiated a
trace-aware version) so one request's tree spans client → GCS → worker →
data-plane → Serve/LLM engine.  Sampling is **head-based**: the ROOT of a
trace decides once —

- ``tracing.trace(name)`` roots are always sampled (the user asked);
- ``tracing.request_trace(name)`` roots (per-request auto-spans, e.g. the
  Serve proxy) sample at ``trace_sample_rate``;
- children inherit the root's decision, and an UNSAMPLED context neither
  emits events nor rides the wire — the always-on cost of a sampled-out
  request is one ``random()`` call.

Usage::

    from ray_tpu.util import tracing

    with tracing.trace("ingest-and-train"):       # driver: new trace root
        ref = preprocess.remote(batch)            # ctx propagates to tasks
        ...

    with tracing.profile_device("train_step"):    # any process with jax
        state, m = step_fn(state, batch)          # device events captured
        jax.block_until_ready(m)
    # both land in ray_tpu.timeline(): host spans carry
    # trace_id/span_id/parent_id args; device events carry cat="device".

Hot loops (the LLM engine's step, the model runner) use ``hot_span``
instead: no context, no sampling, no shipping — a
``jax.profiler.TraceAnnotation`` on the device trace's own clock when jax
is already imported, and a running total the caller reads in-process
(DESIGN.md §4h; the span names are a contract, PERF.md §3).

What a device operation is.  A device trace names an operation by its
HLO instruction (``fusion.414``); what that instruction computes is known
to the program that compiled it.  The places that compile step programs
(``spmd.build_train_program``, the LLM ``ModelRunner``, the paged cache's
scatter) call ``register_program(name, jitted, args)``, which keeps a
reference and does nothing else; ``op_maps()`` (or ``op_map(compiled)``
for one executable) is what an operator or a benchmark calls afterwards
to read, from the compiled module's own text, each instruction's
``jax.named_scope`` path, pass, primitive and source line (the contract
of the keys: PERF.md section 3).

``hot_span`` and ``register_program`` / ``op_map`` / ``op_maps`` are the
two things a hot path may use from this module.

Set-up is seen from inside.  ``listen_to_compiles()`` registers the
process's ONE listener with ``jax.monitoring`` (the places that build step
programs call it; the XLA watchdog subscribes to it and registers none of
its own), and a ``setup_span`` is a ``hot_span`` round a piece of the
program's own set-up that takes what jax says of tracing, lowering,
compiling and its persistent cache while it is open: as attributes on the
span and as the catalog's ``rtpu_xla_compile_seconds`` /
``rtpu_xla_cache_lookups_total`` by ``program`` (PERF.md §3,
perfbench/SETUP_TRACE.md).  It works at compile events only: a step after
its first call runs none of it.

Span context lives in a ``contextvars.ContextVar`` (not a bare
``threading.local``): each thread still has its own current span, and the
context additionally flows into asyncio tasks scheduled from a thread
that holds a span (``run_coroutine_threadsafe`` captures the caller's
context), so async actor methods and Serve deployments inherit it.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import random
import re
import sys
import threading
import time
import weakref
from typing import Any, Dict, Iterator, List, Optional, Tuple

_SPAN: "contextvars.ContextVar[Optional[SpanContext]]" = \
    contextvars.ContextVar("rtpu_span", default=None)


class SpanContext:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "sampled",
                 "attrs")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str], name: str,
                 sampled: bool = True, attrs: Optional[dict] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.sampled = sampled
        # mutable span attributes merged into the event args at emit time
        # (lets a caller tag e.g. byte counts known only at span close)
        self.attrs = attrs

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name}

    @staticmethod
    def from_dict(d: Optional[dict]) -> Optional["SpanContext"]:
        if not d:
            return None
        return SpanContext(d["trace_id"], d["span_id"],
                           d.get("parent_id"), d.get("name", ""))

    # ------------------------------------------------- wire frame field
    # Compact form riding the optional ``trace`` frame field
    # (wire.TRACE_FIELD) on trace-aware connections: [trace_id, span_id].
    # parent/name never cross the wire — the receiver only ever creates
    # CHILDREN of the sender's span.  Only sampled contexts are packed
    # (head-based sampling: an unsampled root costs the wire nothing).
    def to_wire(self) -> list:
        return [self.trace_id, self.span_id]

    @staticmethod
    def from_wire(v, name: str = "") -> Optional["SpanContext"]:
        if not isinstance(v, (list, tuple)) or len(v) < 2:
            return None
        return SpanContext(str(v[0]), str(v[1]), None, name)


def current_span() -> Optional[SpanContext]:
    return _SPAN.get()


def _set_span(ctx: Optional[SpanContext]) -> None:
    _SPAN.set(ctx)


# Span/trace id generator: 64 random bits as hex.  NOT uuid4 — that is
# ~30µs/call on small sandboxed hosts (the PR-2 task-id finding), and a
# fully-traced task can mint several ids; a urandom-seeded PRNG is
# ~0.3µs with the same collision math for 64-bit ids.
_ids = random.Random(int.from_bytes(os.urandom(8), "big"))
_ids_lock = threading.Lock()


def _new_id() -> str:
    with _ids_lock:
        return f"{_ids.getrandbits(64):016x}"


# ------------------------------------------------------- wire plumbing
# The ONLY writers/readers of the optional ``trace`` frame field
# (rtlint's wire-trace rule keeps ad-hoc ``msg["trace"]`` plumbing out
# of the protocol layer — see tools/rtlint/wirecheck.py).

def attach_wire_trace(msg: dict,
                      ctx: Optional[SpanContext] = None) -> None:
    """Attach the current (or an explicitly carried) sampled span to an
    outgoing frame dict.

    Callers gate on the negotiated connection version
    (``wire.PROTO_TRACE`` / ``wire.DATA_PROTO_TRACE``) so un-upgraded
    peers never see the field."""
    if ctx is None:
        ctx = _SPAN.get()
    if ctx is not None and ctx.sampled:
        from ray_tpu._private import wire
        msg[wire.TRACE_FIELD] = [ctx.trace_id, ctx.span_id]


def extract_wire_trace(msg: dict, name: str = "") -> Optional[SpanContext]:
    """Pop and decode the ``trace`` field from an incoming frame dict
    (absent / malformed → None; the frame itself is never rejected)."""
    from ray_tpu._private import wire
    v = msg.pop(wire.TRACE_FIELD, None)
    if v is None:
        return None
    return SpanContext.from_wire(v, name=name)


def adopt(ctx: Optional[SpanContext]):
    """Make ``ctx`` the current span; returns a token for restore().
    Server dispatch loops bracket handler execution with adopt/restore
    so an adopted caller span can never leak onto the next frame."""
    return _SPAN.set(ctx)


def restore(token) -> None:
    _SPAN.reset(token)


# -------------------------------------------------------- thread rows
# Stable per-thread timeline rows.  ``threading.get_ident() % 100000``
# collided across threads (idents are reused pthread addresses — a new
# thread can inherit a dead one's ident, and with it its row AND name);
# instead rows are keyed by the Thread OBJECT (unique per thread
# lifetime, weakly held so dead threads' entries drop) and each thread
# gets a monotonically-assigned small id.  The FIRST span from a thread
# also emits a Chrome ``thread_name`` metadata event so multi-threaded
# spans render on distinct, named rows.
_tid_lock = threading.Lock()
_tid_counter = itertools.count(1)
# Thread object -> [tid, name_emitted_for_pid set]
_tids: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _thread_row(pid) -> tuple:
    """(tid, metadata_event_or_None) for the calling thread."""
    t = threading.current_thread()
    with _tid_lock:
        ent = _tids.get(t)
        if ent is None:
            ent = _tids[t] = [next(_tid_counter), set()]
        tid, seen_pids = ent
        if pid in seen_pids:
            return tid, None
        seen_pids.add(pid)
    return tid, {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                 "args": {"name": t.name}}


@contextlib.contextmanager
def trace(name: str, **attrs) -> Iterator[SpanContext]:
    """Open a span (new trace root, or child of the current span).

    Submissions made inside inherit the span context through task
    metadata and the wire trace field, so worker-side spans link back to
    this one in the timeline dump.  Extra keyword ``attrs`` (and anything
    added to ``ctx.attrs`` inside the block) are merged into the event
    args.  A child of an UNSAMPLED root inherits the sampled-out decision
    and emits nothing (head-based sampling)."""
    parent = _SPAN.get()
    ctx = SpanContext(
        trace_id=parent.trace_id if parent else _new_id(),
        span_id=_new_id(),
        parent_id=parent.span_id if parent else None,
        name=name,
        sampled=parent.sampled if parent else True,
        attrs=dict(attrs) if attrs else None)
    _SPAN.set(ctx)
    t0 = time.time()
    try:
        yield ctx
    finally:
        _SPAN.set(parent)
        if ctx.sampled:
            pid = _host_pid()
            tid, meta = _thread_row(pid)
            args = ctx.to_dict()
            if ctx.attrs:
                args.update(ctx.attrs)
            evs = [] if meta is None else [meta]
            evs.append({"name": name, "cat": "span", "ph": "X",
                        "pid": pid, "tid": tid,
                        "ts": t0 * 1e6, "dur": (time.time() - t0) * 1e6,
                        "args": args})
            _emit(evs)


@contextlib.contextmanager
def request_trace(name: str, **attrs) -> Iterator[Optional[SpanContext]]:
    """Per-request auto-root (e.g. one Serve HTTP request): when no span
    is current, roots a new trace sampled at ``trace_sample_rate``; under
    an existing span it is an ordinary child.  Sampled-out requests carry
    an unsampled context so every downstream propagation point skips the
    work — the whole tree costs one random() call."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    parent = _SPAN.get()
    if parent is None:
        rate = GLOBAL_CONFIG.trace_sample_rate
        sampled = bool(rate > 0.0 and random.random() < rate)
        if GLOBAL_CONFIG.metrics_enabled:
            from ray_tpu.util import metrics_catalog as mcat
            mcat.get("rtpu_trace_sampled_total").inc(
                tags={"decision": "sampled" if sampled else "dropped"})
        if not sampled:
            tok = _SPAN.set(SpanContext(_new_id(), _new_id(), None, name,
                                        sampled=False))
            try:
                yield None
            finally:
                _SPAN.reset(tok)
            return
    with trace(name, **attrs) as ctx:
        yield ctx


def child_span(parent: Optional[SpanContext], name: str) -> SpanContext:
    """A child context of ``parent`` (or a fresh sampled root when
    ``parent`` is None) — for execution paths that carry context by hand
    (task exec, actor dispatch) rather than via the context variable."""
    if parent is None:
        return SpanContext(_new_id(), _new_id(), None, name)
    return SpanContext(parent.trace_id, _new_id(), parent.span_id, name,
                       sampled=parent.sampled)


def emit_span(name: str, parent: Optional[SpanContext], t0: float,
              dur: float, cat: str = "span", pid=None, tid=None,
              **attrs) -> Optional[SpanContext]:
    """Emit one completed span as a child of an EXPLICIT parent context —
    for event-loop / cross-thread code (LLM engine iterations, GCS
    dispatch, data-plane serving) where the context variable does not
    follow the work.  ``t0`` is wall-clock seconds; returns the child
    context (so callers can link further spans under it), or None when
    the parent is absent or sampled out."""
    if parent is None or not parent.sampled:
        return None
    ctx = SpanContext(parent.trace_id, _new_id(), parent.span_id, name)
    if pid is None:
        pid = _host_pid()
    evs: List[dict] = []
    if tid is None:
        tid, meta = _thread_row(pid)
        if meta is not None:
            evs.append(meta)
    args = ctx.to_dict()
    if attrs:
        args.update(attrs)
    evs.append({"name": name, "cat": cat, "ph": "X", "pid": pid,
                "tid": tid, "ts": t0 * 1e6, "dur": dur * 1e6,
                "args": args})
    _emit(evs)
    return ctx


def emit_ctx_span(ctx: Optional[SpanContext], name: str, t0: float,
                  dur: float, cat: str = "span", **attrs) -> None:
    """Emit the completed-span event for an EXISTING context (one whose
    id was already handed to children — e.g. an actor method span set
    before execution): the event must carry that same span_id or the
    children orphan."""
    if ctx is None or not ctx.sampled:
        return
    pid = _host_pid()
    tid, meta = _thread_row(pid)
    evs: List[dict] = [] if meta is None else [meta]
    args = ctx.to_dict()
    if attrs:
        args.update(attrs)
    evs.append({"name": name, "cat": cat, "ph": "X", "pid": pid,
                "tid": tid, "ts": t0 * 1e6, "dur": dur * 1e6,
                "args": args})
    _emit(evs)


def span_event(name: str, parent: Optional[SpanContext], t0: float,
               dur: float, cat: str, pid, tid, **attrs) -> Optional[dict]:
    """Build (but do not ship) one span event as a child of ``parent`` —
    for processes that own an event buffer directly (the GCS appends
    under its own ``_events_lock`` instead of paying an RPC)."""
    if parent is None or not parent.sampled:
        return None
    ctx = SpanContext(parent.trace_id, _new_id(), parent.span_id, name)
    args = ctx.to_dict()
    if attrs:
        args.update(attrs)
    return {"name": name, "cat": cat, "ph": "X", "pid": pid, "tid": tid,
            "ts": t0 * 1e6, "dur": dur * 1e6, "args": args}


def _host_pid() -> str:
    """Timeline row for this process: the executing node for workers,
    'driver' for the driver (matching the task-event convention)."""
    from ray_tpu._private import worker as worker_mod
    w = worker_mod.try_global_worker()
    if w is None or w.role == "driver":
        return "driver"
    return w.node_id or "worker"


def _emit(events) -> None:
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.config import GLOBAL_CONFIG
    if not GLOBAL_CONFIG.timeline_enabled:
        return  # operator disabled the timeline: emit nothing anywhere,
        # so trace trees never appear partially (and GCS events stay flat)
    w = worker_mod.try_global_worker()
    if w is None:
        return
    if GLOBAL_CONFIG.metrics_enabled:
        from ray_tpu.util import metrics_catalog as mcat
        for e in events:
            if e.get("ph") != "M":
                mcat.get("rtpu_trace_spans_total").inc(
                    tags={"cat": e.get("cat", "span")})
    if w.role == "driver":
        # drivers have no task conn; ship via rpc (best effort)
        try:
            w.rpc_oneway("ingest_events", events=events)
        except Exception:  # noqa: BLE001 - tracing must never break work
            pass
    else:
        w._send_event({"kind": "profile_events", "events": events})


class hot_span:
    """A span for hot loops (the LLM engine's step, the model runner):
    one measurement, two sinks.

    (a) When ``jax`` is ALREADY imported in this process, entering opens
    a ``jax.profiler.TraceAnnotation(name, **attrs)``, so under any
    ``jax.profiler`` capture the span lies on the device trace's own
    clock; with no capture running that is a no-op of under a
    microsecond.  This module never imports jax itself (drivers import
    it and must stay off the chip).
    (b) On exit, ``perf_counter`` seconds and one count are added to
    ``totals[name]`` (``[count, seconds]``, a dict the caller owns and
    reads, e.g. ``LLMEngine.stats()["span_s"]``) and kept as ``.dur`` for
    a caller that also ships the span to the cluster timeline
    (``emit_span``).  Updates are plain read-modify-writes: a name
    entered from several threads at once may lose a count.

    Attribute values are str/int/float; a comma cuts a value short in
    the profiler's encoding, so join lists with ``|``; a string that
    parses as a number is read back as that number, and an empty one not
    at all.  ``set()`` adds attributes known only inside the span."""

    __slots__ = ("name", "totals", "dur", "_t0", "_ann")

    def __init__(self, name: str, totals: Dict[str, list], **attrs):
        self.name = name
        self.totals = totals
        self.dur = 0.0
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._ann = None if profiler is None else \
            profiler.TraceAnnotation(name, **attrs)

    def set(self, **attrs) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> "hot_span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        total = self.totals.setdefault(self.name, [0, 0.0])
        total[0] += 1
        total[1] += self.dur


# ------------------------------------------------- set-up, seen from inside
# JAX times its own compiles and tells whoever listens (``jax.monitoring``),
# on the thread that compiles: each stage below when it starts (a scalar)
# and when it ends (a duration), and what the persistent cache said.
# ``backend_compile_duration`` wraps ``compile_or_get_cached``, so it ends on
# a persistent-cache hit too and is silent only on jit's in-memory hit.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# called, with nothing, each time a backend compile (or its load from the
# persistent cache) ends, on the thread that compiled: the XLA watchdog
_ON_COMPILE: List[Any] = []
_LISTEN_LOCK = threading.Lock()


def _catalog():
    """The metrics catalog, or None where built-in series are off."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    if not GLOBAL_CONFIG.metrics_enabled:
        return None
    from ray_tpu.util import metrics_catalog
    return metrics_catalog


class _Compiles(threading.local):
    """The process's one listener on jax's compile events; what it holds
    is each thread's own: the set-up spans open on it, innermost last,
    and what jax has said of the compile in progress."""

    listening = False           # the process's, under _LISTEN_LOCK

    def __init__(self):
        self.spans: List["setup_span"] = []
        self.open = 0           # jax's timed stages, one inside another
        self.looked = self.hit = False

    def started(self, event: str, _start: float, **_kw) -> None:
        if event in _STAGES:
            self.open += 1

    def said(self, event: str, **_kw) -> None:
        if event == _CACHE_LOOKUP:
            self.looked, self.hit = True, False
        elif event == _CACHE_HIT:
            self.hit = True

    def took(self, event: str, seconds: float, **_kw) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            if event == _CACHE_READ:    # a part of the backend's seconds
                self.charge("cache_read", seconds)
            return
        self.open = max(0, self.open - 1)
        # a stage inside another (a jitted function traced inside a trace,
        # an operation a trace runs eagerly) is in the outer one's seconds
        if not self.open:
            self.charge(stage, seconds)
        if stage == "backend":
            # a miss is a lookup that ended without a hit: jax's own
            # ``cache_misses`` fires only where an entry is WRITTEN, which
            # the cache's size and time thresholds decide
            if self.looked:
                self.looked = False
                self.count("hit" if self.hit else "miss")
            for tell in _ON_COMPILE:
                tell()

    def program(self) -> str:
        return self.spans[-1].program if self.spans else "other"

    def charge(self, stage: str, seconds: float) -> None:
        if self.spans:
            self.spans[-1].seen[stage + "_s"] += seconds
        mcat = _catalog()
        if mcat:
            mcat.get("rtpu_xla_compile_seconds").observe(
                seconds, tags={"stage": stage, "program": self.program()})

    def count(self, result: str) -> None:
        if self.spans:
            self.spans[-1].seen[
                "cache_hits" if result == "hit" else "cache_misses"] += 1
        mcat = _catalog()
        if mcat:
            mcat.get("rtpu_xla_cache_lookups_total").inc(
                tags={"result": result, "program": self.program()})


_COMPILES = _Compiles()


def listen_to_compiles(on_compile: Any = None) -> None:
    """Register the process's one listener with ``jax.monitoring``, once
    (the places that build step programs call this: they have jax
    imported); ``on_compile``, if given, is called with nothing after
    every backend compile from then on, on the thread that compiled."""
    with _LISTEN_LOCK:
        if on_compile is not None and on_compile not in _ON_COMPILE:
            _ON_COMPILE.append(on_compile)
        if _Compiles.listening:
            return
        import jax.monitoring as monitoring
        monitoring.register_scalar_listener(_COMPILES.started)
        monitoring.register_event_listener(_COMPILES.said)
        monitoring.register_event_duration_secs_listener(_COMPILES.took)
        _Compiles.listening = True


class setup_span(hot_span):
    """A ``hot_span`` round a piece of the program's own set-up (weights,
    the cache's pool, the first call of a step program): while it is the
    innermost one open on its thread, what jax says of compiles is charged
    to its ``program`` (the tag of ``rtpu_xla_compile_seconds`` and
    ``rtpu_xla_cache_lookups_total``; a short closed set, PERF.md §3), and
    on exit the span carries what it saw as attributes: ``trace_s``,
    ``lower_s``, ``backend_s`` (of which ``cache_read_s``), ``cache_hits``,
    ``cache_misses``.  Its own seconds less the first three are the enqueue
    and the Python round it.  The outermost span alone observes its wall
    time under ``stage="total"``, so the sum over programs counts nothing
    twice.  ``program`` is positional only: ``llm.compile`` has an
    attribute of that name."""

    __slots__ = ("program", "seen")

    def __init__(self, name: str, totals: Dict[str, list], program: str, /,
                 **attrs):
        super().__init__(name, totals, **attrs)
        self.program = program
        self.seen = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                     "cache_read_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def __enter__(self) -> "setup_span":
        _COMPILES.spans.append(self)
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        self.set(**self.seen)
        super().__exit__(*exc)
        spans = _COMPILES.spans
        spans.remove(self)
        if spans:               # inside another: the outer one's wall time
            return
        mcat = _catalog()
        if mcat:
            mcat.get("rtpu_xla_compile_seconds").observe(
                self.dur, tags={"stage": "total", "program": self.program})


def profile_event_lists(out_dir: str):
    """Yield one raw Chrome-trace event list per ``*.trace.json.gz``
    file a jax profiler capture wrote under ``out_dir`` — the single
    parser for jax's profile output layout (re-basing in
    :func:`profile_device` and the overlap breakdown in ``bench.py``
    both consume it, so a layout change breaks one place)."""
    import glob
    import gzip
    import json

    for path in glob.glob(os.path.join(out_dir, "plugins", "profile",
                                       "*", "*.trace.json.gz")):
        data = json.loads(gzip.open(path).read())
        yield data.get("traceEvents", [])


def _rebase_device_events(raw, host_start_us: float, span, name: str
                          ) -> List[dict]:
    """Re-base one jax device-trace event list onto the wall-clock epoch
    axis.  Complete (``X``) events AND counter (``C``) events — memory /
    occupancy series — are carried through; counters keep their value
    args (merged with the span tag) so they render in the merged
    timeline.  Returns [] when the capture held no complete events
    (nothing to anchor the re-basing to)."""
    xs = [e["ts"] for e in raw
          if e.get("ts") is not None and e.get("ph") == "X"]
    if not xs:
        return []
    base = min(xs)
    events: List[dict] = []
    for e in raw:
        ph = e.get("ph")
        if ph not in ("X", "C") or e.get("ts") is None:
            continue
        ev = {"name": e.get("name", "?"), "cat": "device",
              "ph": ph,
              "pid": f"device:{name}",
              "tid": e.get("tid", 0),
              "ts": host_start_us + (e["ts"] - base)}
        if ph == "X":
            ev["dur"] = e.get("dur", 0)
        args = dict(e.get("args") or {}) if ph == "C" else {}
        if span is not None:
            args.update(span.to_dict())
        if args:
            ev["args"] = args
        events.append(ev)
    return events


@contextlib.contextmanager
def profile_device(name: str = "device",
                   keep_dir: Optional[str] = None) -> Iterator[None]:
    """Capture a jax.profiler device trace and merge it onto the cluster
    timeline's clock.

    jax writes a Chrome trace (``*.trace.json.gz``) with timestamps
    relative to capture start; events are re-based to wall-clock epoch µs
    (the timeline's clock) using the capture-start host time, tagged
    cat="device", and shipped to the GCS — one ``ray_tpu.timeline()``
    dump then shows host task/span rows and XLA device rows together."""
    import shutil
    import tempfile

    import jax

    out_dir = keep_dir or tempfile.mkdtemp(prefix="rtpu_devtrace_")
    span = current_span()
    host_start_us = time.time() * 1e6
    try:
        with jax.profiler.trace(out_dir):
            yield
    finally:
        events = []
        try:
            for raw in profile_event_lists(out_dir):
                events.extend(_rebase_device_events(
                    raw, host_start_us, span, name))
        except Exception:  # noqa: BLE001 - tracing must never break work
            events = []
        if events:
            _emit(events)
        if keep_dir is None:
            shutil.rmtree(out_dir, ignore_errors=True)


# ---------------------------------------------------------------- op maps
# What each instruction of a compiled step program is: its named-scope
# path, pass, primitive and source line, read from the executable's own
# text.  JAX writes the name stack into the module's stack-frame tables
# (``FunctionNames``) even with full tracebacks off, where an
# instruction's ``op_name`` is the bare primitive; the device trace prints
# the instruction's name, so the join is by name within a module.

# name -> (the jax.jit object, its abstract arguments); a new program of
# a name replaces the old, so the registry is bounded by the names
_PROGRAMS: Dict[str, Tuple[Any, tuple]] = {}

# path components that transforms and control flow write, not the program
# (those this repository's programs show; another is kept as a scope)
_TRANSFORMS = ("transpose", "jvp", "vmap")
_STRUCTURE = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation", "remat2", "jit", "custom_vjp_call"))
# what takes no scope from its users: it holds or passes on other work
_NOT_INFERRED = frozenset(("while", "conditional", "call", "tuple",
                           "parameter", "constant", "get-tuple-element"))
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_ARRAY = re.compile(r"\(?([a-z]+[0-9]*\[[0-9,]*\])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_SOURCE = re.compile(r'source_file="([^"]*)" source_line=(\d+)')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%([^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,)}]+)")


def register_program(name: str, jitted: Any, args: tuple) -> None:
    """Keep a reference to a step program and the abstract arguments it
    is called with (``jax.ShapeDtypeStruct`` trees), for ``op_maps``.
    Nothing is lowered, compiled or parsed here."""
    _PROGRAMS[name] = (jitted, args)


def abstract(tree: Any) -> Any:
    """A tree of arrays as ``jax.ShapeDtypeStruct``s, the abstract
    arguments ``register_program`` keeps (the caller has jax imported;
    this module does not import it).  A committed array keeps its
    sharding, any other (numpy, an array jax placed by default) none: so
    lowering with these finds the very program the call built, in jax's
    own in-memory caches."""
    jax = sys.modules["jax"]
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=x.sharding if getattr(x, "committed", False) else None),
        tree)


def registered_programs() -> List[str]:
    return sorted(_PROGRAMS)


def op_maps() -> Dict[str, dict]:
    """Every registered program as ``{"module": its HLO module's name,
    "ops": op_map(compiled)}``.  Lowers with the registered arguments
    and compiles: in the process that has run the program both are hits
    in jax's in-memory caches and what is left is the parsing (under a
    second for a train step of 7,000 instructions); a process that has
    not pays a load from its compilation cache, or a whole compile."""
    out = {}
    for name, (jitted, args) in sorted(_PROGRAMS.items()):
        text = jitted.lower(*args).compile().as_text()
        out[name] = {"module": module_name(text), "ops": op_map(text)}
    return out


def module_name(compiled: Any) -> str:
    """``HloModule jit__step, ...`` -> ``jit__step``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    head = text[:text.find("\n")]
    return head.split()[1].rstrip(",") if head.startswith("HloModule") else ""


def scope_of(path: str) -> str:
    """The components of a name-stack path that the program wrote, joined
    by ``/``: ``jit(_step)/grads/transpose(jvp(moe))/moe_dispatch`` ->
    ``grads/moe/moe_dispatch``."""
    kept = []
    for part in path.split("/"):
        while True:
            head, paren, rest = part.partition("(")
            if paren and head in _TRANSFORMS and rest.endswith(")"):
                part = rest[:-1]
            else:
                break
        head = part.partition("(")[0]
        if part and head not in _STRUCTURE and "->" not in part:
            kept.append(part)
    return "/".join(kept)


def _frame_tables(text: str) -> Tuple[Dict[int, Tuple[str, str]], int]:
    """stack_frame_id -> (function name, ``file.py:line``), and where the
    tables end.  With full tracebacks off a location is one frame, so a
    frame's parent is not followed."""
    rows: Dict[str, Dict[int, str]] = {t: {} for t in _TABLES}
    table, end = None, 0
    for m in re.finditer(r"^(.*)$", text[:text.find("\n%") + 1 or None],
                         re.M):
        line = m.group(1)
        if line in _TABLES:
            table = line
        elif table and line[:1].isdigit():
            key, _, value = line.partition(" ")
            rows[table][int(key)] = value
            end = m.end()
        elif line and table:
            table = None

    def field(row: str, key: str) -> int:
        hit = re.search(rf"{key}=(\d+)", row)
        return int(hit.group(1)) if hit else 0

    frames = {}
    for fid, row in rows["StackFrames"].items():
        loc = rows["FileLocations"].get(field(row, "file_location_id"), "")
        fn = rows["FunctionNames"].get(field(loc, "function_name_id"), '""')
        fname = rows["FileNames"].get(field(loc, "file_name_id"), '""')
        frames[fid] = (fn.strip('"'),
                       f"{os.path.basename(fname.strip(chr(34)))}:"
                       f"{field(loc, 'line')}")
    return frames, end


def _split_shape(rest: str) -> Tuple[str, str]:
    """``(bf16[6400]{0}, f32[8]) fusion(...)`` -> (shape, what follows)."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[:i + 1], rest[i + 2:]
    return rest, ""


class _Module:
    """A compiled module's text as parsed: each instruction's entry and
    opcode, the computation it lies in, the computations it calls and
    the instructions of its computation that use it."""

    def __init__(self, text: str):
        frames, at = _frame_tables(text)
        # the module holds a backward at all
        self.differentiated = "transpose(jvp(" in text
        self.ops: Dict[str, dict] = {}
        self.opcode: Dict[str, str] = {}
        self.members: Dict[str, List[str]] = {}     # computation -> its own
        self.comp_of: Dict[str, str] = {}
        self.called: Dict[str, List[Tuple[str, str]]] = {}  # (how, comp)
        self.users: Dict[str, List[str]] = {}
        operands: Dict[str, List[str]] = {}
        comp = ""
        for line in text[at:].splitlines():
            if not line.startswith(" "):
                head = _COMPUTATION.match(line)
                if head:
                    comp = head.group(1)
                continue
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            name, rest = m.groups()
            shape, tail = _split_shape(rest)
            opcode, _, args = tail.partition("(")
            op_name = (_OP_NAME.search(tail) or [None, ""])[1]
            fn, src = frames.get(int((_FRAME.search(tail) or [0, 0])[1]),
                                 ("", ""))
            old = _SOURCE.search(tail)
            if old:                         # metadata without frame tables
                src = f"{os.path.basename(old.group(1))}:{old.group(2)}"
            # jax writes the name stack into the frame's function name and
            # the bare primitive into op_name, or (under an inlined jit)
            # the whole of it into op_name
            path, _, prim = op_name.rpartition("/")
            if not path and fn != prim:
                path = fn
            first = _ARRAY.match(shape)
            self.ops[name] = {
                "scope": scope_of(path), "path": path,
                "pass": self.pass_of(path), "prim": prim, "src": src,
                "shape": first.group(1) if first else ""}
            self.opcode[name] = opcode.strip()
            self.comp_of[name] = comp
            self.members.setdefault(comp, []).append(name)
            calls = _CALLED.findall(tail)
            branches = _BRANCHES.search(tail)
            if branches:
                calls += [("branch", c.strip().lstrip("%"))
                          for c in branches.group(1).split(",")]
            if calls:
                self.called[name] = calls
            operands[name] = _OPERAND.findall(args.partition("), ")[0])
        for name, ins in operands.items():
            for i in ins:
                if self.comp_of.get(i) == self.comp_of[name]:
                    self.users.setdefault(i, []).append(name)

    def pass_of(self, path: str) -> str:
        """What a path says of its pass by itself.  In a differentiated
        module what ``jax.checkpoint`` lowers (its name stack starts with
        ``checkpoint``) is the backward's: the recomputation and the
        transposed block."""
        if "transpose(" in path or "rematted_computation" in path or (
                self.differentiated and path.startswith("checkpoint/")):
            return "bwd"
        return "fwd" if "jvp(" in path else ""

    def infer_from_users(self) -> None:
        """An instruction with no path of its own takes the one path that
        its users agree on; a chain of such (copy-start, copy-done) is
        followed."""
        for _ in range(4):
            moved = False
            for name, entry in self.ops.items():
                if entry["path"] or name not in self.users \
                        or self.opcode[name] in _NOT_INFERRED:
                    continue
                said = {(self.ops[u]["path"], self.ops[u]["src"])
                        for u in self.users[name] if self.ops[u]["path"]}
                if len({path for path, _ in said}) == 1:
                    path, src = sorted(said)[0]
                    entry.update(path=path, scope=scope_of(path),
                                 src=entry["src"] or src, inferred=True,
                                 **{"pass": self.pass_of(path)})
                    moved = True
            if not moved:
                break

    def said(self, comp: str, seen: frozenset) -> str:
        """The pass that a computation's instructions, and those of what
        it calls, name by themselves."""
        out = set()
        for name in self.members.get(comp, ()):
            # a constant does no work, and one that the compiler shares
            # between the two loops keeps either's metadata
            if self.opcode[name] != "constant":
                out.add(self.ops[name]["pass"])
            for _, inner in self.called.get(name, ()):
                if inner not in seen:
                    out.add(self.said(inner, seen | {inner}))
        return "bwd" if "bwd" in out else "fwd" if "fwd" in out else ""

    def feeds_backward(self, loop: str, body_pass: Dict[str, str]) -> bool:
        seen, todo = {loop}, [loop]
        while todo:
            for user in self.users.get(todo.pop(), ()):
                if user in seen:
                    continue
                if "bwd" in (self.ops[user]["pass"], body_pass.get(user)):
                    return True
                seen.add(user)
                todo.append(user)
        return False

    def resolve_passes(self) -> None:
        """Inside a loop body a name stack is relative to the loop, so the
        pass is the body's: what its instructions say by themselves
        (``said``); a body that says nothing is ``fwd`` if what its loop
        returns reaches a ``bwd`` instruction of the same computation (the
        forward scan of a differentiated program), else it has its
        caller's pass.  A loop of one trip is no loop in the compiled
        module: its body's relative paths lie in the entry computation,
        where in a differentiated module they are the forward's (the
        backward's say ``checkpoint`` or ``transpose``)."""
        done = set()

        def descend(comp: str, inherited: str, top: bool = False) -> None:
            done.add(comp)
            body_pass = {
                name: self.said(inner, frozenset((inner,)))
                for name in self.members.get(comp, ())
                for how, inner in self.called.get(name, ()) if how == "body"}
            for name in self.members.get(comp, ()):
                entry = self.ops[name]
                if name in body_pass:
                    entry["pass"] = body_pass[name] or entry["pass"] or (
                        "fwd" if self.feeds_backward(name, body_pass)
                        else inherited)
                elif not entry["pass"]:
                    relative = entry["path"] and not entry["path"] \
                        .startswith(("jit(", "pjit("))
                    entry["pass"] = inherited or (
                        "fwd" if top and self.differentiated and relative
                        else "")
                for _, inner in self.called.get(name, ()):
                    if inner not in done:
                        descend(inner, entry["pass"])

        inner_comps = {c for calls in self.called.values() for _, c in calls}
        for comp in self.members:
            if comp not in inner_comps:      # the entry computation
                descend(comp, "", top=True)

    def note_mixed_fusions(self) -> None:
        for name, calls in self.called.items():
            if self.opcode[name] != "fusion":
                continue
            inner = sorted({self.ops[i]["scope"]
                            for i in self.members.get(calls[0][1], ())
                            if self.ops[i]["scope"]})
            if len(inner) > 1:
                self.ops[name]["mixed"] = inner


def op_map(compiled: Any) -> Dict[str, dict]:
    """Instruction name (as a device trace prints it) -> what it is, for
    every instruction of every computation of a compiled executable (or
    of its ``as_text()``).  Pure text parsing, no device work.

    ``path``: the name stack as the module holds it; ``scope``: its
    components that the program wrote (``scope_of``); ``pass``: ``fwd``
    under ``jvp``, ``bwd`` under ``transpose`` or in what a checkpoint
    lowers, else what the enclosing loop body says, else ``""``;
    ``prim``: the jax primitive; ``src``: ``file.py:line``; ``shape``:
    the (first) result, as ``perfbench.trace.short_name`` prints it.
    A fusion has its root's metadata and, where the instructions it
    calls name more than one scope, ``mixed``: those scopes.  An
    instruction without metadata (a kernel's custom call, a copy the
    compiler added) takes the one path its users agree on and says so
    with ``inferred``."""
    module = _Module(compiled if isinstance(compiled, str)
                     else compiled.as_text())
    module.infer_from_users()
    module.resolve_passes()
    module.note_mixed_fusions()
    return module.ops
