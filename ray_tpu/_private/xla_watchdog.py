"""RAY_TPU_XLA_WATCHDOG — runtime oracle for XLA compute-plane hygiene
(DESIGN.md §4q; the static half is tools/rtlint/jaxlint.py).

Fourth oracle in the lock_watchdog / resource_sanitizer /
block_watchdog lineage.  ``RAY_TPU_XLA_WATCHDOG=1`` arms two checks,
both scoped to *step regions* — the ``compile_budget("<site>")``
context managers wrapped around the steady-state jit dispatches
(train step, LLM prefill/decode):

- **No host transfers inside a step region.**  JAX's transfer guard is
  installed per-region (``transfer_guard_device_to_host("disallow")``
  — catches implicit device→host transfers natively on TPU), and
  because the CPU rig's host reads are zero-copy (no transfer exists
  for the guard to see — and the device array's C-level buffer
  protocol bypasses any Python ``__array__`` patch), the watchdog
  additionally interposes on ``jax.device_get``, on ``np.asarray`` /
  ``np.array`` of a device array, and on the array's ``_value``
  host-materialization property (the choke point behind ``float()`` /
  ``int()`` / ``.item()`` / ``.tolist()``) while armed: a host read on
  a thread inside a step region raises :class:`XlaHygieneViolation`
  with the transferred shape and the acquiring stack.  jax-internal
  callers are exempt (const lowering during a compile materializes
  captured arrays — a compile-time cost already metered by the budget,
  not a per-step sync).  Designed syncs (the engine's post-dispatch
  ``np.asarray`` pulls, bench's device_get-of-a-scalar timing sync)
  sit OUTSIDE the regions and stay legal.

- **Zero steady-state recompiles.**  Every backend compile is observed
  through ``jax.monitoring``: the ``/jax/core/compile/
  backend_compile_duration`` event wraps ``compile_or_get_cached``
  (``jax/_src/interpreters/pxla.py``, jax 0.9.0), so it fires once per
  distinct program a process builds, on a hit in the PERSISTENT
  compilation cache too, and is silent only on jit's in-memory hit (the
  program was built in this process already).  The budget counts both
  as a compile: either way the step path stalled for a program it did
  not have.  The oracle registers no listener of its own: it subscribes
  to ``util.tracing``'s (``listen_to_compiles``), the one in the
  process, armed or not.  Each compile is charged to the innermost
  active region's owner.  A region owner exceeding ``budget +
  RAY_TPU_XLA_WATCHDOG_WARMUP`` raises on region exit — generalizing
  the LLM engine's ad-hoc bounded-compiles assertion into a declared
  contract (``lock_watchdog.COMPILE_BUDGETS``; jaxlint proves the
  table and the call sites agree 1:1, exactly like BLOCK_BOUNDS).
  The violating compile also folds into the §4o profiler as a
  synthetic ``waiting:recompile:<site>`` frame and into the flight
  recorder.

Zero-cost when disarmed: ``compile_budget`` is a no-op context
manager, nothing is interposed and nothing subscribes.
"""

from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import Dict, List, Tuple

from ray_tpu._private.lock_watchdog import COMPILE_BUDGETS


class XlaHygieneViolation(RuntimeError):
    """A step region saw a host transfer or an over-budget recompile."""


def xla_watchdog_enabled() -> bool:
    return os.environ.get("RAY_TPU_XLA_WATCHDOG") == "1"


def _warmup_budget() -> int:
    try:
        return int(os.environ.get("RAY_TPU_XLA_WATCHDOG_WARMUP", "0"))
    except ValueError:
        return 0


# --------------------------------------------------------------- state
# Innermost-first stack of active compile_budget regions on this
# thread (the listener and the host-read interposers charge to the
# stack top).
_TLS = threading.local()

# site -> [compiles, transfer violations]; guarded by: _XLA_STATS_LOCK
_XLA_STATS: Dict[str, List[int]] = {}
_XLA_STATS_LOCK = threading.Lock()

_INSTALL_LOCK = threading.Lock()
_installed = False


def xla_stats() -> Dict[str, Tuple[int, int]]:
    """{site: (compiles, transfer_violations)} since the last reset."""
    with _XLA_STATS_LOCK:
        return {k: (v[0], v[1]) for k, v in _XLA_STATS.items()}


def reset_xla_stats() -> None:
    with _XLA_STATS_LOCK:
        _XLA_STATS.clear()


def _region_stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _note_compile() -> None:
    st = _region_stack()
    if not st:
        return
    region = st[-1]
    region._compiles += 1
    with _XLA_STATS_LOCK:
        _XLA_STATS.setdefault(region.site, [0, 0])[0] += 1
    if region._compiles > region._allowed():
        region._overrun = True
        # visible while the violation is in flight: a profiler sample
        # between this compile and the region exit sees the blocked
        # step under waiting:recompile:<site> (§4o namespace)
        from ray_tpu.util import profiler
        profiler.note_lock_wait(f"recompile:{region.site}")


def _host_read(what: str, aval) -> None:
    """Called by the interposers on every host read while armed."""
    st = _region_stack()
    if not st:
        return
    region = st[-1]
    with _XLA_STATS_LOCK:
        _XLA_STATS.setdefault(region.site, [0, 0])[1] += 1
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    stack = "".join(traceback.format_stack(limit=16)[:-2])
    from ray_tpu._private import flight_recorder
    if flight_recorder.enabled():
        flight_recorder.record(
            "xlatransfer", f"{region.site} {what} shape={shape}")
    raise XlaHygieneViolation(
        f"host transfer inside step region {region.site!r}: {what} of "
        f"shape={shape} dtype={dtype} — step paths must stay on "
        f"device (move the pull outside the compile_budget region or "
        f"fix the sync).  Transfer point:\n{stack}")


def _caller_is_jax_internal() -> bool:
    """True when the frame that triggered a host read lives inside
    jax/jaxlib — e.g. const lowering materializing a captured array
    during a compile.  That cost is metered by the compile budget, not
    the transfer check."""
    f = sys._getframe(2)
    while f is not None:
        mod = f.f_globals.get("__name__", "")
        if mod.startswith("ray_tpu._private.xla_watchdog"):
            f = f.f_back
            continue
        return mod == "jax" or mod.startswith(("jax.", "jaxlib"))
    return False


def _install_interposers() -> None:
    """Wrap jax.device_get, np.asarray/np.array, and the device
    array's ``_value`` host-materialization property.

    The C++ device array dispatches ``__array__``/``__float__`` at the
    C level (a Python patch on the class is never consulted, and the
    numpy buffer-protocol path is zero-copy on CPU), so the hooks sit
    one layer up: the numpy entry points and the ``_value`` property
    every scalar coercion funnels through.  Installed once,
    process-wide, only after the first ARMED region entry; each
    wrapper is a fast passthrough when no region is active on the
    calling thread."""
    global _installed
    with _INSTALL_LOCK:
        if _installed:
            return
        import jax
        import numpy as np

        from ray_tpu.util import tracing
        tracing.listen_to_compiles(_note_compile)

        orig_device_get = jax.device_get

        def guarded_device_get(x):
            if _region_stack():
                _host_read("jax.device_get",
                           jax.tree_util.tree_leaves(x)[0]
                           if jax.tree_util.tree_leaves(x) else None)
            return orig_device_get(x)

        jax.device_get = guarded_device_get

        def make_np(orig, what):
            def guarded(a, *args, **kw):
                if _region_stack() and isinstance(a, jax.Array) \
                        and not _caller_is_jax_internal():
                    _host_read(what, a)
                return orig(a, *args, **kw)
            return guarded

        np.asarray = make_np(np.asarray, "np.asarray")
        np.array = make_np(np.array, "np.array")

        try:
            from jax._src.array import ArrayImpl
            orig_value = ArrayImpl._value
        except (ImportError, AttributeError):  # pragma: no cover
            ArrayImpl = None
        if ArrayImpl is not None:
            def guarded_value(self):
                if _region_stack() and not _caller_is_jax_internal():
                    _host_read("host materialization (float()/int()/"
                               ".item()/.tolist())", self)
                return orig_value.fget(self)

            ArrayImpl._value = property(guarded_value)
        _installed = True


class compile_budget:
    """One step region: scoped transfer guard + compile accounting.

    Long-lived — the owner (a ModelRunner, an SpmdProgram wrapper)
    creates it once and re-enters it around every steady-state
    dispatch; the compile counter spans the owner's life, so "zero
    recompiles after warmup" is checked per owner, not per call:

        self._budget = compile_budget("llm.prefill", len(buckets))
        ...
        with self._budget:
            out = self._prefill(params, toks, last_pos=pos)
        logits = np.asarray(out)          # designed pull: OUTSIDE

    ``budget=`` overrides the ``COMPILE_BUDGETS`` default for sites
    whose ceiling is config-driven (bucket-table length); the table
    row is still mandatory — it is the declared ceiling, and jaxlint
    pins the site name to it (``compile-budget-undeclared``).
    No-op unless ``RAY_TPU_XLA_WATCHDOG=1``.
    """

    __slots__ = ("site", "budget", "_compiles", "_overrun", "_entered",
                 "_tg")

    def __init__(self, site: str, budget: int = None):
        self.site = site
        self.budget = budget
        self._compiles = 0
        self._overrun = False
        self._entered = False

    def _allowed(self) -> int:
        base = self.budget if self.budget is not None \
            else COMPILE_BUDGETS.get(self.site, 0)
        return int(base) + _warmup_budget()

    def __enter__(self):
        if not xla_watchdog_enabled():
            return self
        if self.site not in COMPILE_BUDGETS:
            raise XlaHygieneViolation(
                f"compile_budget site {self.site!r} is not declared in "
                f"lock_watchdog.COMPILE_BUDGETS (rtlint: "
                f"compile-budget-undeclared)")
        _install_interposers()
        import jax
        self._entered = True
        self._tg_enter(jax)
        _region_stack().append(self)
        return self

    # The real JAX transfer guard rides along for backends where
    # device→host is an actual transfer (TPU); "disallow" scopes the
    # implicit-transfer check to this region.  Kept per-entry so
    # regions nest correctly.
    def _tg_enter(self, jax) -> None:
        self._tg = jax.transfer_guard_device_to_host("disallow")
        self._tg.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self._entered:
            return False
        self._entered = False
        st = _region_stack()
        if st and st[-1] is self:
            st.pop()
        self._tg.__exit__(exc_type, exc, tb)
        if self._overrun:
            self._overrun = False
            from ray_tpu.util import profiler
            profiler.clear_lock_wait()
            from ray_tpu._private import flight_recorder
            if flight_recorder.enabled():
                flight_recorder.record(
                    "xlarecompile",
                    f"{self.site} compiled {self._compiles} programs "
                    f"over budget {self._allowed()}")
            if exc_type is None:
                raise XlaHygieneViolation(
                    f"steady-state recompile at site {self.site!r}: "
                    f"{self._compiles} distinct programs compiled, "
                    f"over the declared budget {self._allowed()} "
                    f"(COMPILE_BUDGETS[{self.site!r}]"
                    f"{' + warmup' if _warmup_budget() else ''}) — a "
                    f"shape/dtype/static-arg is changing per call; "
                    f"run tools/rtlint --pass retrace on the step "
                    f"path")
        # a transfer-guard XlaRuntimeError from the scoped guard (TPU
        # path) converts to the typed violation with the site attached
        if exc is not None and exc_type is not XlaHygieneViolation \
                and "Disallowed" in str(exc) and "transfer" in str(exc):
            raise XlaHygieneViolation(
                f"host transfer inside step region {self.site!r}: "
                f"{exc}") from exc
        return False
