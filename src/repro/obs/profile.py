"""Dispatch-level profiling: sections, spans, compile-phase split and
recompile counting.

Three instruments, all cheap enough to leave on:

* :class:`CompileLog` -- a process-global accumulator of the
  ``jax.monitoring`` compile-phase duration events
  (jaxpr tracing, MLIR lowering, backend compilation).  A
  :class:`Profiler` section snapshots it around a region of host code,
  which splits the region's wall time into trace/lower/compile vs
  everything else (execute + host work) *without* AOT plumbing -- a
  warm dispatch shows zero compile seconds, a shape miss shows exactly
  where the time went.
* :class:`RecompileCounter` -- reads the jit caches of the functions it
  watches (``fn._cache_size()``, keyed on abstract input signatures:
  shapes/dtypes + static args).  A stable count across repeated
  dispatches proves shape stability (the property
  ``Evaluator.pad_quantum`` exists to buy); a growing count is a
  recompile leak (one compiled shape per call).
* :func:`span` -- the one call library code uses to mark its host
  work (and :func:`count`, the one it adds to a program counter
  with).  A :class:`Profiler` section makes its profiler the *current*
  one for the section's duration; ``span(name)`` opens a section of
  the profiler it is given, else of the current one, else does nothing
  (a shared no-op context: no clock read, no allocation).  So a
  rollup deep in the fleet code is timed whenever its caller runs
  under a profiler, without a ``profiler=`` parameter on every
  function in between.

The two counters read JAX APIs directly and raise if one goes missing:
a counter that read ``-1`` on both sides of a window would show a
recompile delta of 0 while measuring nothing.
"""

from __future__ import annotations

import contextlib
import contextvars
import copy
import time
from typing import Callable, Dict, Optional, Set

import jax

#: jax.monitoring event -> the compile phase it times
_EVENT_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_PHASES = ("trace_s", "lower_s", "compile_s")


class CompileLog:
    """Accumulates jax compile-phase durations via ``jax.monitoring``.

    One process-global instance (:data:`COMPILE_LOG`) is installed at
    import; sections diff its :meth:`snapshot` around regions.  The
    listener registration is append-only in jax, so exactly one install
    per log instance."""

    def __init__(self) -> None:
        self.totals: Dict[str, float] = {k: 0.0 for k in _PHASES}
        self.counts: Dict[str, int] = {k: 0 for k in _PHASES}
        self.installed = False

    def _listen(self, event: str, duration: float, **kw) -> None:
        key = _EVENT_KEYS.get(event)
        if key is not None:
            self.totals[key] += float(duration)
            self.counts[key] += 1

    def install(self) -> "CompileLog":
        if not self.installed:
            jax.monitoring.register_event_duration_secs_listener(
                self._listen)
            self.installed = True
        return self

    def snapshot(self) -> Dict[str, Dict]:
        return {"totals": dict(self.totals), "counts": dict(self.counts)}


#: the process-global compile log every Profiler defaults to
COMPILE_LOG = CompileLog().install()


class Profiler:
    """Named per-section counters with a compile/execute wall split.

    ``with prof.section("fleet.engine"): ...`` accumulates, per name:
    ``calls``, ``wall_s``, the compile-phase seconds that elapsed
    inside (``trace_s``/``lower_s``/``compile_s`` from the
    :class:`CompileLog`), ``n_compiles`` (backend compilations
    triggered), and ``execute_s`` (wall minus compile phases -- device
    execution plus host-side work).  Sections nest; compile time then
    shows up in every enclosing section, which is the truthful reading
    (it *did* elapse there).

    While a section is open its profiler is the current one, which is
    what :func:`span` falls back to; the previous current profiler is
    restored on exit, exception or not.  :func:`span` always enters
    through :meth:`section`, so a subclass that overrides it (to add a
    trace annotation, say) sees every span.  A span whose name is
    already open on the profiler is not entered again: rollups that
    call each other are counted once.  Calling :meth:`section`
    directly always counts."""

    def __init__(self, compile_log: Optional[CompileLog] = None) -> None:
        self.sections: Dict[str, Dict[str, float]] = {}
        #: program counters, by name, added to by :func:`count`
        self.counters: Dict[str, float] = {}
        self._log = compile_log if compile_log is not None else COMPILE_LOG
        self._open: Set[str] = set()

    @contextlib.contextmanager
    def section(self, name: str):
        token = _CURRENT.set(self)
        fresh = name not in self._open
        self._open.add(name)
        before = self._log.snapshot()
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            wall = time.perf_counter() - t0
            if fresh:
                self._open.discard(name)
            _CURRENT.reset(token)
            after = self._log.snapshot()
            d = self.sections.setdefault(name, {
                "calls": 0.0, "wall_s": 0.0, "trace_s": 0.0,
                "lower_s": 0.0, "compile_s": 0.0, "execute_s": 0.0,
                "n_compiles": 0.0})
            d["calls"] += 1.0
            d["wall_s"] += wall
            in_compile = 0.0
            for k in _PHASES:
                dt = after["totals"][k] - before["totals"][k]
                d[k] += dt
                in_compile += dt
            d["n_compiles"] += (after["counts"]["compile_s"]
                                - before["counts"]["compile_s"])
            d["execute_s"] += max(0.0, wall - in_compile)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """JSON-ready copy of all section counters."""
        return copy.deepcopy(self.sections)


#: the profiler whose section is open in this context, if any
_CURRENT: contextvars.ContextVar[Optional[Profiler]] = \
    contextvars.ContextVar("repro_obs_current_profiler", default=None)
_NOOP = contextlib.nullcontext()


def span(name: str, profiler: Optional[Profiler] = None):
    """A section called ``name`` of ``profiler``, else of the current
    profiler, else a shared no-op context.  Nothing is entered when
    that profiler already has a section of this name open.
    ``with span(...) as timed`` binds the profiler, or None where
    nothing is timed.  A span does not wait for the device: code that
    wants device work inside a span's wall time blocks itself, and
    only when ``timed`` is set."""
    prof = profiler if profiler is not None else _CURRENT.get()
    if prof is None or name in prof._open:
        return _NOOP
    return prof.section(name)


def count(name: str, inc: float = 1.0) -> None:
    """Add ``inc`` to the current profiler's counter ``name`` (named as
    spans are, ``<layer>.<what>``); nothing without a profiler."""
    prof = _CURRENT.get()
    if prof is not None:
        prof.counters[name] = prof.counters.get(name, 0.0) + float(inc)


def jit_cache_size(fn) -> int:
    """Entries in a jitted function's compile cache (one per abstract
    input signature seen)."""
    return int(fn._cache_size())


class RecompileCounter:
    """Watches the jit caches of named functions.

    ``RecompileCounter(run_programs=engine.run_programs).counts()``
    returns ``{name: cache entries}``; :meth:`delta` diffs two readings
    (positive = that many new abstract signatures were compiled in
    between).  Counts are process-global per function, so *stability*
    across repeated calls, not the absolute value, is the signal."""

    def __init__(self, **fns: Callable) -> None:
        if not fns:
            raise ValueError("name at least one function to watch")
        self._fns = dict(fns)

    @classmethod
    def engine_default(cls) -> "RecompileCounter":
        """The engine + fleet-timing dispatch surface."""
        from repro.core import engine, timing
        return cls(apply_op=engine.apply_op,
                   run_program=engine.run_program,
                   run_programs=engine.run_programs,
                   simulate_fleet_ops=timing.simulate_fleet_ops)

    def counts(self) -> Dict[str, int]:
        return {n: jit_cache_size(f) for n, f in self._fns.items()}

    def delta(self, before: Dict[str, int]) -> Dict[str, int]:
        return {n: c - before.get(n, 0)
                for n, c in self.counts().items()}
