"""Process-wide XLA compile-event accounting.

JAX emits a ``jax.monitoring`` duration event every time it actually hands
a computation to the backend compiler (``/jax/core/compile/
backend_compile_duration`` on current releases, ``..._time_sec`` on older
ones); jit-cache hits emit nothing.  This module installs ONE passive
listener for those events and exposes a monotonic counter, which is what
lets the serve engine answer "did this dispatch compile anything?" without
reaching into jit internals:

* ``ServeEngine`` brackets every hot-path dispatch with :func:`total` and
  feeds the delta into the ``serve_compile_total`` counter (phase label
  ``serve`` vs ``warmup``), so a mid-serve compile — the latency cliff the
  AOT warmup exists to eliminate — is visible in metrics the moment it
  happens;
* the swanlint Layer-2 audit and ``bench_warmup`` gate "zero new XLA
  compiles after ``warmup()``" on the same counter, which also catches
  compiles the per-family jit-cache census cannot see (eager host-side
  ops like the temperature-row gather).

A second listener counts the persistent compilation cache's loads and
writes (``/jax/compilation_cache/cache_hits`` and ``.../cache_misses``,
the latter recorded when a missed executable is written), so a run can
show whether a warm restart was served from disk: :func:`persistent_cache`.

The same duration listener keeps a bounded timeline of set-up phases,
each entry ``(phase, receipt time on the program clock in
perf_counter_ns, seconds)``: ``trace`` (``jaxpr_trace_duration``),
``lower`` (``jaxpr_to_mlir_module_duration``), and each backend-compile
event as ``compile`` or, when the persistent cache answered it (its hit
event fires inside the compile event), ``cache_load``.  Tracing nests (a
jitted function traced inside another emits its own event first), so an
entry that overlaps a later one of the same group (trace and lower; compile
and cache load) is folded into it: the timeline holds only the outermost
intervals and :func:`phase_seconds` never counts a nested one twice.

The listeners are pure Python counter increments — it never touches the
arrays being compiled and adds nothing to dispatch latency.  Install is
idempotent; listeners cannot be unregistered in JAX, so the counter is
process-global and monotonic (consumers must difference it).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Iterable, List, Optional, Tuple

_COMPILE_EVENT_PREFIX = "/jax/core/compile/backend_compile"

_PHASE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
_GROUP = {"trace": "lower", "lower": "lower",
          "compile": "compile", "cache_load": "compile"}

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}

# timeline entries kept per group at most (the oldest drop first); a
# warmup of a few hundred executables holds about three per executable
TIMELINE_MAX = 1 << 16

_total = 0
_seconds = 0.0
_hit_pending = False
_cache = {"hits": 0, "writes": 0}
_timeline = {g: deque(maxlen=TIMELINE_MAX) for g in set(_GROUP.values())}
_installed = False


def _note(phase: str, now_ns: int, seconds: float) -> None:
    line = _timeline[_GROUP[phase]]
    start_ns = now_ns - int(seconds * 1e9)
    while line and line[-1][1] > start_ns:       # ended inside this one
        line.pop()
    line.append((phase, now_ns, seconds))


def _listener(event: str, duration: float, **kwargs) -> None:
    global _total, _seconds, _hit_pending
    now_ns = time.perf_counter_ns()
    if event.startswith(_COMPILE_EVENT_PREFIX):
        _total += 1
        _seconds += duration
        phase = "cache_load" if _hit_pending else "compile"
        _hit_pending = False
        _note(phase, now_ns, duration)
        return
    phase = _PHASE_EVENTS.get(event)
    if phase is not None:
        _note(phase, now_ns, duration)


def _cache_listener(event: str, **kwargs) -> None:
    global _hit_pending
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _cache[name] += 1
        _hit_pending = name == "hits"


def install() -> None:
    """Register the compile-event listeners (idempotent, process-global)."""
    global _installed
    if _installed:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_listener)
    jax.monitoring.register_event_listener(_cache_listener)
    _installed = True


def total() -> int:
    """Backend compiles observed since :func:`install` (monotonic)."""
    return _total


def seconds() -> float:
    """Seconds spent in those compiles since :func:`install` (monotonic).
    The event spans the persistent-cache lookup too, so a compile served
    from disk counts with its load time (:func:`phase_seconds` splits
    the seconds); tracing and lowering, which run before it, are not
    included."""
    return _seconds


def timeline() -> List[Tuple[str, int, float]]:
    """The outermost ``(phase, receipt perf_counter_ns, seconds)`` entries
    of every group, in receipt order."""
    return sorted((e for line in _timeline.values() for e in line),
                  key=lambda e: e[1])


def phase_seconds(phases: Iterable[str], after_ns: Optional[float] = None,
                  before_ns: Optional[float] = None) -> float:
    """Seconds of the timeline's ``phases`` whose events were received in
    ``(after_ns, before_ns]`` on the program clock (open ends by
    default)."""
    want = set(phases)
    return sum(sec for phase, t, sec in timeline()
               if phase in want
               and (after_ns is None or t > after_ns)
               and (before_ns is None or t <= before_ns))


def persistent_cache() -> dict:
    """Persistent-cache ``hits`` and ``writes`` since :func:`install`
    (monotonic)."""
    return dict(_cache)
