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

The listeners are pure Python counter increments — it never touches the
arrays being compiled and adds nothing to dispatch latency.  Install is
idempotent; listeners cannot be unregistered in JAX, so the counter is
process-global and monotonic (consumers must difference it).
"""
from __future__ import annotations

_COMPILE_EVENT_PREFIX = "/jax/core/compile/backend_compile"

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}

_total = 0
_seconds = 0.0
_cache = {"hits": 0, "writes": 0}
_installed = False


def _listener(event: str, duration: float, **kwargs) -> None:
    global _total, _seconds
    if event.startswith(_COMPILE_EVENT_PREFIX):
        _total += 1
        _seconds += duration


def _cache_listener(event: str, **kwargs) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _cache[name] += 1


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
    from disk counts with its load time; tracing and lowering, which run
    before it, are not included."""
    return _seconds


def persistent_cache() -> dict:
    """Persistent-cache ``hits`` and ``writes`` since :func:`install`
    (monotonic)."""
    return dict(_cache)
