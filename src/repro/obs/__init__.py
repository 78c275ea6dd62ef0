"""repro.obs — dependency-free serving observability.

Two host-side primitives threaded through the serving stack:

* :mod:`repro.obs.metrics` — a :class:`MetricsRegistry` of counters,
  gauges and fixed-bucket histograms with Prometheus-text and JSON
  snapshot exporters.  Instrumentation lives entirely on the host side of
  every dispatch boundary: no wall-clock reads or metric updates ever
  happen inside jitted code, and device-side quantities are step-indexed
  (engine scheduler steps), never timed.
* :mod:`repro.obs.compile_events` — a process-global XLA compile
  counter fed by ``jax.monitoring`` backend-compile events.  Backs the
  engine's ``serve_compile_total`` counter and every zero-compile gate
  (warmup coverage, steady-state recompile checks): unlike jit-cache
  introspection it also sees eager one-off executables.  It also keeps
  a bounded timeline of tracing, lowering, compile and cache-load
  seconds on the program clock.
* :mod:`repro.obs.trace` — a structured JSONL event trace (admission,
  chunk dispatch, first token, decode dispatch, retirement, page
  map/free, pool grow/exhaustion, …) keyed by request uid and engine
  step and stamped with the program clock, host ``span``s that land in
  the ``jax.profiler`` trace and an in-memory buffer while a session
  records, and a :class:`StepProfiler` hook that brackets N engine steps
  with ``jax.profiler`` start/stop.

The contract the serve tests pin: metrics/tracing on vs off produces
IDENTICAL tokens and IDENTICAL dispatch counts — the subsystem observes
the engine, it never participates in it (tests/test_obs_engine.py).
"""
from repro.obs import compile_events
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_REGISTRY, NullRegistry,
                               parse_prometheus)
from repro.obs.trace import (EventTrace, SpanRecord, StepProfiler,
                             clear_spans, profile_options, record_spans,
                             recorded_spans, recording, span, step_span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL_REGISTRY", "parse_prometheus", "EventTrace", "StepProfiler",
    "SpanRecord", "span", "step_span", "recording", "record_spans",
    "recorded_spans", "clear_spans", "profile_options", "compile_events",
]
