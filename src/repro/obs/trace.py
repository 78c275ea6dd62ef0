"""Structured JSONL event trace for the serving stack, host spans on the
profiler's clock, and a ``jax.profiler`` step hook.

Every event is one JSON object per line::

    {"event": "admit", "step": 12, "t_ns": 81234567890, "uid": "req3",
     "slot": 1, "shard": 0, "prompt_len": 44, "k": 8, "mode": "chunked"}

``step`` is the ENGINE step at emit time, the deterministic scheduler
clock every serve metric is indexed by; ``t_ns`` is the program clock
(``time.perf_counter_ns()``) when the event was emitted, stamped in
:meth:`EventTrace.emit` and nowhere else.

Spans (:func:`span`, :func:`step_span`) name host phases.  While a
``jax.profiler`` session records, each span is a
``jax.profiler.TraceAnnotation`` (so it lands in the profiler's host plane,
on the device ops' clock) and also goes into a bounded in-memory buffer
(:func:`recorded_spans`) timed on the program clock.  Outside a session a
span costs one enabled-check and allocates nothing; :func:`record_spans`
turns the buffer on without a profiler.  A step span's annotation carries
``host_ns``, the program clock at its start: the profiler reports event
times relative to its session start, so ``host_ns - start_ns`` of any step
annotation is the one offset that maps every in-memory span and every
event's ``t_ns`` onto the trace.

The serve-engine event vocabulary (see docs/observability.md for the full
field schema and the span table): ``submit``, ``admit``,
``admission_hold``, ``chunk_dispatch``, ``prefill_complete``,
``first_token``, ``token``, ``decode_dispatch``, ``retire``, ``page_map``,
``page_free``, ``pool_grow``, ``pool_exhausted``, ``span``,
``profile_start``, ``profile_stop``.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

try:
    from jax._src.lib import _profiler as _xprof
    _is_enabled = _xprof.TraceMe.is_enabled
except (ImportError, AttributeError):     # a JAX build without the hook
    def _is_enabled() -> bool:
        return False


class EventTrace:
    """JSONL event sink.  ``path`` appends one JSON line per event to a
    file (line-buffered, so a crash loses at most the current line);
    ``keep=True`` (the default when no path is given) also retains events
    in-memory on ``.events`` for tests and in-process consumers."""

    def __init__(self, path: Optional[str] = None,
                 keep: Optional[bool] = None):
        self.path = path
        self._fh = open(path, "w", buffering=1) if path else None
        self.keep = (path is None) if keep is None else keep
        self.events: List[Dict[str, Any]] = []

    def emit(self, event: str, step: int, **fields: Any) -> None:
        rec = {"event": event, "step": int(step),
               "t_ns": time.perf_counter_ns(), **fields}
        if self.keep:
            self.events.append(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec, sort_keys=True) + "\n")

    def select(self, event: str, **match: Any) -> List[Dict[str, Any]]:
        """In-memory events of one type whose fields match ``match``."""
        return [e for e in self.events if e["event"] == event
                and all(e.get(k) == v for k, v in match.items())]

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "EventTrace":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @staticmethod
    def read(path: str) -> List[Dict[str, Any]]:
        """Parse a JSONL trace file back into event dicts."""
        with open(path) as fh:
            return [json.loads(line) for line in fh if line.strip()]


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

# in-memory spans kept at most (the oldest drop first); a traced window of a
# few seconds holds a few hundred
SPAN_BUFFER = 1 << 16


class SpanRecord(NamedTuple):
    """One finished span on the program clock (``perf_counter_ns``).
    ``parent`` is the ``id`` of the span that enclosed it, -1 at the top."""
    name: str
    step: int
    uid: Any
    id: int
    parent: int
    t0_ns: int
    t1_ns: int
    stats: Optional[Dict[str, Any]]


_buffer: "deque[SpanRecord]" = deque(maxlen=SPAN_BUFFER)
_open: List[int] = []                  # ids of the spans now open
_next_id = 0
_forced = False


def record_spans(on: bool = True) -> None:
    """Record spans into the in-memory buffer even with no profiler
    session (tests, operators reading :func:`recorded_spans`)."""
    global _forced
    _forced = bool(on)


def recording() -> bool:
    """True while spans are recorded: a profiler session is active, or
    :func:`record_spans` turned recording on."""
    return _forced or _is_enabled()


def recorded_spans() -> List[SpanRecord]:
    """The buffered spans, in the order they ended."""
    return list(_buffer)


def clear_spans() -> None:
    _buffer.clear()


class _Span:
    """A span that is timed; recorded and annotated only when ``on``."""

    __slots__ = ("name", "step", "uid", "stats", "trace", "on", "marker",
                 "id", "parent", "t0_ns", "t1_ns", "_ann")

    def __init__(self, name, step, uid, stats, trace, on, marker):
        self.name, self.step, self.uid, self.stats = name, step, uid, stats
        self.trace, self.on, self.marker = trace, on, marker
        self._ann = None

    def __enter__(self) -> "_Span":
        global _next_id
        if self.on:
            self.id = _next_id
            _next_id += 1
            self.parent = _open[-1] if _open else -1
            _open.append(self.id)
        if self.on and _is_enabled():
            import jax
            kw = dict(self.stats or ())
            if self.uid is not None:
                kw["uid"] = self.uid
            if self.marker:
                self._ann = jax.profiler.StepTraceAnnotation(
                    self.name, step_num=self.step, **kw)
            else:
                self._ann = jax.profiler.TraceAnnotation(self.name, **kw)
            self._ann.__enter__()
        # the clock is read inside the annotation, so the two agree to
        # within microseconds
        self.t0_ns = time.perf_counter_ns()
        if self.marker and self._ann is not None:
            self._ann.set_metadata(host_ns=self.t0_ns)
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.on:
            _open.pop()
            _buffer.append(SpanRecord(self.name, self.step, self.uid,
                                      self.id, self.parent, self.t0_ns,
                                      self.t1_ns, self.stats))
        if self.trace is not None:
            fields = dict(self.stats or ())
            if self.uid is not None:
                fields["uid"] = self.uid
            self.trace.emit("span", step=self.step, name=self.name,
                            wall_ms=self.ms, **fields)

    @property
    def ms(self) -> float:
        """Wall time of the finished span, in milliseconds."""
        return (self.t1_ns - self.t0_ns) * 1e-6


class _NoSpan:
    """What :func:`span` returns when nothing records: one shared object."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NO_SPAN = _NoSpan()


def span(name: str, step: int = -1, uid: Any = None,
         stats: Optional[Dict[str, Any]] = None, *,
         trace: Optional[EventTrace] = None, timed: bool = False):
    """A host span around a ``with`` block: ``name``, the engine ``step``,
    the request ``uid`` where it concerns one request, and ``stats``
    (counters; the caller builds them only while :func:`recording`).

    Recorded (buffer, and profiler annotation under a session) only while
    :func:`recording`.  ``timed=True`` always reads the clock, so a caller
    can feed the span's ``ms`` to a histogram without reading it twice;
    ``trace`` also emits a ``span`` event with ``wall_ms`` to that sink.
    Otherwise, with nothing recording, the call returns one shared no-op
    object.  The clock is read strictly OUTSIDE jitted code: a span around
    an async dispatch measures host enqueue time, not device time."""
    on = _forced or _is_enabled()
    if not (on or timed or trace is not None):
        return _NO_SPAN
    return _Span(name, step, uid, stats, trace, on, False)


def step_span(name: str, step: int):
    """The span of one engine step: under a profiler session a
    ``jax.profiler.StepTraceAnnotation`` with ``step_num`` and ``host_ns``
    (the program clock at its start)."""
    on = _forced or _is_enabled()
    if not on:
        return _NO_SPAN
    return _Span(name, step, None, None, None, on, True)


def profile_options():
    """The profiler options the serve stack traces with, the benchmark's:
    Python tracer off (it would time every Python call), host tracer at
    level 1 (the spans).  HLO protos stay on, so a trace viewer can map
    device ops to the ``jax.named_scope`` names in their metadata."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class StepProfiler:
    """Bracket N engine steps with ``jax.profiler`` start/stop.

    The engine calls :meth:`step_start` / :meth:`step_end` around every
    scheduler step; the first ``step_start`` opens the trace, and the
    N-th ``step_end`` closes it — one profile per instance, covering
    exactly ``n_steps`` engine steps (admission + chunk dispatch + decode
    dispatch included), with :func:`profile_options`.  View with
    TensorBoard or Perfetto against ``logdir``.  ``start``/``stop`` are
    injectable for tests."""

    def __init__(self, logdir: str, n_steps: int,
                 trace: Optional[EventTrace] = None,
                 start: Optional[Callable[[str], Any]] = None,
                 stop: Optional[Callable[[], Any]] = None):
        if n_steps < 1:
            raise ValueError(f"n_steps={n_steps} must be >= 1")
        self.logdir = logdir
        self.n_steps = n_steps
        self.remaining = n_steps
        self.active = False
        self.done = False
        self._trace = trace
        self._start = start
        self._stop = stop

    def step_start(self, step: int = 0) -> None:
        if self.done or self.active:
            return
        if self._start is None:
            import jax

            def start(logdir):
                jax.profiler.start_trace(logdir,
                                         profiler_options=profile_options())
            self._start = start
            self._stop = self._stop or jax.profiler.stop_trace
        self._start(self.logdir)
        self.active = True
        if self._trace is not None:
            self._trace.emit("profile_start", step=step,
                             logdir=self.logdir, n_steps=self.n_steps)

    def step_end(self, step: int = 0) -> None:
        if not self.active:
            return
        self.remaining -= 1
        if self.remaining > 0:
            return
        self._stop()
        self.active = False
        self.done = True
        if self._trace is not None:
            self._trace.emit("profile_stop", step=step, logdir=self.logdir)
