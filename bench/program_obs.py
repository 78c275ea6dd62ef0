"""Per-layer metrics read from the program's own spans and counters.

The serve engine names each host phase of a step with a span
(``repro.obs.trace``: ``engine.resolve``, ``engine.dispatch.decode``, ...)
and keeps them in an in-memory buffer while a profiler session records,
which in a ``--trace 1`` run is the traced window.  ``repro.obs.
compile_events`` keeps a timeline of set-up's tracing, lowering, compile
and cache-load seconds, each entry stamped with the program clock when it
was received, and sums it by phase (``phase_seconds``).  Both use
``time.perf_counter``, the clock ``bench/serve.py`` times each ``step()``
call with.

Each reader returns None, and raises nothing, where the program has no
such span or timeline (a program from before they existed) or the run
has nothing for it to read.  ``spans`` is for tests; by default the
readers take the program's own.
"""
from __future__ import annotations

import statistics
from collections import defaultdict


def program_spans():
    """The program's recorded spans, or None where it keeps none."""
    try:
        from repro.obs.trace import recorded_spans
    except ImportError:
        return None
    return recorded_spans()


def program_phase_seconds():
    """The program's sum of its compile-phase timeline by phase and
    receipt time, or None where it keeps no timeline."""
    try:
        from repro.obs.compile_events import phase_seconds
    except ImportError:
        return None
    return phase_seconds


def _by_step(ctx, spans):
    steps = {s.step for s in ctx.traced_steps}
    out = defaultdict(list)
    for sp in spans:
        if sp.step in steps:
            out[sp.step].append(sp)
    return out


def host_gap_ms(ctx, spans=None):
    """Median over the traced steps of the host time from the end of the
    blocking token wait in ``engine.resolve`` (the start of its
    ``engine.sample`` child; the resolve's end where it has none) to the
    start of the step's first ``engine.dispatch.*``.  Once the previous
    decode's tokens are on the host the device queue is empty, so in this
    time the device waits on the host: sampling, applying, admission and
    building the next dispatch (temperature sampling runs tiny eager ops
    on the device, one lane at a time).  Steps with no resolve or no
    dispatch after it are left out."""
    spans = program_spans() if spans is None else spans
    if not spans:
        return None
    gaps = []
    for group in _by_step(ctx, spans).values():
        sampled = {sp.parent: sp.t0_ns for sp in group
                   if sp.name == "engine.sample"}
        waited = [sampled.get(sp.id, sp.t1_ns) for sp in group
                  if sp.name == "engine.resolve"]
        if not waited:
            continue
        t = min(waited)
        starts = [sp.t0_ns for sp in group
                  if sp.name.startswith("engine.dispatch.") and sp.t0_ns >= t]
        if starts:
            gaps.append((min(starts) - t) * 1e-6)
    return statistics.median(gaps) if gaps else None


def decode_live_share(ctx, spans=None):
    """Share of the paged decode kernel's (slot, page) grid cells that
    hold a decoding lane's winnowed tokens: the sum of ``live_pages`` over
    the sum of ``grid_pages`` of the traced steps' ``engine.dispatch.
    decode`` spans."""
    spans = program_spans() if spans is None else spans
    if not spans:
        return None
    live = grid = 0
    for group in _by_step(ctx, spans).values():
        for sp in group:
            st = sp.stats or {}
            if sp.name == "engine.dispatch.decode" and "grid_pages" in st:
                live += st["live_pages"]
                grid += st["grid_pages"]
    return 100.0 * live / grid if grid else None


def setup_seconds(ctx, phases):
    """Seconds of the compile-phase timeline's ``phases`` received before
    the window opened (``record.open_t``, the end of ``setup_s``); what
    runs after it, such as the reference check, is left out."""
    phase_seconds = program_phase_seconds()
    rec = ctx.record
    if phase_seconds is None or rec is None or rec.open_t is None:
        return None
    sec = phase_seconds(phases, before_ns=rec.open_t * 1e9)
    return float(sec) if sec > 0 else None


def setup_lower_s(ctx):
    """Set-up seconds of tracing and lowering to MLIR."""
    return setup_seconds(ctx, ("trace", "lower"))


def setup_compile_s(ctx):
    """Set-up seconds of backend compiles and persistent-cache loads."""
    return setup_seconds(ctx, ("compile", "cache_load"))
