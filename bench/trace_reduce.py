"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* The traced window is the span of the harness's ``bench.step`` host
  annotations (one around every ``engine.step()`` call of the trace).
* Busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), clipped
  to the window and averaged over the devices.
* Kernel time is the summed device duration of the operations that
  ``bench/kernel_names.py`` attributes to a kernel, from their HLO text,
  string stats and the module (``XLA Modules`` line) that ran them.
* The breakdown lists the device operations that took the most time, and
  the longest idle gaps, each named by the harness annotation that
  covered its midpoint on the host (``bench.step``: inside the engine's
  ``step()``; ``bench.harness``: the driver's own bookkeeping;
  ``bench.generate``: submitting requests; ``none``: outside all).
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from bench.kernel_names import kernel_of

HOST_MARKS = ("bench.step", "bench.harness", "bench.generate")


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    kernels: Dict[str, List[float]] = field(default_factory=dict)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _event_label(ev) -> str:
    """An event's name and its string-valued stats (a Pallas call's
    kernel name may sit in either)."""
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def _ops_line(plane):
    lines = list(plane.lines)
    for ln in lines:
        if ln.name == "XLA Ops":
            return [ln]
    return [ln for ln in lines
            if "Module" not in ln.name and "Step" not in ln.name]


def _modules(plane) -> List[Tuple[int, int, str]]:
    """(start, end, name) of every executable run on a device plane."""
    return [(int(e.start_ns), int(e.end_ns), e.name)
            for ln in plane.lines if ln.name == "XLA Modules"
            for e in ln.events]


def _module_at(mods: List[Tuple[int, int, str]], t: int) -> str:
    for a, b, name in mods:
        if a <= t < b:
            return name
    return ""


def load(path: str):
    """A trace from its ``.xplane.pb`` file, gzipped or not."""
    import gzip

    import jax
    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    return jax.profiler.ProfileData.from_file(path)


def reduce_trace(path: str, top: int = 10) -> Reduced:
    return reduce_profile(load(path), top)


def reduce_profile(pd, top: int = 10) -> Reduced:
    """The reduction of a loaded trace (``jax.profiler.ProfileData``)."""
    marks: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in HOST_MARKS:
                        marks.append((int(ev.start_ns), int(ev.end_ns),
                                      ev.name))
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    steps = [(a, b) for a, b, n in marks if n == "bench.step"]
    if not steps or not devices:
        raise ValueError("no harness step annotations or no TPU device "
                         "plane in the trace")
    w0, w1 = min(a for a, _ in steps), max(b for _, b in steps)

    busy_total = 0.0
    kernels: Dict[str, List[float]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for plane in devices:
        ivals = []
        mods = _modules(plane)
        for ln in _ops_line(plane):
            for ev in ln.events:
                a, b = int(ev.start_ns), int(ev.end_ns)
                if b <= w0 or a >= w1:
                    continue
                ivals.append((max(a, w0), min(b, w1)))
                dur = ev.duration_ns * 1e-9
                op_time[ev.name] += dur
                kind = kernel_of(_module_at(mods, a) + " "
                                 + _event_label(ev))
                if kind is not None:
                    kernels[kind].append(dur)
        merged = _union(ivals)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) // 2
                covering = [(m1 - m0, n) for m0, m1, n in marks
                            if m0 <= mid <= m1]
                label = min(covering)[1] if covering else "none"
                gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total / len(devices), n_devices=len(devices),
                   kernels=dict(kernels), device_ops=ops[:top],
                   idle_gaps=gaps[:top])


def describe(path: str, per_line: int = 5) -> dict:
    """Planes, lines and a few events with their stats: for reading a
    trace by hand."""
    pd = load(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for ln in plane.lines:
            evs = list(ln.events)
            lines[ln.name] = {
                "n": len(evs),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "duration_ns": e.duration_ns,
                           "stats": [(k, str(v)[:200]) for k, v in e.stats]}
                          for e in evs[:per_line]]}
        out[plane.name] = lines
    return out
