"""Cut a small excerpt out of a profiler trace, for the reduction's test.

    python3 bench/tests/excerpt_trace.py <trace.xplane.pb> <out.pbtxt.gz> \\
        [--steps 3]

The excerpt keeps the harness's host annotations (``bench.*``) of the
first ``--steps`` engine steps of the trace and every event of each TPU
plane's ``XLA Ops`` and ``XLA Modules`` lines that overlaps them, with
its string stats, as a gzipped text proto that
``jax.profiler.ProfileData.from_text_proto`` reads.
"""
from __future__ import annotations

import argparse
import gzip
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from bench.trace_reduce import HOST_MARKS, load  # noqa: E402


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _plane(pid: int, name: str, t0: int, lines) -> str:
    """One plane; ``lines``: (line name, events), each event (name,
    start_ns, dur_ns, [(stat, str)])."""
    names, stats = {}, {}
    out = [f"planes {{\n  id: {pid}\n  name: {_q(name)}"]
    for lid, (line, events) in enumerate(lines, 1):
        out.append(f"  lines {{\n    id: {lid}\n    name: {_q(line)}\n"
                   f"    timestamp_ns: {t0}")
        for nm, a, d, st in events:
            mid = names.setdefault(nm, len(names) + 1)
            parts = [f"metadata_id: {mid} offset_ps: {(a - t0) * 1000} "
                     f"duration_ps: {d * 1000}"]
            for k, v in st:
                sid = stats.setdefault(k, len(stats) + 1)
                parts.append(
                    f"stats {{ metadata_id: {sid} str_value: {_q(v)} }}")
            out.append("    events { " + " ".join(parts) + " }")
        out.append("  }")
    out += [f"  event_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
            for n, i in names.items()]
    out += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
            for n, i in stats.items()]
    out.append("}")
    return "\n".join(out)


def excerpt(path: str, steps: int) -> str:
    pd = load(path)
    marks = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                marks += [(int(e.start_ns), int(e.duration_ns), e.name)
                          for e in ln.events if e.name in HOST_MARKS]
    marks.sort()
    step_marks = [m for m in marks if m[2] == "bench.step"][:steps]
    w0 = step_marks[0][0]
    w1 = max(a + d for a, d, _ in step_marks)
    host = [(n, a, d, []) for a, d, n in marks if w0 <= a and a + d <= w1]
    planes = [_plane(1, "/host:CPU", w0, [("python3", host)])]
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = []
        for ln in plane.lines:
            if ln.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = []
            for e in ln.events:
                a, d = int(e.start_ns), int(e.duration_ns)
                if a + d > w0 and a < w1:
                    evs.append((e.name, a, d,
                                [(k, v) for k, v in e.stats
                                 if isinstance(v, str)]))
            lines.append((ln.name, sorted(evs, key=lambda x: x[1])))
        planes.append(_plane(len(planes) + 1, plane.name, w0, lines))
    return "\n".join(planes) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--steps", type=int, default=3)
    a = ap.parse_args(argv)
    with gzip.open(a.out, "wt") as fh:
        fh.write(excerpt(a.trace, a.steps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
