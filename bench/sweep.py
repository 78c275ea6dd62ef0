"""Find the knee of an open-loop cell: the highest arrival rate the engine
sustains without a growing queue.

    python3 bench/sweep.py --workload yi-9b.long-prompt --seed 5 \\
        --seconds 20 --rates 1 2 3 4

One set-up; then, for each rate, the cell's traffic at that rate (its
pre-roll, a window of ``--seconds``, its drain) on the same engine, and
one JSON line: requests that arrived in the window and completed, the
queue of arrived but unadmitted requests when the window opened and
closed and its slope over the window (least squares over every step,
requests per second), and the latency tails.  The knee is the highest
swept rate whose queue does not grow over the window; the cell runs at
about four fifths of it, written into its traffic file as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]


def queue_at(rec, t: float) -> int:
    return sum(1 for lg in rec.logs.values()
               if lg.due <= t and (lg.admit is None or lg.admit > t))


def reading(rec, rate: float) -> dict:
    from bench import endtoend
    steps = [s.t0 for s in rec.steps.values()
             if rec.open_t <= s.t0 <= rec.close_t]
    q = [queue_at(rec, t) for t in steps]
    slope = float(np.polyfit(steps, q, 1)[0]) if len(steps) > 2 else 0.0
    smp = endtoend.samples(rec)
    arr = endtoend.window_arrivals(rec)
    pct = (lambda xs, p: float(np.percentile(xs, p)) if xs else None)
    return {"rate_per_s": rate, "arrived": len(arr),
            "completed": sum(lg.done is not None for lg in arr),
            "queue_open": queue_at(rec, rec.open_t),
            "queue_close": queue_at(rec, rec.close_t),
            "queue_slope_per_s": slope,
            "ttft_p50_ms": pct(smp["ttft_ms"], 50),
            "ttft_p95_ms": pct(smp["ttft_ms"], 95),
            "itl_p50_ms": pct(smp["itl_ms"], 50),
            "itl_p95_ms": pct(smp["itl_ms"], 95),
            "tok_per_s": endtoend.compute(rec, 0.0)["tok_per_s"]}


def main(argv=None, *, root=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    opts = ap.parse_args(argv)
    from bench import run
    from bench.calibrate import drain
    from bench.model import build_engine
    from repro.obs import EventTrace
    cell, _ = run.start(opts.workload, root or run.ROOT,
                        require_chip)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{opts.workload} is not an open-loop cell")
    events = EventTrace(keep=True)
    eng, largs = build_engine(cell.config, opts.seed, events)
    run.warm(eng, largs, cell.traffic)
    for rate in opts.rates:
        traffic = dict(cell.traffic)
        traffic["arrivals"] = dict(traffic["arrivals"], rate_per_s=rate)
        driver = run.serve(eng, events, cell, opts.seed, opts.seconds,
                           traffic=traffic)
        driver.eng = None
        drain(eng)
        print(json.dumps(reading(driver.record, rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
