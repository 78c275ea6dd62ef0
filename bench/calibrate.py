"""The readings that a cell's correctness limits are set from, in one
process: the program on a dozen seeds or more, and its control.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11 12 ... --control-seeds 21 22 23

One engine is built and warmed once; each seed then gets its own weights
(``bench.model.set_weights``: ``P_QK`` does not follow the seed, so every
compiled step is reused) and one pre-roll, window and drain of the cell's
own traffic, and its sampled requests are scored by the float32
reference exactly as a run scores them.  The control, on the control
seeds: the reference computed with float8 e4m3 operands, the precision
below the configuration's bf16, put in the program's place: at each
position of the program's own prompts and served tokens, the float32 gap
of the token it puts first.

Every reading is printed as one JSON line; the last line sums them up.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]


def drain(eng) -> None:
    while not eng.done:
        eng.step()


def serve_seeds(cell, seeds, seconds):
    """Serve each seed's traffic with its own weights on one engine;
    returns {seed: sampled (prompt, served, k) requests}."""
    from bench import run
    from bench.model import build_engine, set_weights
    from repro.obs import EventTrace
    events = EventTrace(keep=True)
    eng, largs = build_engine(cell.config, seeds[0], events)
    run.warm(eng, largs, cell.traffic)
    out = {}
    for i, seed in enumerate(seeds):
        if i:
            set_weights(eng, cell.config, seed)
        driver = run.serve(eng, events, cell, seed, seconds)
        out[seed] = run.served(driver.record, seed, cell.traffic)
        driver.eng = None
        drain(eng)
    del eng
    gc.collect()
    return out


def main(argv=None, *, root=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    opts = ap.parse_args(argv)
    from bench import check, run
    cell, _ = run.start(opts.workload, root or run.ROOT,
                        require_chip)
    conf = cell.config

    def report(kind, seed, reqs, control=""):
        nums = check.numbers(run.reference_gaps(conf, seed, reqs, control))
        line = {"kind": kind, "seed": seed, "requests": len(reqs),
                "served_tokens": sum(len(r[1]) for r in reqs), **nums}
        print(json.dumps(line), flush=True)
        return nums

    program = serve_seeds(cell, opts.seeds, opts.seconds)
    summary = {"program": [report("program", s, r)
                           for s, r in program.items()]}
    summary["reference_fp8"] = [
        report("reference_fp8", s, program[s], control="fp8")
        for s in opts.control_seeds if s in program]
    out = {}
    for kind, rows in summary.items():
        for name in ("widest_gap", "mean_gap"):
            vals = [r[name] for r in rows if name in r]
            if vals:
                out[f"{kind}.{name}"] = {"min": min(vals), "max": max(vals),
                                         "n": len(vals)}
    print(json.dumps({"summary": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
