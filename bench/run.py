"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in ``BENCHMARK.json`` and its configuration, traffic
mix, limits and per-layer readers in their own files (``bench/cell.py``),
makes the weights and the traffic from ``--seed``, builds the engine
through the launcher's steps (``bench/model.py``), warms the cell's
executable family, runs the mix's pre-roll, measures for ``--seconds``
(``bench/serve.py``), then checks what was served against the plain
reference (``bench/check.py``).  With ``--trace 1`` it also captures a
profiler trace of a few seconds inside the window and reports the cell's
per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with a trace), then ``checks``, each compared number beside its limit;
the comparisons also end standard error.  With no TPU, or fewer chips
than the cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache lives in ``bench/.jax_cache`` inside
the checkout (a fixed path: it is part of the cache's key), whatever the
environment names, with no size limit, so only a cell's first run in a
checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChip(RuntimeError):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    return ap.parse_args(argv)


def cache_dir(root: Path = ROOT) -> str:
    return str(Path(root) / "bench" / ".jax_cache")


def enable_cache(path: str) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def dir_bytes(path: str) -> int:
    p = Path(path)
    return sum(f.stat().st_size for f in p.rglob("*") if f.is_file()) \
        if p.exists() else 0


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and d.platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {d.platform!r}); this "
                     "benchmark runs only on the chip")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def pct(xs, q):
    import numpy as np
    return float(np.percentile(xs, q)) if len(xs) else None


def say(obj) -> None:
    print(json.dumps(obj), flush=True)


def start(workload: str, root: Path, require_chip: bool):
    """The cell, the device, the compilation cache and the compile
    counter: what every process that drives a cell does first."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.cell import load_cell
    from repro.obs import compile_events
    cell = load_cell(workload, root)
    dev = device_info(cell.chips, require_chip)
    enable_cache(cache_dir(root))
    compile_events.install()
    return cell, dev


def warm(eng, largs, traffic):
    """Compile the executables this cell's traffic can reach: the whole
    family, trimmed to the mix's longest prompt."""
    cap = min(largs.max_prompt_len, int(traffic["prompt_tokens"]["max"]))
    return eng.warmup(max_prompt_len=cap)


def serve(eng, events, cell, seed: int, seconds: float, *, traffic=None,
          profile_dir=None, trace_s: float = 0.0, trace_offset: float = 0.0):
    """One pre-roll, window and drain of the cell's traffic (or
    ``traffic``) on a built engine.  Returns the driver, whose ``run``
    record is ``driver.record``."""
    from bench.model import model_fields
    from bench.serve import Driver
    from bench.traffic import make_plan
    traffic = traffic or cell.traffic
    sv = cell.config["serve"]
    horizon = float(traffic["preroll"].get("seconds", 0)) + seconds
    plan = make_plan(traffic, seed, model_fields(cell.config)["vocab_size"],
                     n_slots=sv["n_slots"], horizon_s=horizon)
    events.events = []
    driver = Driver(eng, events, plan, sv["prefill_chunk"],
                    profile_dir=profile_dir, trace_offset_s=trace_offset,
                    trace_s=trace_s)

    def snapshot():
        live = sum(lg.prefilled + len(lg.tokens)
                   for lg in driver.logs.values()
                   if lg.admit is not None and lg.done is None)
        return {"cache_report": eng.cache_report(), "live_tokens": live}

    driver.on_close = snapshot
    driver.record = driver.run(traffic["preroll"], seconds,
                               float(traffic.get("drain_seconds", 0)))
    return driver


def served(rec, seed: int, traffic) -> list:
    """The sampled finished requests as (prompt, served tokens, k)."""
    from bench import check
    return [(lg.req.tokens, lg.tokens, lg.req.k)
            for lg in check.sample(rec.logs, seed, rec.open_t,
                                   traffic["check"])]


def reference_gaps(conf, seed: int, reqs, control: str = ""):
    """Per request, the float32 reference's gap of each served token; with
    ``control="fp8"``, of the token that the reference computed in that
    precision puts first instead."""
    from bench import check
    from bench.model import (make_projections, make_weights, model_fields,
                             reference_module)
    ref, m, sv = reference_module(conf), model_fields(conf), conf["serve"]
    W, P = make_weights(conf, seed), make_projections(conf, seed)
    scorer = ref.make_scorer(m, conf["swan"], sv["prefill_chunk"])
    if control:
        low = ref.make_scorer(m, conf["swan"], sv["prefill_chunk"], control)
        return check.control_gaps(scorer, low, W, P, reqs, sv["max_seq"])
    return check.served_gaps(scorer, W, P, reqs, sv["max_seq"])


def run(argv=None, *, root: Path = ROOT, require_chip: bool = True,
        use_pallas=None) -> dict:
    args = parse_args(argv)
    cell, dev = start(args.workload, root, require_chip)
    import jax
    from bench import check, endtoend
    from bench.costs import Shapes
    from bench.model import build_engine, model_fields
    from bench.peaks import PEAKS, peaks
    from bench.trace_reduce import describe, reduce_trace
    from repro.obs import EventTrace, compile_events

    conf, traffic = cell.config, cell.traffic
    m = model_fields(conf)
    cdir = cache_dir(root)
    cache_bytes0 = dir_bytes(cdir)

    # -- set-up: weights, engine, warmup ---------------------------------
    events = EventTrace(keep=True)
    eng, largs = build_engine(conf, args.seed, events, use_pallas=use_pallas)
    pc0 = compile_events.persistent_cache()
    warmed = warm(eng, largs, traffic)
    pc1 = compile_events.persistent_cache()

    tdir = None
    trace_s = offset = 0.0
    if args.trace:
        tdir = args.keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        trace_s = traffic["trace"]["seconds"]
        offset = traffic["trace"]["offset_share"] * args.seconds
    driver = serve(eng, events, cell, args.seed, args.seconds,
                   profile_dir=tdir, trace_s=trace_s, trace_offset=offset)
    rec, loop = driver.record, driver.plan.loop
    setup_s = rec.open_t - T_START
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    attempted, failed = endtoend.attempted_failed(rec, loop)
    e2e = endtoend.compute(rec, setup_s)
    smp = endtoend.samples(rec)
    late = [(lg.sent - lg.due) * 1e3 for lg in endtoend.window_arrivals(rec)
            ] if loop == "open" else []
    say({"setup": {"seconds": setup_s,
                   "warmup_s": warmed["warmup_ms"] / 1e3,
                   "executables": warmed["census"]["total"],
                   "warmup_compiles": warmed["compiles"],
                   "cache_hits": pc1["hits"] - pc0["hits"],
                   "cache_writes": pc1["writes"] - pc0["writes"],
                   "cache_dir": cdir, "cache_bytes_before": cache_bytes0,
                   "cache_bytes_after": dir_bytes(cdir)},
         "window": {"seconds": rec.close_t - rec.open_t,
                    "steps": rec.window_step_range[1]
                    - rec.window_step_range[0],
                    "tokens": endtoend.tokens_in_window(rec),
                    "compiles_in_window": rec.compiles_in_window},
         "requests": {"sent": len(rec.logs),
                      "completed": sum(lg.done is not None
                                       for lg in rec.logs.values()),
                      "attempted": attempted, "failed": failed},
         "samples": {"ttft": len(smp["ttft_ms"]), "itl": len(smp["itl_ms"])},
         "ttft_ms": {"p50": pct(smp["ttft_ms"], 50),
                     "p95": pct(smp["ttft_ms"], 95)},
         "itl_ms": {"p50": pct(smp["itl_ms"], 50),
                    "p95": pct(smp["itl_ms"], 95)},
         "generator_late_ms": {"p50": pct(late, 50), "p99": pct(late, 99)},
         "memory_peak_bytes": dev["memory_peak_bytes"]})

    # -- free the program's state, then the reference --------------------
    snap = driver.closed_snapshot
    reqs = served(rec, args.seed, traffic)
    driver.eng = None
    del eng, driver
    gc.collect()
    nums = check.numbers(reference_gaps(conf, args.seed, reqs))
    checks = check.judge(nums, cell.limits)
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    say({"check": {"requests": len(reqs),
                   "served_tokens": sum(len(r[1]) for r in reqs),
                   "prompt_tokens": [len(r[0]) for r in reqs],
                   "k": [r[2] for r in reqs], **nums}})

    # -- metrics -----------------------------------------------------------
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        xp = sorted(Path(tdir).glob("plugins/profile/*/*.xplane.pb"))
        red = None
        if xp and args.keep_trace:
            with open(Path(tdir) / "describe.json", "w") as fh:
                json.dump(describe(str(xp[-1])), fh, indent=1)
        try:
            red = reduce_trace(str(xp[-1])) if xp else None
        except ValueError as e:     # nothing of the device in the trace
            print(f"bench: trace not reduced: {e}", file=sys.stderr)
        span = rec.trace_span
        ctx = SimpleNamespace(
            trace=red, record=rec, snapshot=snap,
            traced_steps=[s for s in rec.steps.values()
                          if span and span[0] <= s.t0 and s.t1 <= span[1]],
            shapes=Shapes.from_fields(m, conf["swan"]),
            peaks=peaks(dev["kind"]) if dev["kind"] in PEAKS else None)
        metrics = {}
        for mt in cell.per_layer:
            v = cell.readers[mt["name"]](ctx) if ctx.peaks else None
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
        if red is not None:
            dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in red.device_ops],
                "idle_gaps": [list(x) for x in red.idle_gaps]}
        if not args.keep_trace:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        metrics = {mt["name"]: {"value": e2e[mt["name"]], "unit": mt["unit"]}
                   for mt in cell.end_to_end if mt["name"] in e2e}
    result["metrics"] = metrics
    result["device"] = dev
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    say(result)
    return result


def main(argv=None) -> int:
    try:
        run(argv)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
