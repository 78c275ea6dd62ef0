"""CPU identity harness for the mesh-sharded serve engine: token identity
and dispatch-count independence from shard count, on 8 simulated host
devices.  It measures no device, and refuses to run where a TPU is
present (four real chips are ``chip_smoke.py --four-chips``).

The sharded engine partitions the batched serve state — slab/ring leaves,
per-sequence pos/k, and the paged pool's page axis — over a ``data`` mesh
axis, with a shard-local slot scheduler on the host (admission, budgeted
round-robin prefill and retirement all decide per shard).  The property
this benchmark gates is the one that makes the design scale: the HOST
issues exactly ONE packed chunk dispatch and ONE decode dispatch per
engine step no matter how many shards the mesh has (the shard fan-out
lives inside shard_map, not in a host loop), and the sharded schedule
never changes a single output token.

Replays the SAME deterministic Poisson trace (mixed prompt lengths, mixed
per-request SWAN k, clustered arrivals, concurrent chunked prefill, paged
pool) through a single-device engine and an 8-way sharded engine on a
simulated host mesh, and gates:

  * sharded tokens == single-device tokens, per request;
  * per-step dispatch count (chunk + decode) identical across shard
    counts, and <= 1 of each per step;
  * the sharded engine drains the trace in the same number of engine
    steps (same decode throughput in scheduler time).

Host wall-clock per step is printed as a CPU number (not gated, and not a
device metric — 8 host devices on one CPU serialize the per-shard
compute).  ``--smoke`` shrinks the trace for CI (run with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
— see .github/workflows/ci.yml).
"""
from __future__ import annotations

import os

# the mesh must exist before jax initialises — force 8 host devices unless
# the caller (CI) already did
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import numpy as np

from benchmarks.common import bench_record, emit, gate, record_metrics
from repro.obs import EventTrace
from repro.configs import SwanConfig, get_smoke_config
from repro.launch.io import make_batch
from repro.launch.mesh import make_serve_mesh
from repro.models import get_model
from repro.runtime.serve_engine import Request, ServeEngine
from repro.runtime.serve_loop import calibrate_swan

DP = 8              # shards on the simulated host mesh
N_SLOTS = 16         # 2 slots per shard
MAX_SEQ = 128
CHUNK = 8
PAGE = 8
BURST_RATE = 2.0     # requests per engine step (Poisson)


def _cfg():
    return get_smoke_config("llama3-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, dtype="float32", param_dtype="float32")


def _trace(cfg, n_requests, gen_tokens):
    """Deterministic Poisson trace: clustered arrivals, mixed prompt
    lengths, mixed per-request k — the full serving feature surface."""
    rng = np.random.default_rng(0)
    arrivals = np.floor(np.cumsum(
        rng.exponential(1.0 / BURST_RATE, n_requests))).astype(int)
    ks = [16, 8, None, 4]
    reqs = []
    for i in range(n_requests):
        plen = [8, 20, 44, 14][i % 4]
        toks = make_batch(cfg, 1, plen, seed=500 + i)["tokens"][0]
        reqs.append(Request(
            uid=f"req{i}", tokens=[int(t) for t in toks],
            max_new_tokens=gen_tokens, k=ks[i % 4],
            arrival_step=int(arrivals[i])))
    return reqs


def _drain(engine, reqs):
    for r in reqs:
        engine.submit(r)
    per_step, durs = [], []
    while not engine.done:
        before = dict(engine.dispatches)
        t0 = time.perf_counter()
        engine.step()
        jax.block_until_ready(engine.state)
        durs.append(time.perf_counter() - t0)
        per_step.append(tuple(engine.dispatches[k] - before[k]
                              for k in ("chunk", "decode")))
    return per_step, np.asarray(durs)


def _run(smoke: bool = False) -> None:
    n_requests, gen_tokens = (10, 5) if smoke else (24, 12)
    cfg = _cfg()
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    pj = calibrate_swan(api, cfg, params, make_batch(cfg, 2, 32, seed=3))
    absorbed = api.absorb(params, cfg, pj)
    swan = SwanConfig(k_max=cfg.d_head, buffer=8, mode="topk")

    stats, tokens = {}, {}
    for mode, mesh in [("single", None), ("sharded", make_serve_mesh(DP))]:
        eng = ServeEngine(cfg, absorbed, swan=swan, projections=pj,
                          max_seq=MAX_SEQ, n_slots=N_SLOTS, paged=True,
                          page_size=PAGE, prefill_chunk=CHUNK,
                          prefill_slots=4, mesh=mesh)
        per_step, durs = _drain(eng, _trace(cfg, n_requests, gen_tokens))
        tokens[mode] = {c.uid: c.tokens for c in eng.completions}
        stats[mode] = {
            "dp": eng.dp,
            "engine_steps": eng.step_count,
            "chunk_dispatches": eng.dispatches["chunk"],
            "decode_dispatches": eng.dispatches["decode"],
            "max_per_step": max(sum(d) for d in per_step),
            "per_step": per_step,
            "step_p50_us": float(np.percentile(durs, 50) * 1e6),
            "step_p99_us": float(np.percentile(durs, 99) * 1e6),
        }
        assert eng.pool.live_pages == 0
        eng.pool.check_consistent()
        record_metrics(eng.metrics, mode)

    # --- acceptance gates ---------------------------------------------------
    one, sh = stats["single"], stats["sharded"]
    gate("shard_counts", sh["dp"] == DP and one["dp"] == 1,
         f"dp={one['dp']}/{sh['dp']}")
    gate("token_identity", tokens["sharded"] == tokens["single"],
         "sharded engine diverged from the single-device engine")
    # the property that scales: per-step dispatch count is INDEPENDENT of
    # shard count — at most one packed chunk + one decode dispatch per
    # step on ANY mesh (the shard fan-out lives inside shard_map, never in
    # a host loop), so 8 shards never issue more per-step work than 1
    gate("one_dispatch_per_step",
         max(one["max_per_step"], sh["max_per_step"]) <= 2,
         "more than one chunk + one decode dispatch in a step")
    gate("no_per_shard_dispatch",
         all(c <= 1 and d <= 1 for c, d in sh["per_step"]),
         "a sharded step issued per-shard dispatches")
    # per-SHARD prefill budgets mean the sharded engine admits bursts at
    # least as fast — never more total dispatches or steps than 1 device
    gate("no_extra_steps", sh["engine_steps"] <= one["engine_steps"],
         "sharding slowed the drain (more engine steps)")
    gate("no_extra_dispatches",
         sh["chunk_dispatches"] + sh["decode_dispatches"]
         <= one["chunk_dispatches"] + one["decode_dispatches"],
         "sharding increased total dispatch count")

    for mode, s in stats.items():
        emit(f"sharded_serve_{mode}",
             s["chunk_dispatches"] + s["decode_dispatches"],
             f"dp={s['dp']};steps={s['engine_steps']};"
             f"chunk={s['chunk_dispatches']};decode={s['decode_dispatches']};"
             f"max_per_step={s['max_per_step']};"
             f"step_p50_us={s['step_p50_us']:.0f};"
             f"step_p99_us={s['step_p99_us']:.0f}")
    emit("sharded_serve_dispatch_ratio",
         (sh["chunk_dispatches"] + sh["decode_dispatches"])
         / max(one["chunk_dispatches"] + one["decode_dispatches"], 1),
         f"dp={DP};slots={N_SLOTS};chunk={CHUNK};page={PAGE};"
         f"burst_rate={BURST_RATE}")

    # --- instrumentation overhead (observability acceptance: < 3% p99) ----
    # identical single-device drains, compile-warmed, with the full stack
    # OFF (null registry, no trace) vs ON (metrics + in-memory trace);
    # also re-proves token identity and dispatch-count identity on/off
    def _mk(instrumented):
        return ServeEngine(cfg, absorbed, swan=swan, projections=pj,
                           max_seq=MAX_SEQ, n_slots=N_SLOTS, paged=True,
                           page_size=PAGE, prefill_chunk=CHUNK,
                           prefill_slots=4, metrics=instrumented,
                           trace=EventTrace() if instrumented else None)

    p99, toks_oo, disp_oo = {}, {}, {}
    for tag in ("off", "on"):
        eng = _mk(tag == "on")
        _drain(eng, _trace(cfg, n_requests, gen_tokens))     # warm compiles
        best = []
        for _ in range(2):
            _, durs = _drain(eng, _trace(cfg, n_requests, gen_tokens))
            best.append(float(np.percentile(durs, 99)))
        p99[tag] = min(best)
        toks_oo[tag] = {c.uid: c.tokens for c in eng.completions}
        disp_oo[tag] = dict(eng.dispatches)
    gate("obs_token_identity", toks_oo["on"] == toks_oo["off"],
         "metrics/tracing changed output tokens")
    gate("obs_dispatch_identity", disp_oo["on"] == disp_oo["off"],
         f"metrics/tracing changed dispatch counts: "
         f"{disp_oo['off']} vs {disp_oo['on']}")
    # 3% relative + 300us absolute slack (absorbs host-timer noise on the
    # tiny smoke model, where one step is only a few ms)
    budget = p99["off"] * 1.03 + 300e-6
    gate("obs_overhead_p99", p99["on"] <= budget,
         f"instrumented p99 {p99['on'] * 1e6:.0f}us exceeds "
         f"{budget * 1e6:.0f}us (off: {p99['off'] * 1e6:.0f}us)")
    emit("sharded_serve_obs_overhead_p99", p99["on"] * 1e6,
         f"off_p99_us={p99['off'] * 1e6:.0f};"
         f"ratio={p99['on'] / max(p99['off'], 1e-12):.3f}")


def run(smoke: bool = False) -> None:
    if any(d.platform == "tpu" for d in jax.devices()):
        raise RuntimeError("bench_sharded_serve is a CPU identity harness; "
                           "this machine has a TPU — run chip_smoke.py "
                           "--four-chips")
    with bench_record("sharded_serve"):
        _run(smoke=smoke)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small trace for CI")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
