"""Serving launcher: calibrate SWAN on a checkpoint (or fresh weights) and
run batched generation.

Lockstep batch (one shared position, the paper's benchmark setting):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --swan --k 8 --buffer 16 --tokens 32

Continuous batching (request queue + slot scheduler, mixed prompt lengths
and per-request SWAN k — see repro.runtime.serve_engine):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --swan --k 8 --buffer 16 --tokens 32 --engine --requests 8 --mixed-k
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import SwanConfig, get_config, get_smoke_config
from repro.launch.io import make_batch
from repro.launch.mesh import make_mesh, make_serve_mesh
from repro.models import get_model, swan_applicable
from repro.obs import EventTrace
from repro.runtime.serve_engine import Request, ServeEngine
from repro.runtime.serve_loop import ServeSession, calibrate_swan


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's command line (``argv=None`` reads ``sys.argv``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the fresh weights (ignored for the "
                         "restored parts of --ckpt-dir)")
    ap.add_argument("--ckpt-dir", help="restore params from a checkpoint")
    ap.add_argument("--swan", action="store_true")
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--buffer", type=int, default=128)
    ap.add_argument("--mode", default="topk", choices=["topk", "truncate"])
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous batching instead of lockstep")
    ap.add_argument("--requests", type=int, default=None,
                    help="engine: number of requests (default: --batch * 2)")
    ap.add_argument("--mixed-k", action="store_true",
                    help="engine: cycle per-request SWAN k overrides")
    ap.add_argument("--paged", action="store_true",
                    help="engine+swan: paged sparse cache — memory follows "
                         "live tokens (repro.core.paged_cache)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged: token positions per page")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="paged: physical pages in the shared pool "
                         "(default: full reservation; smaller over-commits)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="engine: chunked prefill — split prompts into "
                         "power-of-two chunks with bounded prefill work "
                         "per engine step, so long admissions never stall "
                         "decoding (default: monolithic admission)")
    ap.add_argument("--prefill-slots", type=int, default=1,
                    help="engine: batched concurrent prefill — up to P "
                         "in-flight prefills advance per step, packed into "
                         "one multi-slot chunk dispatch (cuts TTFT under "
                         "admission bursts; requires --prefill-chunk)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="engine: per-step prefill token budget "
                         "round-robined across in-flight prefills "
                         "(default: prefill-slots * prefill-chunk)")
    ap.add_argument("--data-parallel", type=int, default=None,
                    help="engine: shard slots, caches and the paged pool "
                         "over a ('data',) mesh of this many devices "
                         "(shard-local slot scheduler; n_slots must "
                         "divide)")
    ap.add_argument("--mesh-shape", default=None,
                    help="engine: explicit mesh as 'AXIS=N,AXIS=N' (must "
                         "include a data axis), e.g. 'data=4' or "
                         "'data=4,model=2' — overrides --data-parallel")
    ap.add_argument("--pool-grow", action="store_true",
                    help="paged: grow the device pool (2x pages, copy, "
                         "extend free lists) when it runs dry instead of "
                         "holding admissions")
    ap.add_argument("--use-pallas", action="store_true", default=None,
                    help="engine: force the Pallas kernel-backed decode/"
                         "chunk attention read (default: auto — compiled "
                         "kernels on TPU, pure-JAX elsewhere; forcing on "
                         "CPU runs the kernels under the interpreter)")
    ap.add_argument("--warmup", action="store_true",
                    help="engine: pre-compile the FULL executable family "
                         "before the first request (repro.runtime.warmup) "
                         "— no mid-serve JIT cliffs; prints the warmup "
                         "report summary")
    ap.add_argument("--max-prompt-len", type=int, default=None,
                    help="engine: trim the warmed prefix family to prompts "
                         "of at most this many tokens (default max-seq); "
                         "longer prompts still serve — their buckets just "
                         "compile lazily")
    ap.add_argument("--async-fetch", action="store_true",
                    help="engine: overlap host scheduling with the decode "
                         "token transfer (copy_to_host_async at dispatch, "
                         "resolved at the next step; token-identical)")
    ap.add_argument("--compilation-cache", action="store_true",
                    help="persist JAX's compilation cache so engine "
                         "restarts reload compiled executables from disk "
                         "instead of recompiling the family: in "
                         "$JAX_COMPILATION_CACHE_DIR where set, else in "
                         ".jax_cache/ at the root of the checkout")
    ap.add_argument("--admission", default="fifo", choices=["fifo", "srf"],
                    help="engine: admission policy — fifo, or srf "
                         "(shortest-remaining-first: bounds TTFT when the "
                         "queue exceeds prefill capacity)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics snapshot here — "
                         "Prometheus text if the path ends in .prom/.txt, "
                         "JSON otherwise (repro.obs.metrics)")
    ap.add_argument("--trace-out", default=None,
                    help="engine: stream a structured JSONL event trace "
                         "(admissions, dispatches, first tokens, page "
                         "map/free, ...) to this path (repro.obs.trace)")
    ap.add_argument("--profile-steps", type=int, default=None,
                    help="engine: capture one jax.profiler trace spanning "
                         "this many engine steps into --profile-dir")
    ap.add_argument("--profile-dir", default="profile",
                    help="engine: jax.profiler trace output directory")
    args = ap.parse_args(argv)
    if args.prefill_chunk and not args.engine:
        raise SystemExit("--prefill-chunk requires --engine")
    if ((args.prefill_slots > 1 or args.prefill_budget is not None)
            and not args.prefill_chunk):
        raise SystemExit("--prefill-slots/--prefill-budget require "
                         "--prefill-chunk")
    if args.paged and not (args.engine and args.swan):
        raise SystemExit("--paged requires --engine and --swan")
    if (args.data_parallel or args.mesh_shape) and not args.engine:
        raise SystemExit("--data-parallel/--mesh-shape require --engine")
    if args.pool_grow and not args.paged:
        raise SystemExit("--pool-grow requires --paged")
    if (args.trace_out or args.profile_steps) and not args.engine:
        raise SystemExit("--trace-out/--profile-steps require --engine")
    if args.use_pallas and not (args.engine and args.swan):
        raise SystemExit("--use-pallas requires --engine and --swan "
                         "(the kernels back the SWAN serve read path)")
    if (args.warmup or args.async_fetch) and not args.engine:
        raise SystemExit("--warmup/--async-fetch require --engine")
    return args


def load_model(args):
    """Weights (fresh from ``--seed``, or restored), then SWAN calibration
    and projection absorption.  Returns (cfg, params, swan, projections);
    swan/projections are None without ``--swan``."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt_dir:
        ck = Checkpointer(args.ckpt_dir)
        step = ck.latest_step()
        if step is not None:
            state = ck.restore(step, {"params": params})
            params = state["params"]
            print(f"restored checkpoint step {step}")

    swan = projections = None
    if args.swan:
        if not swan_applicable(cfg):
            raise SystemExit(f"SWAN inapplicable to {cfg.name} "
                             "(see DESIGN.md §Arch-applicability)")
        b = min(args.buffer, args.max_seq // 4)
        swan = SwanConfig(k_max=args.k or cfg.d_head // 2, buffer=b,
                          mode=args.mode, quantize=args.quantize)
        projections = calibrate_swan(api, cfg, params,
                                     make_batch(cfg, 4, 64, seed=3))
        params = api.absorb(params, cfg, projections)
        print(f"SWAN: k_max={swan.k_max}/{cfg.d_head} buffer={b} "
              f"mode={swan.mode} int8={swan.quantize}")
    return cfg, params, swan, projections


def main(argv=None):
    args = parse_args(argv)
    if args.compilation_cache:
        # before any compile happens, so the whole family lands on disk
        from repro.runtime.warmup import enable_compilation_cache
        print(f"compilation cache -> {enable_compilation_cache()}")
    cfg, params, swan, projections = load_model(args)
    if args.engine:
        _run_engine(cfg, params, swan, projections, args)
        return

    sess = ServeSession(cfg, params, swan=swan, projections=projections,
                        max_seq=args.max_seq, batch=args.batch,
                        metrics=bool(args.metrics_out))
    prompt = make_batch(cfg, args.batch, args.prompt_len, seed=11)
    out = sess.generate(prompt, args.tokens, temperature=args.temperature)
    for i in range(min(args.batch, 2)):
        print(f"seq {i}: {out[i].tolist()}")
    rep = sess.cache_report()
    extra = f" ({rep['saving']:.0%} vs dense)" if "saving" in rep else ""
    print(f"cache [{rep['mode']}]: {rep['bytes'] / 1e6:.2f} MB{extra}")
    _write_metrics(sess.metrics, args.metrics_out)


def _write_metrics(registry, path):
    """Dump a registry snapshot: Prometheus text for .prom/.txt paths,
    JSON otherwise.  No-op when path is None."""
    if not path:
        return
    if path.endswith((".prom", ".txt")):
        body = registry.to_prometheus()
    else:
        body = registry.to_json(indent=2)
    with open(path, "w") as fh:
        fh.write(body)
    print(f"metrics -> {path}")


def _serve_mesh(args):
    """Build the engine mesh from --mesh-shape / --data-parallel (None =
    single device)."""
    if args.mesh_shape:
        pairs = [kv.split("=") for kv in args.mesh_shape.split(",")]
        return make_mesh([int(n) for _, n in pairs], [ax for ax, _ in pairs])
    if args.data_parallel:
        return make_serve_mesh(args.data_parallel)
    return None


def build_engine(cfg, params, swan, projections, args, mesh=None,
                 trace=None, use_pallas=None) -> ServeEngine:
    """The engine the launcher serves with.  ``use_pallas`` overrides
    ``--use-pallas`` (None keeps the flag's value)."""
    return ServeEngine(cfg, params, swan=swan, projections=projections,
                       max_seq=args.max_seq, n_slots=args.batch,
                       paged=args.paged, page_size=args.page_size,
                       n_pages=args.pool_pages,
                       prefill_chunk=args.prefill_chunk,
                       prefill_slots=args.prefill_slots,
                       prefill_budget=args.prefill_budget,
                       mesh=mesh, pool_grow=args.pool_grow,
                       admission=args.admission, trace=trace,
                       use_pallas=(args.use_pallas if use_pallas is None
                                   else use_pallas),
                       async_fetch=args.async_fetch)


def _run_engine(cfg, params, swan, projections, args):
    mesh = _serve_mesh(args)
    trace = EventTrace(args.trace_out, keep=False) if args.trace_out else None
    eng = build_engine(cfg, params, swan, projections, args, mesh=mesh,
                       trace=trace)
    if args.warmup:
        rep = eng.warmup(max_prompt_len=args.max_prompt_len)
        print(f"warmup: {rep['census']['total']} executables, "
              f"{rep['compiles']} compiles in {rep['warmup_ms']:.0f} ms "
              f"({ {k: v['compiles'] for k, v in rep['by_kind'].items()} })")
    if args.profile_steps:
        eng.profile_steps(args.profile_steps, args.profile_dir)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} — {eng.dp} shards x "
              f"{eng.n_local} slots")
    n_req = args.requests or args.batch * 2
    k_cycle = ([None] if (swan is None or not args.mixed_k)
               else [swan.k_max, max(swan.k_max // 2, 1),
                     max(swan.k_max // 4, 1)])
    reqs = []
    for i in range(n_req):
        plen = max(4, args.prompt_len - 3 * (i % 4))     # mixed prompt lengths
        toks = make_batch(cfg, 1, plen, seed=100 + i)["tokens"][0]
        reqs.append(Request(
            uid=f"req{i}", tokens=[int(t) for t in toks],
            max_new_tokens=args.tokens, temperature=args.temperature,
            seed=i, k=k_cycle[i % len(k_cycle)]))
    t0 = time.perf_counter()
    comps = eng.run(reqs)
    dt = time.perf_counter() - t0
    n_tok = sum(len(c.tokens) for c in comps)
    for c in comps[:2]:
        print(f"{c.uid} (prompt {c.prompt_len}, k={c.k}, "
              f"steps {c.admitted_step}->{c.finished_step}): {c.tokens}")
    rep = eng.cache_report()
    extra = f" ({rep['saving']:.0%} vs dense)" if "saving" in rep else ""
    print(f"engine: {len(comps)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, {eng.step_count} steps, "
          f"decode executables: {eng.decode_cache_size}, "
          f"prefill executables: {eng.prefill_cache_size})")
    print(f"cache [{rep['mode']}]: {rep['bytes'] / 1e6:.2f} MB{extra}")
    if args.paged:
        print(f"paged: reserved {rep['reserved_bytes'] / 1e6:.2f} MB over "
              f"{rep['n_pages']} pages ({rep['page_size']} tok/page); "
              f"live now {rep['live_pages']} pages / "
              f"{rep['live_bytes'] / 1e6:.2f} MB "
              f"(slab layout would hold {rep['slab_bytes'] / 1e6:.2f} MB)")
    ttft = eng.metrics.get("serve_ttft_steps")
    if ttft is not None and ttft.count:
        print(f"ttft: p50 ~{ttft.quantile(0.5):.0f} steps, "
              f"p99 ~{ttft.quantile(0.99):.0f} steps (bucket-resolution)")
    _write_metrics(eng.metrics, args.metrics_out)
    if trace is not None:
        trace.close()
        print(f"trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
