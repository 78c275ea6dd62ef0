"""Run the SWAN serve engine once on a TPU, at the full width of OLMo-1B.

    python3 chip_smoke.py [--seed N]       # one chip: the main serve path
    python3 chip_smoke.py --four-chips     # a 4-chip data-parallel engine
                                           # against one chip, token by token

The main path goes through the launcher's own entry points
(``repro.launch.serve.parse_args`` / ``load_model`` / ``build_engine``) with
``--engine --swan --paged --prefill-chunk --warmup --async-fetch``: random
``olmo-1b`` weights from ``--seed`` at published widths, SWAN calibration
and absorption, compiled Pallas kernels (``use_pallas=None`` resolves them
on a TPU), the warmed executable family, then a few mixed-length requests
with mixed per-request ``k``, greedy lanes and one temperature lane.  It
then compares the kernel path with the pure-JAX path (``use_pallas=False``)
on the same chip: decode logits of every request's own prompt and ``k``
against a written tolerance, and the engines' tokens.

Every phase that fails raises, so the exit code is non-zero and the last
line is not printed.  The last line of a run that passed is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.  With
no TPU the script exits non-zero before doing anything: it never falls
back to the CPU.  Times printed here are one-off observations of a smoke
run, not benchmark results.

The phase functions take their sizes as arguments, so a CPU test
(``tests/test_chip_smoke.py``) drives them at smoke widths with the kernels
forced into interpret mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# The main path's shape: full olmo-1b widths, a short warmed family so a
# cold compile fits the run (max_seq 2048, 256-token chunks, 2 prefill
# slots, prompts of at most 256 tokens), and prompts longer than the
# 128-token ring so the kernels read a non-empty sparse prefix.
ARCH = "olmo-1b"
PROMPT_LENS = (256, 200, 130, 40, 97)
NEW_TOKENS = 24
SERVE_FLAGS = ("--engine", "--swan", "--paged", "--prefill-chunk", "256",
               "--prefill-slots", "2", "--max-seq", "2048", "--batch", "4",
               "--warmup", "--async-fetch")
# kernel-vs-XLA logits comparison: decode steps per request, cache length
COMPARE_STEPS, COMPARE_MAX_SEQ = NEW_TOKENS, 1024
# four-chip phase: 8 slots over a 4-way data mesh (2 per chip)
FOUR_CHIP_SLOTS = 8


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def load(argv):
    """Launcher args, then weights from the seed, SWAN calibration and
    absorption — exactly what ``python -m repro.launch.serve`` does."""
    from repro.launch import serve
    args = serve.parse_args(argv)
    t0 = time.perf_counter()
    cfg, params, swan, pj = serve.load_model(args)
    import jax
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_head={cfg.d_head} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={n_params} "
        f"dtype={cfg.dtype}; SWAN k_max={swan.k_max} buffer={swan.buffer} "
        f"(load+calibrate {time.perf_counter() - t0:.1f}s)")
    return args, cfg, params, swan, pj


def make_requests(cfg, swan, prompt_lens, new_tokens: int):
    """Mixed-length prompts with mixed per-request k; greedy lanes plus one
    temperature lane (the second request)."""
    from repro.launch.io import make_batch
    from repro.runtime.serve_engine import Request
    ks = (swan.k_max, swan.k_max // 2, swan.k_max // 4)
    reqs = []
    for i, n in enumerate(prompt_lens):
        toks = make_batch(cfg, 1, n, seed=100 + i)["tokens"][0]
        reqs.append(Request(uid=f"req{i}", tokens=[int(t) for t in toks],
                            max_new_tokens=new_tokens,
                            temperature=0.8 if i == 1 else 0.0, seed=i,
                            k=ks[i % len(ks)]))
    return reqs


def serve_phase(cfg, params, swan, pj, args, reqs, *, use_pallas=None,
                expect_interpret: bool = False, warm: bool = True,
                mesh=None) -> dict:
    """Build the engine the launcher builds, check which attention path it
    resolved, warm the family, serve ``reqs``, count compiles after
    warmup."""
    from repro.launch import serve
    from repro.obs import compile_events
    eng = serve.build_engine(cfg, params, swan, pj, args, mesh=mesh,
                             use_pallas=use_pallas)
    if use_pallas is not False:
        check(eng.use_pallas, "engine did not resolve the Pallas kernels")
        check(eng._pallas_interpret == expect_interpret,
              f"kernels resolved interpret={eng._pallas_interpret}, "
              f"expected {expect_interpret}")
    out = {"engine": eng, "warmup_executables": 0, "warmup_s": 0.0}
    if warm:
        pc0, cs0 = compile_events.persistent_cache(), compile_events.seconds()
        rep = eng.warmup(max_prompt_len=args.max_prompt_len)
        pc1 = compile_events.persistent_cache()
        out["warmup_compile_s"] = compile_events.seconds() - cs0
        out["warmup_executables"] = rep["census"]["total"]
        out["warmup_compiles"] = rep["compiles"]
        out["warmup_s"] = rep["warmup_ms"] / 1e3
        out["warmup_cache"] = {n: pc1[n] - pc0[n] for n in pc1}
    c0 = compile_events.total()
    t0 = time.perf_counter()
    comps = eng.run(reqs)
    out["wall_s"] = time.perf_counter() - t0
    out["compiles_after_warmup"] = compile_events.total() - c0
    out["tokens"] = {c.uid: list(c.tokens) for c in comps}
    out["n_tokens"] = sum(len(c.tokens) for c in comps)
    check(len(comps) == len(reqs), f"{len(comps)}/{len(reqs)} completed")
    for c in comps:
        check(len(c.tokens) == next(r.max_new_tokens for r in reqs
                                    if r.uid == c.uid),
              f"{c.uid} returned {len(c.tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in c.tokens),
              f"{c.uid} returned a token outside the vocabulary")
    out["cache"] = eng.cache_report()
    return out


def compare_phase(cfg, params, swan, pj, reqs, feed: dict, *, steps: int,
                  max_seq: int, interpret: bool) -> dict:
    """Decode logits of the Pallas kernel path vs the pure-JAX path, both
    in the model's bf16, and of both vs a float32 run of the pure-JAX path
    (the plain reference), for each request's own prompt and ``k``: prompts
    longer than the ring (a non-empty sparse prefix), prompts shorter than
    it (a partly filled ring, an empty sparse prefix) and ``k < k_max``.
    Each request decodes alone from a prefill padded to the longest prompt
    (one prefill executable per path); all three paths are fed ``feed``,
    the tokens the pure-JAX engine generated for it, so step ``i`` scores
    the engine's token ``i + 1`` on the engine's own trajectory.

    Tolerance, per request: the kernel path may differ from the pure-JAX
    path by at most twice the pure-JAX path's own distance from float32.
    Both read the same packed cache; the kernel keeps scores and
    probabilities in f32 where the pure-JAX path rounds probabilities to
    bf16, so the kernel is no further from float32 than the pure-JAX path
    is, and the triangle inequality bounds their distance by twice that.
    A wrong index, mask or scale moves the logits far beyond bf16 rounding.

    Each step also records whether the two bf16 paths pick the same token,
    the pure-JAX path's margin between its two highest logits and the
    largest logit difference between the paths: where the paths part, the
    margin is below twice that difference, and a margin small beside the
    logits' spread is a near tie."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    api = get_model(cfg)
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")
    params32 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    bucket = max(len(r.tokens) for r in reqs)
    paths = {"kernel": (cfg, params, True), "xla": (cfg, params, False),
             "f32": (cfg32, params32, False)}

    def prefill(c):
        return jax.jit(lambda p, t, n, k, s: api.prefill(
            p, c, {"tokens": t}, s, swan, pj, k_active=k, true_len=n))

    def stepper(c, kern):
        return jax.jit(lambda p, tok, pos, k, s: api.decode_step(
            p, c, tok, pos, s, swan, pj, k_active=k, use_pallas=kern,
            pallas_interpret=interpret))

    prefill_fn = {n: prefill(c) for n, (c, _, _) in paths.items()}
    step_fn = {n: stepper(c, kern) for n, (c, _, kern) in paths.items()}

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    out = {"requests": {}}
    for r in reqs:
        n = len(r.tokens)
        toks = jnp.asarray([r.tokens + [0] * (bucket - n)], jnp.int32)
        k = jnp.full((1,), r.k, jnp.int32)
        logits, state = {}, {}
        for name, (c, p, _) in paths.items():
            logits[name], state[name] = prefill_fn[name](
                p, toks, jnp.int32(n), jnp.int32(r.k),
                api.init_serve_state(c, swan, 1, max_seq))
        res = {"prompt": n, "k": r.k, "kernel_xla": [], "xla_f32": [],
               "kernel_f32": [], "agree": [], "margin": [], "max_diff": []}
        for i in range(min(steps, len(feed[r.uid]))):
            tok = jnp.full((1,), feed[r.uid][i], jnp.int32)
            pos = jnp.full((1,), n + i, jnp.int32)
            for name, (_, p, _) in paths.items():
                lg, state[name] = step_fn[name](p, tok, pos, k, state[name])
                logits[name] = lg.astype(jnp.float32)
            lk, lx, lf = logits["kernel"], logits["xla"], logits["f32"]
            check(bool(jnp.all(jnp.isfinite(lk))),
                  f"{r.uid}: kernel logits not finite")
            res["kernel_xla"].append(rel(lk, lx))
            res["xla_f32"].append(rel(lx, lf))
            res["kernel_f32"].append(rel(lk, lf))
            res["agree"].append(bool(jnp.argmax(lk) == jnp.argmax(lx)))
            top2 = jax.lax.top_k(lx[0], 2)[0]
            res["margin"].append(float(top2[0] - top2[1]))
            res["max_diff"].append(float(jnp.max(jnp.abs(lk - lx))))
        res["tol"] = 2.0 * max(res["xla_f32"])
        res["ok"] = max(res["kernel_xla"]) <= res["tol"]
        out["requests"][r.uid] = res
    out["ok"] = all(res["ok"] for res in out["requests"].values())
    return out


def token_agreement(reqs, a: dict, b: dict) -> dict:
    """Per-token agreement of two engines' outputs on the greedy requests
    (a temperature lane samples from logits that may differ in the last
    bits, so it is left out), and the first step where each request's
    tokens part."""
    same = total = 0
    parted = {}
    for r in reqs:
        ta, tb = a[r.uid], b[r.uid]
        diff = [i for i, (x, y) in enumerate(zip(ta, tb)) if x != y]
        if diff or len(ta) != len(tb):
            parted[r.uid] = diff[0] if diff else min(len(ta), len(tb))
        if r.temperature == 0.0:
            same += sum(x == y for x, y in zip(ta, tb))
            total += max(len(ta), len(tb))
    return {"same": same, "total": total, "parted": parted,
            "identical": {r.uid for r in reqs if a[r.uid] == b[r.uid]}}


def device_bytes() -> list:
    import jax
    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def run_main(seed: int, *, arch: str = ARCH, smoke: bool = False,
             prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
             serve_flags=SERVE_FLAGS, use_pallas=None,
             expect_interpret: bool = False, compare=None) -> dict:
    """The one-chip main path: load, serve the kernel engine (warmed),
    then the kernel-vs-XLA comparison.  Returns what it printed."""
    argv = ["--arch", arch, "--seed", str(seed), *serve_flags,
            "--max-prompt-len", str(max(prompt_lens))]
    if smoke:
        argv.append("--smoke")
    args, cfg, params, swan, pj = load(argv)
    reqs = make_requests(cfg, swan, prompt_lens, new_tokens)

    kern = serve_phase(cfg, params, swan, pj, args, reqs,
                       use_pallas=use_pallas,
                       expect_interpret=expect_interpret)
    cache = kern["cache"]
    say(f"cache [{cache['mode']}]: {cache['bytes']} bytes "
        f"(reserved {cache.get('reserved_bytes')}, live "
        f"{cache.get('live_bytes')})")
    say(f"warmup: {kern['warmup_executables']} executables, "
        f"{kern['warmup_compiles']} compiles in {kern['warmup_s']:.1f}s "
        f"({kern['warmup_compile_s']:.1f}s of it in backend compile or "
        f"cache load); persistent cache {kern['warmup_cache']['hits']} hits, "
        f"{kern['warmup_cache']['writes']} writes")
    say(f"serve: {len(reqs)} requests, {kern['n_tokens']} tokens in "
        f"{kern['wall_s']:.3f}s; compiles after warmup: "
        f"{kern['compiles_after_warmup']}")
    check(kern["compiles_after_warmup"] == 0,
          f"{kern['compiles_after_warmup']} compiles after warmup")

    xla = serve_phase(cfg, params, swan, pj, args, reqs, use_pallas=False,
                      warm=False)
    agree = token_agreement(reqs, kern["tokens"], xla["tokens"])
    say(f"tokens kernel vs XLA engine (greedy requests): {agree['same']}/"
        f"{agree['total']} agree; identical requests: "
        f"{sorted(agree['identical'])}; first step where tokens part: "
        f"{agree['parted']}")

    cmp = compare_phase(cfg, params, swan, pj, reqs, xla["tokens"],
                        interpret=expect_interpret,
                        **(compare or dict(steps=COMPARE_STEPS,
                                           max_seq=COMPARE_MAX_SEQ)))
    for uid, res in cmp["requests"].items():
        split = [i for i, a in enumerate(res["agree"]) if not a]
        at = (f"first at step {split[0]}: XLA top-2 margin "
              f"{res['margin'][split[0]]:.4g}, max |kernel - XLA| logit "
              f"{res['max_diff'][split[0]]:.4g}" if split else "none")
        t = agree["parted"].get(uid)     # engine token t is scored at step t-1
        if t is not None:
            at += (f"; engine tokens part at token {t}: " + (
                f"XLA top-2 margin there {res['margin'][t - 1]:.4g}, max "
                f"|kernel - XLA| logit {res['max_diff'][t - 1]:.4g}"
                if 1 <= t <= len(res["margin"]) else "not scored"))
        say(f"logits {uid} (prompt {res['prompt']}, k={res['k']}, "
            f"{len(res['agree'])} steps), relative L2: kernel vs XLA max "
            f"{max(res['kernel_xla']):.3e} (tolerance {res['tol']:.3e} = "
            f"2 x max XLA vs float32), XLA vs float32 max "
            f"{max(res['xla_f32']):.3e}, kernel vs float32 max "
            f"{max(res['kernel_f32']):.3e}; argmax partings "
            f"{len(split)}/{len(res['agree'])}, {at}; median XLA top-2 "
            f"margin {sorted(res['margin'])[len(res['margin']) // 2]:.4g}")
    for uid, res in cmp["requests"].items():
        check(res["ok"], f"{uid}: kernel logits off by "
                         f"{max(res['kernel_xla']):.3e} > {res['tol']:.3e}")
    return {"kernel": kern, "xla": xla, "agreement": agree, "compare": cmp}


def run_four_chips(seed: int, *, arch: str = ARCH, smoke: bool = False,
                   prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
                   serve_flags=SERVE_FLAGS, use_pallas=None,
                   expect_interpret: bool = False, n_dev: int = 4) -> dict:
    """Only the data-parallel engine on a 4-device ('data',) mesh and the
    single-device engine it must match token for token."""
    from repro.launch.mesh import make_serve_mesh
    # no warmup: this phase compares tokens, and the one-chip run already
    # checks the warmed family; skipping it halves the cold compile
    flags = [f for f in serve_flags if f != "--warmup"]
    flags[flags.index("--batch") + 1] = str(FOUR_CHIP_SLOTS)
    argv = ["--arch", arch, "--seed", str(seed), *flags,
            "--max-prompt-len", str(max(prompt_lens))]
    if smoke:
        argv.append("--smoke")
    args, cfg, params, swan, pj = load(argv)
    reqs = make_requests(cfg, swan, prompt_lens, new_tokens)
    mesh = make_serve_mesh(n_dev)
    dp = serve_phase(cfg, params, swan, pj, args, reqs, mesh=mesh,
                     use_pallas=use_pallas,
                     expect_interpret=expect_interpret, warm=False)
    say(f"data-parallel engine: mesh {dict(mesh.shape)}, "
        f"{dp['engine'].n_local} slots per device, {dp['n_tokens']} tokens "
        f"in {dp['wall_s']:.3f}s")
    say(f"device bytes in use: {device_bytes()}")
    one = serve_phase(cfg, params, swan, pj, args, reqs,
                      use_pallas=use_pallas,
                      expect_interpret=expect_interpret, warm=False)
    same = dp["tokens"] == one["tokens"]
    say(f"single-device engine: {one['n_tokens']} tokens in "
        f"{one['wall_s']:.3f}s; token for token identical: {same}")
    check(same, "data-parallel tokens differ from the single-device engine")
    return {"dp": dp, "one": one}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random olmo-1b weights")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel engine and its "
                         "single-device comparison")
    opts = ap.parse_args(argv)
    dev = device_info()
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX finds no TPU; this script runs only on the "
              "chip", file=sys.stderr)
        return 2
    need = 4 if opts.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {dev['count']}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.runtime.warmup import enable_compilation_cache
    say(f"compilation cache: {enable_compilation_cache()}")
    t0 = time.perf_counter()
    if opts.four_chips:
        run_four_chips(opts.seed)
    else:
        run_main(opts.seed)
    say(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
