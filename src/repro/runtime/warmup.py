"""AOT executable-family warmup for :class:`repro.runtime.serve_engine.
ServeEngine`.

Under real traffic the first request to hit each (prompt-chunk x lane x
slab-read-prefix / page-table-prefix) shape bucket eats a multi-second XLA
compile in the middle of serving.  Every bucket is enumerable from the
engine's STATIC config, so this module enumerates the complete family
(:func:`executable_family`) and pre-compiles it at startup
(:func:`warmup_engine`) — after which a randomized mixed workload triggers
ZERO new compiles (machine-checked by the swanlint Layer-2 audit and
``benchmarks/bench_warmup.py`` via ``repro.obs.compile_events``).

Why dummy dispatches instead of ``jit(...).lower(...).compile()``: an AOT
``lower().compile()`` produces a compiled artifact but does NOT populate
the jit callable's dispatch cache — the first real call would re-trace and
re-compile anyway (verified empirically: ``_cache_size()`` stays put after
``lower().compile()`` and bumps on a real call).  So warmup drives the
SAME jitted callables ``step()`` dispatches through, with dead-lane no-op
operands the engine's own scheduling contract already guarantees are
side-effect-free:

* decode with every lane at ``pos = -1`` — the dead-lane rule from chunked
  prefill (ring untouched, sparse/dense writes dropped or sent to the
  shard's trash page);
* chunk with every lane's slot parked at the out-of-range local index
  ``n_local`` — exactly how ``_advance_prefills`` pads unused lanes;
* monolithic-admission prefill into a fresh batch=1 transient, then the
  insert parked at global slot ``n_slots`` (scatter ``mode="drop"`` /
  trash-page rows).

State leaves are donated into those dispatches, so the engine's ``state``
is re-bound to each call's output — contents are bit-identical (warmed ==
unwarmed engines are token-identical, gated in tests/test_warmup.py).

The family also includes the EAGER executables on the serve path, which
the per-dispatch jit census cannot see but the zero-compile gate does: the
power-of-two-bucketed temperature-row gather + async host copies
(``_start_fetch``), the admission logits-row slice, and the
``sample_token``/PRNG ops behind ``_sample``.

Growth executables (``pool_grow``) are the one family NOT warmed here:
their shape depends on the runtime growth sequence, growth is a rare
control-plane event, and ``_grow_pool`` re-warms the whole family anyway
(the pool leaf changes shape, staleing every state-carrying executable).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import paged_cache as pc
from repro.obs import compile_events
from repro.obs.trace import span

_COMPILE_HELP = "XLA backend compiles by phase (warmup vs mid-serve)"


@dataclass(frozen=True)
class WarmupItem:
    """One warm dispatch: a (kind, shape-bucket) the scheduler can legally
    request.  ``detail`` is the human-readable bucket key that lands in
    the warmup report and the bench rows."""
    kind: str                           # decode|chunk|prefill|fetch|sample
    page_bucket: Optional[int] = None   # shipped table width (paged)
    n_lanes: int = 0                    # chunk lane width (dp * Pl)
    chunk: int = 0                      # chunk token width C
    prefix: Optional[int] = None        # slab read-prefix bucket
    pad_len: int = 0                    # monolithic prompt bucket
    width: int = 0                      # fetch: temp-row gather width
    src: str = ""                       # fetch: "decode" or "chunk"

    @property
    def detail(self) -> str:
        if self.kind == "decode":
            return (f"page_bucket={self.page_bucket}"
                    if self.page_bucket is not None else "slab")
        if self.kind == "chunk":
            tail = (f"page_bucket={self.page_bucket}"
                    if self.page_bucket is not None
                    else f"prefix={self.prefix}")
            return f"lanes={self.n_lanes} C={self.chunk} {tail}"
        if self.kind == "prefill":
            return f"pad={self.pad_len}"
        if self.kind == "fetch":
            return f"{self.src} lanes={self.n_lanes} rows={self.width}"
        return self.kind


def _pow2_buckets(cap: int) -> List[int]:
    """Every power-of-two value ``min(_pow2(x), cap)`` can take for
    ``x in 1..cap`` — the engine's universal bucket rule: powers of two up
    to ``cap``, plus ``cap`` itself when it is not one (the clamp)."""
    out: List[int] = []
    b = 1
    while b <= cap:
        out.append(b)
        b <<= 1
    if not out or out[-1] != cap:
        out.append(cap)
    return out


def executable_family(eng, max_prompt_len: Optional[int] = None
                      ) -> Dict[str, Any]:
    """Enumerate every executable bucket the scheduler can legally request
    from ``eng``'s static config.

    Returns ``{"items": [WarmupItem...], "expected": {...}, "skipped":
    [...]}`` where ``expected`` mirrors :meth:`ServeEngine.
    executable_census`'s keys — per-family compiled-executable counts a
    fully-warmed engine must meet (the Layer-2 audit asserts
    ``census >= expected`` bucket by bucket).  ``items`` are ordered so a
    fetch item always follows the dispatch item that produces its source
    logits.  ``max_prompt_len`` (admission-side bound on prompt tokens)
    trims the slab read-prefix, paged chunk page-table and monolithic pad
    families."""
    items: List[WarmupItem] = []
    skipped: List[str] = []
    prompt_cap = min(max_prompt_len or eng.max_seq, eng.max_seq)
    prompt_pow2 = eng._pow2(prompt_cap)

    # --- decode family -------------------------------------------------
    if eng.paged:
        widths = _pow2_buckets(eng.pool.pages_per_seq)
        items += [WarmupItem("decode", page_bucket=w) for w in widths]
        n_decode = len(widths)
    else:
        items.append(WarmupItem("decode"))
        n_decode = 1
    # temperature-row gather over decode logits [n_slots, V]
    items += [WarmupItem("fetch", src="decode", n_lanes=eng.n_slots,
                         width=w) for w in _pow2_buckets(eng.n_slots)]

    # --- chunk family (chunked prefill) --------------------------------
    exp_chunk: Dict[str, int] = {}
    n_prefill = n_insert = n_insert_paged = 0
    if eng.prefill_chunk is not None:
        pl_buckets = _pow2_buckets(eng._pow2(eng.prefill_slots))
        c_buckets = _pow2_buckets(eng.prefill_chunk)
        if eng.paged:
            # a chunk ships the pow2 bucket of its lanes' mapped pages,
            # which cover at most the prompt's own tokens
            ps, pps = eng.pool.page_size, eng.pool.pages_per_seq
            chunk_widths = _pow2_buckets(
                min(eng._pow2(-(-prompt_cap // ps)), pps))
        for pl in pl_buckets:
            lanes = eng.dp * pl
            for c in c_buckets:
                if eng.paged:
                    for w in chunk_widths:
                        items.append(WarmupItem(
                            "chunk", n_lanes=lanes, chunk=c, page_bucket=w))
                        exp_chunk["paged"] = exp_chunk.get("paged", 0) + 1
                else:
                    # prefix = min(pow2(start_max + C), max_seq) with
                    # start >= 0 => every pow2 bucket in [C, prompt bound]
                    for p in _pow2_buckets(eng.max_seq):
                        if p < c or p > max(prompt_pow2, c):
                            continue
                        items.append(WarmupItem(
                            "chunk", n_lanes=lanes, chunk=c, prefix=p))
                        exp_chunk[str(p)] = exp_chunk.get(str(p), 0) + 1
            items += [WarmupItem("fetch", src="chunk", n_lanes=lanes,
                                 width=w) for w in _pow2_buckets(lanes)]
    elif eng._bucketing:
        # monolithic admission: one (prefill, insert) pair per prompt
        # pad bucket
        pads = [b for b in _pow2_buckets(eng.max_seq) if b <= prompt_pow2]
        items += [WarmupItem("prefill", pad_len=b) for b in pads]
        n_prefill = len(pads)
        if eng.paged:
            n_insert_paged = len(pads)
        else:
            n_insert = len(pads)
    else:
        skipped.append(
            "monolithic prefill with bucket_prompts=False compiles once "
            "per distinct prompt length — an unbounded family warmup "
            "cannot enumerate")
    items.append(WarmupItem("sample"))

    return {
        "items": items,
        "expected": {"decode": n_decode, "prefill": n_prefill,
                     "chunk": exp_chunk, "insert": n_insert,
                     "insert_paged": n_insert_paged},
        "skipped": skipped,
    }


def _warm_fetch(eng, logits, greedy, width: int) -> None:
    """Compile the async token-fetch path for one temperature-lane bucket
    against REAL dispatch outputs (right shape, dtype and sharding): the
    padded row gather, both ``copy_to_host_async`` transfers, and the
    host conversions."""
    idx = np.zeros((width,), np.int32)
    rows = logits[jnp.asarray(idx)]
    rows.copy_to_host_async()
    greedy.copy_to_host_async()
    np.asarray(rows)
    np.asarray(greedy)


def warmup_engine(eng, max_prompt_len: Optional[int] = None
                  ) -> Dict[str, Any]:
    """Pre-compile ``eng``'s whole executable family (see module
    docstring).  Returns the warmup report::

        {"warmup_ms": ..., "compiles": ..., "items": [{kind, detail,
         compiles, ms}...], "by_kind": {kind: {"items", "compiles"}},
         "expected": <family expectation>, "census": <executable_census>,
         "skipped": [...]}

    and records ``serve_warmup_ms`` / ``serve_compile_total{phase=
    "warmup"}`` in the engine's metrics registry plus ``warmup`` trace
    events.  Safe to call mid-serve (pool growth does): the dummy
    operands are dead-lane no-ops, so live sequences are untouched."""
    if not eng._jit:
        raise RuntimeError("warmup requires jit=True — a no-jit engine "
                           "has no executables to pre-compile")
    fam = executable_family(eng, max_prompt_len=max_prompt_len)
    t_start = time.perf_counter()
    c_start = compile_events.total()
    if eng.trace is not None:
        eng.trace.emit("warmup_start", step=eng.step_count,
                       n_items=len(fam["items"]))

    rows: List[Dict[str, Any]] = []

    def timed(item: WarmupItem, fn) -> Any:
        c0 = compile_events.total()
        t0 = time.perf_counter()
        with span("warmup.item", eng.step_count,
                  stats={"family": item.kind, "shape": item.detail}):
            out = fn()
        dc = compile_events.total() - c0
        rows.append({"kind": item.kind, "detail": item.detail,
                     "compiles": dc,
                     "ms": (time.perf_counter() - t0) * 1e3})
        if dc:
            eng.metrics.counter("serve_compile_total", _COMPILE_HELP,
                                phase="warmup", kind=item.kind).inc(dc)
        return out

    # dead-lane decode operands: pos = -1 everywhere, exactly the state a
    # fresh engine decodes with while every slot is still prefilling
    dead_tok = np.zeros((eng.n_slots,), np.int32)
    dead_pos = np.full((eng.n_slots,), -1, np.int32)
    dead_k = np.full((eng.n_slots,), eng._k_fill, np.int32)
    # last dispatch outputs per fetch source, keyed by (src, n_lanes)
    last: Dict[Any, Any] = {}

    for item in fam["items"]:
        if item.kind == "decode":
            tab = (eng._device_table(item.page_bucket)
                   if item.page_bucket is not None
                   else np.zeros((), np.int32))

            def run_decode(tab=tab):
                logits, greedy, state = eng._decode(
                    eng.params, dead_tok, dead_pos, dead_k, tab, eng.state)
                eng.state = state
                return logits, greedy
            last[("decode", eng.n_slots)] = timed(item, run_decode)

        elif item.kind == "chunk":
            lanes = item.n_lanes
            toks = np.zeros((lanes, item.chunk), np.int32)
            slot_v = np.full((lanes,), eng.n_local, np.int32)  # parked OOB
            start_v = np.zeros((lanes,), np.int32)
            tlen_v = np.ones((lanes,), np.int32)
            k_v = np.full((lanes,), eng._k_fill, np.int32)
            tab = (eng._device_table(item.page_bucket)
                   if item.page_bucket is not None
                   else np.zeros((), np.int32))

            def run_chunk(toks=toks, slot_v=slot_v, start_v=start_v,
                          tlen_v=tlen_v, k_v=k_v, tab=tab,
                          prefix=item.prefix):
                logits, greedy, state = eng._chunk_call(
                    eng.params, toks, eng.state, slot_v, start_v, k_v,
                    tlen_v, tab, prefix=prefix)
                eng.state = state
                return logits, greedy
            last[("chunk", lanes)] = timed(item, run_chunk)

        elif item.kind == "prefill":
            pad = item.pad_len
            if eng.paged:
                ps = eng.pool.page_size
                s1 = -(-pad // ps) * ps
            else:
                s1 = eng.max_seq

            def run_prefill(pad=pad, s1=s1):
                state1 = eng.api.init_serve_state(eng.cfg, eng.swan, 1, s1)
                toks = np.zeros((pad,), np.int32)
                logits, state1 = eng._prefill(
                    eng.params, {"tokens": toks[None]}, state1,
                    np.int32(eng._k_fill), np.int32(1))
                np.asarray(logits[0, -1])     # admission-row slice + copy
                if eng.paged:
                    trash = np.full((s1 // eng.pool.page_size,),
                                    pc.TRASH_PAGE, np.int32)
                    # parked at slot n_slots: ring writes drop, pool
                    # writes land on the trash page
                    eng.state = eng._insert_paged(
                        eng.state, state1, np.int32(eng.n_slots), trash)
                else:
                    eng.state = eng._insert(eng.state, state1,
                                            np.int32(eng.n_slots))
            timed(item, run_prefill)

        elif item.kind == "fetch":
            src = last.get((item.src, item.n_lanes))
            if src is None:
                continue
            timed(item, lambda src=src, w=item.width:
                  _warm_fetch(eng, src[0], src[1], w))

        elif item.kind == "sample":
            dec = last[("decode", eng.n_slots)]
            row = np.asarray(dec[0])[0]       # real dtype/width [V] row

            def run_sample(row=row):
                eng._sample(row, SimpleNamespace(temperature=1.0, seed=0),
                            0)
            timed(item, run_sample)

    warmup_ms = (time.perf_counter() - t_start) * 1e3
    compiles = compile_events.total() - c_start
    by_kind: Dict[str, Dict[str, int]] = {}
    for r in rows:
        agg = by_kind.setdefault(r["kind"], {"items": 0, "compiles": 0})
        agg["items"] += 1
        agg["compiles"] += r["compiles"]
    report = {"warmup_ms": warmup_ms, "compiles": compiles, "items": rows,
              "by_kind": by_kind, "expected": fam["expected"],
              "census": eng.executable_census(),
              "skipped": fam["skipped"]}
    eng.metrics.gauge("serve_warmup_ms",
                      "wall time of the last executable-family warmup"
                      ).set(warmup_ms)
    if eng.trace is not None:
        eng.trace.emit("warmup_done", step=eng.step_count,
                       n_items=len(rows), compiles=compiles,
                       warmup_ms=warmup_ms)
    return report


# fixed, inside the checkout: a path built from a temp name, a pid or the
# time would be a new, empty directory in every process and never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and cache every
    executable (serve executables are small; the point is caching the
    whole family), so engine restarts reload the warmed family from disk.
    The directory is ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    ``.jax_cache/`` at the root of this checkout.  Returns the directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
