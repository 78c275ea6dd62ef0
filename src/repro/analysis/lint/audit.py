"""swanlint Layer 2: compiled-dispatch auditor for the serve hot path.

Layer 1 reads source; this layer reads what XLA actually built.  It
AOT-lowers the ServeEngine's chunk/decode executables over a
(bucket × paged × mesh) matrix via ``ServeEngine.lower_decode`` /
``lower_chunk``, parses the post-optimization HLO through
``repro.analysis.hlo``, and asserts the ROADMAP perf contract:

  (i)   executable-count bounds — power-of-two bucketing keeps the
        compile universe at O(log max_seq): ONE decode executable per
        page bucket (exactly one for slab), one chunk executable per
        (lane, chunk, prefix) bucket, and an identical workload re-run
        compiles NOTHING new.  Counting goes through
        ``ServeEngine.executable_census()`` — decode, prefill, the chunk
        family, both admission inserts and pool-grow — so no family can
        silently escape the bounds;
  (i')  the warmup contract (``warmup_checks``) — after
        ``ServeEngine.warmup()`` the census covers every bucket the
        scheduler can legally request (``repro.runtime.warmup.
        executable_family``), a second warmup compiles nothing, and a
        randomized mixed workload (mixed k, temperatures, prompt lengths
        spanning the chunk/page/prefix buckets) triggers ZERO new XLA
        compiles (``repro.obs.compile_events``);
  (ii)  zero host transfers inside dispatch bodies — no infeed/outfeed,
        no host sends/recvs, no S(5) copies, no MoveToHost annotations
        (the designed host fetch points live OUTSIDE the executables);
  (iii) collective inventory matches the sharding contract — the serve
        path is lane-local by design (shard_map bodies never
        communicate), so the per-collective census must be EMPTY;
  (iv)  Pallas kernel prechecks — grid divisibility and the TPU
        compiler's block-layout rules for the SWAN kernels (and the
        ``flash_prefill`` VMEM model) at the engine's shapes.

Each assertion is an ``AuditCheck`` with status pass/fail/skip; the CLI
folds them into the JSON report next to the Layer 1 findings.  The check
helpers (``transfer_check``/``collective_check``/``count_check``) are
pure text/number functions so tests can drive them with synthetic HLO
and synthetic counts — the engine-building matrix is only needed for the
integration smoke.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.analysis.hlo import analyze_hlo, transfer_stats

__all__ = ["AuditCheck", "transfer_check", "collective_check",
           "count_check", "logical_view_check", "kernel_precheck_checks",
           "audit_lowered", "warmup_checks", "run_audit"]


@dataclass
class AuditCheck:
    check: str                  # e.g. "host-transfers/slab/decode"
    status: str                 # "pass" | "fail" | "skip"
    detail: str = ""

    def to_json(self) -> Dict[str, str]:
        return {"check": self.check, "status": self.status,
                "detail": self.detail}


# ---------------------------------------------------------------------------
# Pure check helpers (unit-testable without building an engine)
# ---------------------------------------------------------------------------

def transfer_check(hlo_text: str, label: str) -> AuditCheck:
    """(ii): the executable must not cross the host boundary, and every
    async start must pair with a done."""
    ts = transfer_stats(hlo_text)
    problems = []
    if ts.host_total:
        problems.append(f"{ts.host_total} host transfer(s): "
                        f"{ts.to_json()}")
    if ts.unmatched_async:
        problems.append(f"{ts.unmatched_async} unmatched async "
                        "collective start(s)")
    if problems:
        return AuditCheck(f"host-transfers/{label}", "fail",
                          "; ".join(problems))
    return AuditCheck(f"host-transfers/{label}", "pass",
                      "no host boundary crossings")


def collective_check(hlo_text: str, label: str,
                     allowed: tuple = ()) -> AuditCheck:
    """(iii): collective inventory vs the declared sharding contract
    (empty for the lane-local serve path)."""
    costs = analyze_hlo(hlo_text)
    extra = {k: v for k, v in costs.per_collective.items()
             if k not in allowed}
    if extra:
        return AuditCheck(
            f"collectives/{label}", "fail",
            f"undeclared collectives on the serve path: {extra}")
    return AuditCheck(f"collectives/{label}", "pass",
                      f"inventory matches contract (allowed={list(allowed)})")


def count_check(label: str, observed: int, bound: int,
                what: str = "executables") -> AuditCheck:
    """(i): observed compiled-executable count within its O(log) bound."""
    if observed < 0:
        return AuditCheck(f"exec-count/{label}", "skip",
                          "cache size not exposed by this jax version")
    if observed > bound:
        return AuditCheck(f"exec-count/{label}", "fail",
                          f"{observed} {what} > bound {bound}")
    return AuditCheck(f"exec-count/{label}", "pass",
                      f"{observed} {what} <= bound {bound}")


def _log2_buckets(n: int) -> int:
    """Number of power-of-two buckets in [1, n]."""
    return max(1, int(math.log2(max(1, n))) + 1)


_GATHER_RE = re.compile(
    r"=\s*[a-z0-9]+\[([0-9,]*)\][^=]*\bgather\(")


def logical_view_check(hlo_text: str, label: str, view_elems: int,
                       expect_materialized: bool = False) -> AuditCheck:
    """Paged decode HLO inspection: the Pallas paged kernel gathers pool
    pages INSIDE the kernel (page-table scalar prefetch -> VMEM tiles), so
    its executable must contain no gather materialising the
    ``paged_logical_view`` — i.e. no gather whose result holds at least
    ``view_elems`` elements (= B x Kv x bucket*page_size x k_max, the
    view's vals leaf).  ``expect_materialized=True`` inverts the check for
    the pure-JAX reference executable, proving the detector actually sees
    the logical-view gather it is meant to rule out."""
    big = []
    for m in _GATHER_RE.finditer(hlo_text):
        dims = m.group(1)
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        if elems >= view_elems:
            big.append((dims, elems))
    name = f"logical-view/{label}"
    if expect_materialized:
        if big:
            return AuditCheck(name, "pass",
                              f"reference path materialises the view as "
                              f"expected: gather result [{big[0][0]}]")
        return AuditCheck(name, "fail",
                          f"detector found no gather >= {view_elems} "
                          "elements in the reference executable — "
                          "threshold or HLO idiom drifted")
    if big:
        return AuditCheck(name, "fail",
                          f"{len(big)} materialised logical-view gather(s) "
                          f">= {view_elems} elements: "
                          f"{[d for d, _ in big[:3]]}")
    return AuditCheck(name, "pass",
                      f"no gather >= {view_elems} elements — pool pages "
                      "stream through the kernel's VMEM tiles")


def kernel_precheck_checks(cfg, swan, max_seq: int,
                           page_size: Optional[int] = None
                           ) -> List[AuditCheck]:
    """(iv): static Pallas grid and block-layout validation at the engine's
    shapes.  The SWAN decode and chunk-stats kernels share their tile and
    scale BlockSpecs, so one check covers both; ``page_size`` adds the
    paged-tile grid (sequence blocks = page-sized pool tiles gathered via
    scalar prefetch).  VMEM is the TPU compile's call
    (``tests/test_tpu_compile.py``)."""
    from repro.kernels.flash_prefill import flash_prefill as fp
    from repro.kernels.swan_decode import swan_decode as sd

    def fold(name: str, r: dict) -> AuditCheck:
        status = "fail" if r["errors"] else "pass"
        detail = "; ".join(r["errors"] + r["warnings"]) or "block rules hold"
        return AuditCheck(f"pallas-precheck/{name}", status, detail)

    out: List[AuditCheck] = []
    if swan is not None:
        common = dict(k_max=swan.k_max, dh=cfg.d_head,
                      quantized=getattr(swan, "quantize", False))
        out.append(fold("swan_decode",
                        sd.precheck(S=max(max_seq, 1), **common)))
        if page_size is not None:
            # paged-tile grid: sequence blocks are pool pages, so the block
            # is the page and S spans the per-seq page reservation
            out.append(fold("swan_decode@paged", sd.precheck(
                S=max(max_seq // page_size, 1) * page_size,
                block_s=page_size, paged=True, **common)))
    else:
        out.append(AuditCheck("pallas-precheck/swan_decode", "skip",
                              "no SWAN config on this engine"))
    r = fp.precheck(B=1, H=cfg.n_heads, Kv=cfg.n_kv_heads, Sq=max_seq,
                    Sk=max_seq, dh=cfg.d_head)
    status = "fail" if r["errors"] else "pass"
    detail = "; ".join(r["errors"] + r["warnings"]) or \
        f"vmem {r['vmem_bytes']} B"
    out.append(AuditCheck("pallas-precheck/flash_prefill", status, detail))
    return out


# ---------------------------------------------------------------------------
# Engine-driven audit
# ---------------------------------------------------------------------------

def audit_lowered(eng, label: str,
                  page_buckets: tuple = (None,)) -> List[AuditCheck]:
    """Checks (ii)+(iii) over the engine's AOT-lowered decode and chunk
    executables, one decode per requested page bucket."""
    out: List[AuditCheck] = []
    for pb in page_buckets:
        tag = f"{label}/decode" + (f"@pg{pb}" if pb is not None else "")
        try:
            txt = eng.lower_decode(page_bucket=pb).compile().as_text()
        except Exception as e:                         # pragma: no cover
            out.append(AuditCheck(f"lower/{tag}", "fail", repr(e)))
            continue
        out.append(transfer_check(txt, tag))
        out.append(collective_check(txt, tag))
    tag = f"{label}/chunk"
    try:
        txt = eng.lower_chunk().compile().as_text()
    except Exception as e:                             # pragma: no cover
        out.append(AuditCheck(f"lower/{tag}", "fail", repr(e)))
        return out
    out.append(transfer_check(txt, tag))
    out.append(collective_check(txt, tag))
    return out


def _drive(eng, prompts, max_new: int = 3) -> None:
    from repro.runtime.serve_engine import Request
    for u, p in enumerate(prompts):
        eng.submit(Request(uid=f"a{u}", tokens=p, max_new_tokens=max_new))
    while not eng.done:
        eng.step()


def _exec_count_checks(make_engine, label: str, prompts,
                       paged: bool) -> List[AuditCheck]:
    """(i): drive a mixed-length workload, bound the compile universe
    family by family via ``executable_census()`` (decode, prefill, the
    chunk family, BOTH admission inserts and pool-grow — the families the
    old decode/prefill-only counting missed), then re-run the identical
    workload and require zero new compiles anywhere."""
    out: List[AuditCheck] = []
    eng = make_engine()
    _drive(eng, prompts)
    try:
        census = eng.executable_census()
    except RuntimeError as e:
        return [AuditCheck(f"exec-count/{label}", "skip", str(e))]
    if paged:
        dec_bound = _log2_buckets(eng.pool.pages_per_seq)
    else:
        dec_bound = 1
    # chunk executables: one per (lane-width, chunk-len, prefix/table
    # bucket) triple, each axis O(log) by power-of-two bucketing
    chunk_bound = (_log2_buckets(eng.n_slots)
                   * _log2_buckets(eng.prefill_chunk or 1)
                   * _log2_buckets(eng.pool.pages_per_seq if paged
                                   else eng.max_seq))
    out.append(count_check(f"{label}/decode", census["decode"], dec_bound,
                           "decode executables"))
    out.append(count_check(f"{label}/prefill+chunk",
                           census["prefill"] + census["chunk_total"],
                           1 + chunk_bound, "prefill executables"))
    # admission inserts compile once per monolithic prompt pad bucket
    # (zero on the chunked path); pool-grow once per growth delta
    out.append(count_check(f"{label}/insert",
                           census["insert"] + census["insert_paged"],
                           _log2_buckets(eng.max_seq), "insert executables"))
    out.append(count_check(f"{label}/pool-grow", census["pool_grow_total"],
                           _log2_buckets(eng.max_seq), "grow executables"))
    _drive(eng, prompts)                       # identical workload again
    census2 = eng.executable_census()
    if census2 != census:
        out.append(AuditCheck(
            f"exec-count/{label}/steady-state", "fail",
            f"identical workload recompiled: {census} -> {census2}"))
    else:
        out.append(AuditCheck(f"exec-count/{label}/steady-state", "pass",
                              "no new executables on identical re-run "
                              "(full census stable)"))
    return out


def _mixed_workload(vocab: int, max_prompt_len: int, seed: int = 0):
    """Randomized mixed serve workload for the post-warmup zero-compile
    gate: prompt lengths spanning the chunk/page/prefix buckets, mixed
    per-request SWAN k, greedy and temperature lanes.  Token ids come from
    seeded numpy (NOT jnp slicing — building the workload itself must not
    compile anything)."""
    import numpy as np
    from repro.runtime.serve_engine import Request
    rng = np.random.RandomState(seed)
    reqs = []
    for u in range(8):
        plen = int(rng.randint(1, max_prompt_len + 1))
        reqs.append(Request(
            uid=f"w{u}",
            tokens=rng.randint(0, vocab, size=plen).tolist(),
            max_new_tokens=int(rng.randint(2, 5)),
            temperature=float(rng.choice([0.0, 0.0, 0.7, 1.3])),
            seed=int(rng.randint(0, 2 ** 16)),
            k=[None, 4, 8][int(rng.randint(0, 3))]))
    return reqs


def warmup_checks(make_engine, label: str, vocab: int,
                  max_prompt_len: int = 16) -> List[AuditCheck]:
    """The warmup contract, machine-checked:

    (a) coverage — after ``warmup()`` the executable census meets the
        static family enumeration bucket by bucket (any legally
        requestable bucket absent from the warmed family fails);
    (b) idempotency — a second ``warmup()`` compiles nothing;
    (c) zero steady-state compiles — a randomized mixed workload (mixed
        k, temperatures, prompt lengths spanning the buckets) triggers
        zero XLA compiles and leaves the census bit-identical.
    """
    from repro.obs import compile_events
    out: List[AuditCheck] = []
    eng = make_engine()
    try:
        report = eng.warmup(max_prompt_len=max_prompt_len)
    except Exception as e:
        return [AuditCheck(f"warmup/{label}", "fail", repr(e))]
    census, exp = report["census"], report["expected"]
    missing = [f"{fam}: {census[fam]} < {exp[fam]}"
               for fam in ("decode", "prefill", "insert", "insert_paged")
               if census[fam] < exp[fam]]
    missing += [f"chunk[{key}]: {census['chunk'].get(key, 0)} < {n}"
                for key, n in exp["chunk"].items()
                if census["chunk"].get(key, 0) < n]
    if missing:
        out.append(AuditCheck(
            f"warmup/{label}/coverage", "fail",
            "legally-requestable buckets absent from the warmed family: "
            + "; ".join(missing)))
    else:
        out.append(AuditCheck(
            f"warmup/{label}/coverage", "pass",
            f"census covers the enumerated family "
            f"({census['total']} executables, "
            f"{report['compiles']} compiles in "
            f"{report['warmup_ms']:.0f} ms)"))
    rep2 = eng.warmup(max_prompt_len=max_prompt_len)
    if rep2["compiles"]:
        out.append(AuditCheck(
            f"warmup/{label}/idempotent", "fail",
            f"second warmup compiled {rep2['compiles']} executable(s): "
            f"{[r for r in rep2['items'] if r['compiles']][:3]}"))
    else:
        out.append(AuditCheck(f"warmup/{label}/idempotent", "pass",
                              "second warmup compiled nothing"))
    reqs = _mixed_workload(vocab, max_prompt_len)
    c0 = compile_events.total()
    for r in reqs:
        eng.submit(r)
    while not eng.done:
        eng.step()
    dc = compile_events.total() - c0
    census2 = eng.executable_census()
    if dc or census2 != census:
        out.append(AuditCheck(
            f"warmup/{label}/zero-compile", "fail",
            f"post-warmup mixed workload compiled {dc} executable(s); "
            f"census {'stable' if census2 == census else 'DRIFTED'}"))
    else:
        out.append(AuditCheck(
            f"warmup/{label}/zero-compile", "pass",
            f"{len(reqs)}-request mixed workload: 0 compiles, census "
            "stable"))
    return out


def run_audit(smoke: bool = True) -> List[AuditCheck]:
    """Build the (bucket × paged × mesh) engine matrix on the smoke config
    and run every check.  Matrix: slab dp=1, paged dp=1, and paged dp=2
    when >= 2 devices are visible (CI forces 2 host devices)."""
    import jax
    import numpy as np
    from repro.configs import SwanConfig, get_smoke_config
    from repro.launch.io import make_batch
    from repro.models import get_model
    from repro.runtime.serve_engine import ServeEngine
    from repro.runtime.serve_loop import calibrate_swan

    cfg = get_smoke_config("llama3-8b").replace(dtype="float32",
                                                param_dtype="float32")
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    pj = calibrate_swan(api, cfg, params, make_batch(cfg, 2, 24, seed=3))
    swan = SwanConfig(k_max=cfg.d_head, buffer=4, mode="topk")
    max_seq = 64

    def prompts():
        rng = np.random.RandomState(0)
        return [rng.randint(0, cfg.vocab_size, size=n).tolist()
                for n in (5, 11, 19)]

    page_size = 16
    checks: List[AuditCheck] = kernel_precheck_checks(
        cfg, swan, max_seq, page_size=page_size)

    # xla = pure-JAX reference read path; pallas = kernel-backed decode
    # and chunk attention reads (interpret mode on CPU — the HLO contract
    # checks cover the same executables production would dispatch)
    variants = [("slab", dict(paged=False)),
                ("paged", dict(paged=True, page_size=page_size)),
                ("slab-pallas", dict(paged=False, use_pallas=True)),
                ("paged-pallas", dict(paged=True, page_size=page_size,
                                      use_pallas=True))]
    for label, kw in variants:
        def make_engine(kw=kw):
            return ServeEngine(cfg, params, swan=swan, projections=pj,
                               n_slots=2, max_seq=max_seq, prefill_chunk=8,
                               prefill_slots=2, **kw)
        if not kw.get("use_pallas"):
            # executable-count bounds are trace-shape properties, identical
            # across read-path implementations — drive them once per layout
            checks += _exec_count_checks(make_engine, label, prompts(),
                                         paged=kw.get("paged", False))
            # warmup contract: full-family coverage, idempotency, zero
            # compiles under a randomized mixed workload (also once per
            # layout — the family enumeration is read-path independent)
            checks += warmup_checks(make_engine, label, cfg.vocab_size)
        eng = make_engine()
        checks += audit_lowered(eng, label)
        if kw.get("paged"):
            # the materialised-logical-view detector: the kernel path must
            # gather pool pages in VMEM only; the reference path must trip
            # the detector (proving the threshold still matches the HLO)
            pb = 2
            view = eng.n_slots * cfg.n_kv_heads * pb * page_size * swan.k_max
            txt = eng.lower_decode(page_bucket=pb).compile().as_text()
            checks.append(logical_view_check(
                txt, f"{label}/decode@pg{pb}", view,
                expect_materialized=not kw.get("use_pallas")))

    if jax.device_count() >= 2:
        mesh = jax.make_mesh((2,), ("data",))
        eng = ServeEngine(cfg, params, swan=swan, projections=pj,
                          n_slots=2, max_seq=max_seq, prefill_chunk=8,
                          prefill_slots=2, paged=True, page_size=16,
                          mesh=mesh)
        checks += audit_lowered(eng, "paged-dp2")
    else:
        checks.append(AuditCheck("lower/paged-dp2", "skip",
                                 f"{jax.device_count()} device(s) visible"))
    return checks
