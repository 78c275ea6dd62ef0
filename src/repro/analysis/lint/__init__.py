"""swanlint — repo-invariant static analysis for the SWAN serve stack.

Two layers:

* **Layer 1 (``repro.analysis.lint.rules``)** — stdlib-``ast`` rules
  that machine-check the ROADMAP standing constraints at review time:
  no host syncs on the serve hot path (SWAN102), power-of-two shape
  bucketing in dispatch builders (SWAN103), sharding-spec completeness
  for serve-state leaves (SWAN104), and MetricsRegistry-only
  observability (SWAN105).
  Dependency-free: no jax import, runs anywhere.
* **Layer 2 (``repro.analysis.lint.audit``)** — a compiled-artifact
  auditor that lowers the engine's chunk/decode executables for a
  (bucket × paged × mesh) matrix, parses post-optimization HLO through
  ``repro.analysis.hlo``, and asserts the perf contract: bounded
  executable counts (one per step shape), zero host transfers inside
  dispatch bodies, an empty collective inventory (the serve path is
  lane-local by contract), and Pallas grid/block-layout prechecks for
  the ``swan_decode`` / ``swan_chunk`` / ``flash_prefill`` kernels.

CLI: ``python -m repro.analysis.lint [--check] [--audit-smoke] ...`` —
see ``docs/static_analysis.md`` for the rule catalogue, suppression
policy and baseline workflow.  The committed clean baseline lives at
``bench_out/LINT_BASELINE.json``; ``--check`` fails only on findings
NOT in the baseline, so diffs surface new violations exactly.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Optional

from repro.analysis.lint.rules import (Finding, RULES, lint_paths,
                                       lint_source)

__all__ = ["Finding", "RULES", "lint_source", "lint_paths",
           "collect_files", "run_lint", "make_report", "load_baseline",
           "new_findings", "DEFAULT_BASELINE"]

DEFAULT_BASELINE = os.path.join("bench_out", "LINT_BASELINE.json")

# what Layer 1 walks by default: library code + the benchmark/example
# drivers (tests are exempt — they intentionally seed violations)
DEFAULT_SCAN_DIRS = ("src/repro", "benchmarks", "examples")


def collect_files(root: str,
                  dirs: Iterable[str] = DEFAULT_SCAN_DIRS) -> List[str]:
    out: List[str] = []
    for d in dirs:
        base = os.path.join(root, d)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [x for x in dirnames if x != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    out.append(os.path.relpath(os.path.join(dirpath, fn),
                                               root))
    return sorted(out)


def run_lint(root: str,
             dirs: Iterable[str] = DEFAULT_SCAN_DIRS) -> List[Finding]:
    return lint_paths(root, collect_files(root, dirs))


def make_report(findings: List[Finding],
                audit_checks: Optional[List] = None,
                baseline: Optional[Dict] = None) -> Dict:
    """JSON-serializable report: full finding list, active/suppressed
    split, new-vs-baseline diff, optional Layer 2 results."""
    new = new_findings(findings, baseline)
    rep: Dict = {
        "tool": "swanlint",
        "version": 1,
        "rules": RULES,
        "findings": [f.to_json() for f in findings],
        "counts": {
            "total": len(findings),
            "active": sum(not f.suppressed for f in findings),
            "suppressed": sum(f.suppressed for f in findings),
            "new": len(new),
        },
        "new_findings": [f.to_json() for f in new],
    }
    if audit_checks is not None:
        rep["audit"] = [c.to_json() for c in audit_checks]
        rep["counts"]["audit_failures"] = sum(
            c.status == "fail" for c in audit_checks)
    return rep


def load_baseline(path: str) -> Optional[Dict]:
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def new_findings(findings: List[Finding],
                 baseline: Optional[Dict]) -> List[Finding]:
    """Active findings whose fingerprint is not in the baseline.
    Fingerprints are line-number-free (rule|path|normalized snippet), so
    unrelated edits above a known finding don't resurface it."""
    active = [f for f in findings if not f.suppressed]
    if not baseline:
        return active
    known = {f.get("fingerprint") for f in baseline.get("findings", [])}
    return [f for f in active if f.fingerprint not in known]
