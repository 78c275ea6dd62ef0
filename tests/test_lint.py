"""swanlint: every Layer 1 rule fires on a seeded violation, stays quiet
on its negative twin, and honors (only) justified suppressions; the Layer
2 check helpers fail on seeded compiled artifacts; and the repo itself is
clean vs the committed baseline (the CI --check contract)."""
import os
import textwrap

import pytest

from repro.analysis.lint import (DEFAULT_BASELINE, load_baseline,
                                 make_report, new_findings, run_lint)
from repro.analysis.lint.audit import (collective_check, count_check,
                                       kernel_precheck_checks,
                                       transfer_check)
from repro.analysis.lint.rules import lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, rel="src/repro/runtime/engine.py"):
    return lint_source(textwrap.dedent(src), rel)


def _active(findings, rule=None):
    return [f for f in findings
            if not f.suppressed and (rule is None or f.rule == rule)]


# ---------------------------------------------------------------------------
# SWAN102 — host sync on the serve hot path
# ---------------------------------------------------------------------------

HOT_SRC = """
    import jax
    import numpy as np

    class Eng:
        def __init__(self):
            self._decode = jax.jit(lambda x: x)

        def step(self):
            logits = self._decode(1)
            x = float(logits)                     # tainted conversion
            self._decode(1).block_until_ready()   # sync primitive
            return self._fetch(logits)

        def _fetch(self, logits):
            return np.asarray(logits)             # taint crosses the call

        def _lane_tokens(self, logits):
            return np.asarray(logits)             # designed fetch point

        def offline(self, logits):
            return float(logits)                  # not reachable from step
"""


def test_host_sync_flags_reachable_syncs_only():
    hits = _active(_lint(HOT_SRC), "SWAN102")
    lines = {f.line for f in hits}
    assert len(hits) == 3, hits
    assert lines == {11, 12, 16}                 # float, sync, _fetch


def test_host_sync_untainted_conversion_ok():
    src = """
        import jax
        import numpy as np

        class Eng:
            def __init__(self):
                self._decode = jax.jit(lambda x: x)
                self.slot_pos = np.zeros((4,), np.int32)

            def step(self):
                i = int(self.slot_pos[0])     # host numpy, never tainted
                self._decode(i)
    """
    assert not _active(_lint(src), "SWAN102")


def test_host_sync_scoped_to_runtime():
    assert not _active(_lint(HOT_SRC, rel="src/repro/launch/driver.py"),
                       "SWAN102")


# ---------------------------------------------------------------------------
# SWAN103 — shape bucketing
# ---------------------------------------------------------------------------

BUCKET_SRC = """
    import numpy as np

    def build_decode(n):
        return np.zeros((4, 48), np.int32)

    def init_params(n):
        return np.zeros((4, 48), np.float32)   # not a dispatch builder
"""


def test_bucketing_flags_non_pow2_in_dispatch_builders():
    hits = _active(_lint(BUCKET_SRC), "SWAN103")
    assert len(hits) == 1 and "48" in hits[0].message


def test_bucketing_pow2_and_scope_negatives():
    ok = "import numpy as np\ndef build_decode(n):\n" \
         "    return np.zeros((4, 64), np.int32)\n"
    assert not _active(_lint(ok), "SWAN103")
    assert not _active(_lint(BUCKET_SRC, rel="src/repro/optim/adamw.py"),
                       "SWAN103")


# ---------------------------------------------------------------------------
# SWAN104 — spec completeness (cross-module)
# ---------------------------------------------------------------------------

def _spec_fixture(tmp_path, cache_src):
    (tmp_path / "src/repro/sharding").mkdir(parents=True)
    (tmp_path / "src/repro/core").mkdir(parents=True)
    (tmp_path / "src/repro/sharding/serve_specs.py").write_text(
        'KNOWN_LEAF_NAMES = ("k", "v")\n')
    (tmp_path / "src/repro/core/hybrid_cache.py").write_text(
        textwrap.dedent(cache_src))
    return lint_paths(str(tmp_path), ["src/repro/sharding/serve_specs.py",
                                      "src/repro/core/hybrid_cache.py"])


def test_spec_completeness_flags_rogue_leaf(tmp_path):
    hits = _active(_spec_fixture(tmp_path, """
        import jax.numpy as jnp

        def init_cache(L):
            d = {"k": jnp.zeros((L,)), "rogue": jnp.zeros((L,))}
            d["late"] = jnp.zeros((L,))
            return d
    """), "SWAN104")
    assert {f.message.split("'")[1] for f in hits} == {"rogue", "late"}


def test_spec_completeness_known_leaves_ok(tmp_path):
    assert not _active(_spec_fixture(tmp_path, """
        import jax.numpy as jnp

        def init_cache(L):
            return {"k": jnp.zeros((L,)), "v": jnp.zeros((L,))}
    """), "SWAN104")


def test_spec_completeness_suppressible(tmp_path):
    fs = _spec_fixture(tmp_path, """
        import jax.numpy as jnp

        def init_cache(L):
            return {
                # swanlint: disable=SWAN104 -- host-only scratch, never
                # crosses shard_map
                "rogue": jnp.zeros((L,)),
            }
    """)
    hits = [f for f in fs if f.rule == "SWAN104"]
    assert hits and all(f.suppressed for f in hits)


# ---------------------------------------------------------------------------
# SWAN105 — obs hygiene
# ---------------------------------------------------------------------------

OBS_SRC = """
    _step_counters = {}

    limits = {}     # not metric-named: fine
"""


def test_obs_flags_module_level_metric_dicts():
    hits = _active(_lint(OBS_SRC), "SWAN105")
    assert len(hits) == 1 and "_step_counters" in hits[0].message


def test_obs_allows_registry_module():
    assert not _active(_lint(OBS_SRC, rel="src/repro/obs/metrics.py"),
                       "SWAN105")


# ---------------------------------------------------------------------------
# Suppressions
# ---------------------------------------------------------------------------

def test_justified_suppression_covers_whole_statement():
    src = """
        import jax

        class Eng:
            def __init__(self):
                self._decode = jax.jit(lambda x: x)

            def step(self):
                out = self._decode(1)
                # swanlint: disable=SWAN102 -- test fixture: measured sync
                return [float(out),
                        float(out)]
    """
    fs = _lint(src)
    hits = [f for f in fs if f.rule == "SWAN102"]
    assert len(hits) == 2 and all(f.suppressed for f in hits)
    assert all("measured sync" in f.justification for f in hits)


def test_unjustified_suppression_is_a_finding_and_does_not_suppress():
    src = """
        import jax

        class Eng:
            def __init__(self):
                self._decode = jax.jit(lambda x: x)

            def step(self):
                out = self._decode(1)
                return float(out)  # swanlint: disable=SWAN102
    """
    fs = _lint(src)
    assert _active(fs, "SWAN100")
    assert _active(fs, "SWAN102")              # NOT suppressed


def test_unknown_rule_id_flagged():
    fs = _lint("x = 1  # swanlint: disable=SWAN999 -- nope\n")
    assert _active(fs, "SWAN100")


# ---------------------------------------------------------------------------
# Layer 2 check helpers on seeded artifacts
# ---------------------------------------------------------------------------

DIRTY_HLO = """\
HloModule dirty

ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128] parameter(0)
  %ar-start = (f32[8,128], f32[8,128]) all-reduce-start(%p0), replica_groups={}
  %cp = f32[8,128]{1,0:S(5)} copy(%p0)
  %tok = token[] after-all()
  %inf = ((f32[4]), token[]) infeed(%tok)
  ROOT %out = f32[8,128] add(%cp, %p0)
}
"""

CLEAN_HLO = """\
HloModule clean

ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128] parameter(0)
  ROOT %out = f32[8,128] add(%p0, %p0)
}
"""


def test_transfer_check_fails_on_seeded_host_traffic():
    c = transfer_check(DIRTY_HLO, "seeded")
    assert c.status == "fail"
    assert "host transfer" in c.detail and "unmatched" in c.detail
    assert transfer_check(CLEAN_HLO, "clean").status == "pass"


def test_collective_check_fails_on_undeclared_collective():
    assert collective_check(DIRTY_HLO, "seeded").status == "fail"
    assert collective_check(CLEAN_HLO, "clean").status == "pass"
    assert collective_check(DIRTY_HLO, "ok",
                            allowed=("all-reduce",)).status == "pass"


def test_count_check_bounds():
    assert count_check("x", 5, 3).status == "fail"
    assert count_check("x", 3, 3).status == "pass"
    assert count_check("x", -1, 3).status == "skip"


def test_kernel_precheck_fails_on_seeded_shapes():
    from repro.kernels.flash_prefill.flash_prefill import precheck as fp
    from repro.kernels.swan_decode.swan_decode import precheck as sd
    bad = sd(S=1000, k_max=256, dh=128)
    assert any("divisible" in e for e in bad["errors"])
    assert any("k_max" in e for e in bad["errors"])
    # the TPU block rule: a sub-S block is a sublane multiple, and a
    # quantized one (lane-major scale blocks) a lane multiple
    assert any("sublane" in e for e in sd(S=1020, k_max=64, dh=128,
                                           block_s=204)["errors"])
    lanes = sd(S=1024, k_max=64, dh=128, block_s=64, quantized=True)
    assert any("lane tile" in e for e in lanes["errors"])
    assert not sd(S=1024, k_max=64, dh=128, block_s=64)["errors"]
    assert not sd(S=64, k_max=64, dh=128, block_s=64, quantized=True,
                  paged=True)["errors"]
    assert not sd(S=1024, k_max=64, dh=128)["errors"]
    assert not sd(S=2048, k_max=64, dh=128, quantized=True)["errors"]
    assert any("Kv" in e or "H=" in e
               for e in fp(B=1, H=8, Kv=3, Sq=512, Sk=512, dh=128)["errors"])
    assert not fp(B=1, H=8, Kv=4, Sq=512, Sk=512, dh=128)["errors"]


def test_kernel_precheck_checks_smoke_config():
    from repro.configs import SwanConfig, get_smoke_config
    cfg = get_smoke_config("llama3-8b")
    checks = kernel_precheck_checks(
        cfg, SwanConfig(k_max=cfg.d_head, buffer=4, mode="topk"), 64)
    assert all(c.status == "pass" for c in checks), checks


# ---------------------------------------------------------------------------
# The repo gate itself
# ---------------------------------------------------------------------------

def test_repo_is_clean_vs_baseline():
    findings = run_lint(REPO)
    assert not _active(findings), [f.to_json() for f in _active(findings)]
    baseline = load_baseline(os.path.join(REPO, DEFAULT_BASELINE))
    assert baseline is not None, "bench_out/LINT_BASELINE.json missing"
    assert new_findings(findings, baseline) == []


def test_report_counts_and_fingerprint_stability():
    findings = run_lint(REPO)
    rep = make_report(findings)
    assert rep["counts"]["total"] == len(findings)
    assert rep["counts"]["active"] == 0
    # fingerprints are line-number-free: shifting a finding down a line
    # must not mint a new identity
    src = ("import jax\n\nclass E:\n    def __init__(self):\n"
           "        self._d = jax.jit(lambda x: x)\n"
           "    def step(self):\n        return float(self._d(1))\n")
    f1 = _active(lint_source(src, "src/repro/runtime/x.py"), "SWAN102")
    shifted = src.replace("import jax\n", "import jax\n# pad\n")
    f2 = _active(lint_source(shifted, "src/repro/runtime/x.py"), "SWAN102")
    assert f1[0].fingerprint == f2[0].fingerprint
    assert f1[0].line != f2[0].line
