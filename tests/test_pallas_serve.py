"""Pallas fast path on the serve hot path: kernel-vs-pure-JAX equivalence
(interpret mode — TPU semantics executed on CPU) and engine-level token
identity with ``use_pallas`` on vs off.

Covers the tentpole contract: slab and paged decode kernels, the paged
in-kernel page gather (trash pages, ring wrap, buffer-straddling
positions), the bulk-chunk prefill stats kernel, dispatch eligibility
resolution, and the ServeEngine threading (mixed per-request k,
temperature lanes, concurrent chunked prefill with dead lanes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SwanConfig, get_smoke_config
from repro.core import swan_attention as swa
from repro.kernels.dispatch import (pallas_decode_supported,
                                    resolve_interpret, resolve_use_pallas)
from repro.kernels.flash_prefill.swan_chunk import (
    swan_chunk_stats_paged_pallas, swan_chunk_stats_pallas)
from repro.kernels.swan_decode.ops import (swan_decode_attention_kernel_paged,
                                           swan_decode_paged_from_cache)


def _unique_idx(rng, shape, dh):
    k = shape[-1]
    flat = np.stack([rng.permutation(dh)[:k]
                     for _ in range(int(np.prod(shape[:-1])))])
    return jnp.asarray(flat.reshape(shape), jnp.int8)


# ---------------------------------------------------------------------------
# Dispatch policy
# ---------------------------------------------------------------------------

def test_resolve_defaults_follow_backend():
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_use_pallas(None) == on_tpu
    assert resolve_interpret(True) and not resolve_interpret(False)
    assert resolve_use_pallas(True) and not resolve_use_pallas(False)


def test_pallas_decode_supported_gates():
    assert not pallas_decode_supported(None)
    assert pallas_decode_supported(SwanConfig(k_max=8, buffer=4, mode="topk"))
    assert not pallas_decode_supported(
        SwanConfig(k_max=8, buffer=4, mode="truncate"))
    assert not pallas_decode_supported(
        SwanConfig(k_max=8, buffer=0, mode="topk"))


# ---------------------------------------------------------------------------
# Paged decode kernel vs the pure-JAX logical-view path
# ---------------------------------------------------------------------------

def _paged_fixture(rng, *, B, Kv, ps, n_log, dh, k, b, quant=False):
    """Pool + table + ring with per-sequence positions chosen so lanes mix
    ring wrap, partially-filled pages, and trash-backed table tails."""
    n_pages = B * n_log + 1
    def side():
        s = {"vals": (jnp.asarray(rng.integers(-127, 128,
                                               (n_pages, Kv, ps, k)),
                                  jnp.int8) if quant else
                      jnp.asarray(rng.standard_normal((n_pages, Kv, ps, k)),
                                  jnp.float32)),
             "idx": _unique_idx(rng, (n_pages, Kv, ps, k), dh)}
        if quant:
            s["scale"] = jnp.asarray(rng.random((n_pages, Kv, ps)) * 0.1
                                     + 0.01, jnp.float32)
        return s
    # per-lane positions: lane 0 full view, later lanes shorter prefixes
    # (their unmapped tail entries point at the trash page 0)
    pos = np.array([n_log * ps + b - 1 - 7 * i for i in range(B)], np.int32)
    sp = np.maximum(pos + 1 - b, 0)
    tab = np.zeros((B, n_log), np.int32)
    for lane in range(B):
        n_mapped = min(n_log, -(-int(sp[lane]) // ps) or 1)
        tab[lane, :n_mapped] = 1 + lane * n_log + np.arange(n_mapped)
    bpos = np.zeros((B, b), np.int32)
    for lane in range(B):
        for p in range(int(pos[lane]) - b + 1, int(pos[lane]) + 1):
            bpos[lane, p % b] = p          # ring wrap: slot = pos % b
    cache = {
        "pool": {"k": side(), "v": side()},
        "buf_k": jnp.asarray(rng.standard_normal((B, Kv, b, dh)),
                             jnp.float32),
        "buf_v": jnp.asarray(rng.standard_normal((B, Kv, b, dh)),
                             jnp.float32),
        "buf_pos": jnp.asarray(bpos),
    }
    return cache, jnp.asarray(tab), jnp.asarray(pos)


@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_kernel_matches_pure(quant):
    rng = np.random.default_rng(5)
    B, Kv, G, dh, ps, n_log, k, b = 3, 2, 2, 32, 16, 4, 8, 8
    cfg = get_smoke_config("llama3-8b").replace(
        n_kv_heads=Kv, n_heads=Kv * G, d_head=dh, dtype="float32")
    swan = SwanConfig(k_max=k, buffer=b, mode="topk", quantize=quant)
    cache, tab, pos = _paged_fixture(rng, B=B, Kv=Kv, ps=ps, n_log=n_log,
                                     dh=dh, k=k, b=b, quant=quant)
    q = jnp.asarray(rng.standard_normal((B, Kv, G, dh)), jnp.float32)
    o_ref = swa.swan_decode_attention_paged(q, cache, swan, cfg, pos, tab)
    o_ker = swan_decode_paged_from_cache(q, cache, swan, pos, tab)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=5e-5)
    # jitted wrapper (the form the serve decode body uses)
    o_jit = swan_decode_attention_kernel_paged(q, cache, swan, cfg, pos, tab)
    np.testing.assert_allclose(np.asarray(o_jit), np.asarray(o_ref),
                               atol=5e-5)


# ---------------------------------------------------------------------------
# Bulk-chunk prefill stats kernel vs _sparse_stats_bulk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True])
def test_chunk_stats_kernel_matches_bulk(quant):
    rng = np.random.default_rng(6)
    B, Kv, Q, dh, S, k = 2, 2, 8, 32, 48, 8
    swan = SwanConfig(k_max=k, buffer=4, mode="topk", quantize=quant)
    def side():
        s = {"vals": (jnp.asarray(rng.integers(-127, 128, (B, Kv, S, k)),
                                  jnp.int8) if quant else
                      jnp.asarray(rng.standard_normal((B, Kv, S, k)),
                                  jnp.float32)),
             "idx": _unique_idx(rng, (B, Kv, S, k), dh)}
        if quant:
            s["scale"] = jnp.asarray(rng.random((B, Kv, S)) * 0.1 + 0.01,
                                     jnp.float32)
        return s
    ks_, vs_ = side(), side()
    q = jnp.asarray(rng.standard_normal((B, Kv, Q, dh)), jnp.float32)
    sp = jnp.asarray([S - 5, 0], jnp.int32)       # lane 1: empty prefix
    m_r, l_r, o_r = swa._sparse_stats_bulk(q, ks_, vs_, swan, sp, dh)
    m_k, l_k, o_k = swan_chunk_stats_pallas(
        q, ks_["vals"], ks_["idx"], vs_["vals"], vs_["idx"], sp,
        k_scale=ks_.get("scale"), v_scale=vs_.get("scale"), block_s=16)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=5e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), atol=5e-5)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=5e-5)


def test_chunk_stats_paged_kernel_matches_bulk_on_view():
    rng = np.random.default_rng(7)
    B, Kv, Q, dh, ps, n_log, k, b = 2, 2, 6, 32, 16, 3, 8, 8
    swan = SwanConfig(k_max=k, buffer=b, mode="topk")
    cache, tab, pos = _paged_fixture(rng, B=B, Kv=Kv, ps=ps, n_log=n_log,
                                     dh=dh, k=k, b=b)
    sp = jnp.maximum(pos + 1 - b, 0)
    q = jnp.asarray(rng.standard_normal((B, Kv, Q, dh)), jnp.float32)
    view = swa.paged_logical_view(cache, tab)
    m_r, l_r, o_r = swa._sparse_stats_bulk(q, view["k"], view["v"], swan,
                                           sp, dh)
    pk, pv = cache["pool"]["k"], cache["pool"]["v"]
    m_k, l_k, o_k = swan_chunk_stats_paged_pallas(
        q, pk["vals"], pk["idx"], pv["vals"], pv["idx"], sp, tab)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=5e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), atol=5e-5)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=5e-5)


# ---------------------------------------------------------------------------
# ServeEngine: use_pallas on == off, token for token
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_serve():
    from repro.launch.io import make_batch
    from repro.models import get_model
    from repro.runtime.serve_loop import calibrate_swan

    cfg = get_smoke_config("llama3-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, dtype="float32", param_dtype="float32")
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    pj = calibrate_swan(api, cfg, params, make_batch(cfg, 2, 32, seed=3))
    absorbed = api.absorb(params, cfg, pj)
    return cfg, absorbed, pj, make_batch


def _requests(cfg, make_batch, n=5):
    from repro.runtime.serve_engine import Request
    out = []
    for i in range(n):
        plen = max(4, 20 - 3 * (i % 4))           # mixed lengths -> dead lanes
        toks = make_batch(cfg, 1, plen, seed=100 + i)["tokens"][0]
        out.append(Request(uid=f"r{i}", tokens=[int(t) for t in toks],
                           max_new_tokens=12,     # > 2*buffer: ring wraps
                           temperature=0.7 if i % 3 == 0 else 0.0, seed=i,
                           k=[8, 4, 2][i % 3]))
    return out


@pytest.mark.parametrize("paged", [False, True])
def test_engine_pallas_token_identity(tiny_serve, paged):
    from repro.runtime.serve_engine import ServeEngine

    cfg, absorbed, pj, make_batch = tiny_serve
    swan = SwanConfig(k_max=8, buffer=4, mode="topk")
    kw = dict(paged=True, page_size=16) if paged else {}

    from repro.obs import clear_spans, record_spans, recorded_spans

    def run(use_pallas):
        eng = ServeEngine(cfg, absorbed, swan=swan, projections=pj,
                          max_seq=64, n_slots=3, prefill_chunk=8,
                          prefill_slots=2, use_pallas=use_pallas, **kw)
        clear_spans()
        record_spans(True)
        try:
            comps = eng.run(_requests(cfg, make_batch))
        finally:
            record_spans(False)
        eng.spans = recorded_spans()
        clear_spans()
        return {c.uid: c.tokens for c in comps}, eng

    def dispatch_spans(eng, kind, kernel):
        return [s for s in eng.spans if s.name == f"engine.dispatch.{kind}"
                and s.stats["kernel"] == kernel]

    t_ref, e_ref = run(False)
    t_pal, e_pal = run(True)
    assert e_pal.use_pallas and not e_ref.use_pallas
    assert t_ref == t_pal
    # one chunk + one decode dispatch per step, independent of the backend
    assert e_pal.dispatches == e_ref.dispatches
    # every hot-path dispatch on the pallas engine went through the kernels
    for kind in ("decode", "chunk"):
        assert e_pal.metrics.value("serve_pallas_dispatch_total",
                                   kind=kind) == e_pal.dispatches[kind]
        assert len(dispatch_spans(e_pal, kind, "pallas")) \
            == e_pal.dispatches[kind] > 0
        assert e_ref.metrics.value("serve_pallas_dispatch_total",
                                   kind=kind) == 0
        assert len(dispatch_spans(e_ref, kind, "xla")) \
            == e_ref.dispatches[kind] > 0


def test_engine_use_pallas_rejects_non_kernel_path(tiny_serve):
    from repro.runtime.serve_engine import ServeEngine

    cfg, absorbed, pj, _ = tiny_serve
    with pytest.raises(ValueError, match="use_pallas"):
        ServeEngine(cfg, absorbed, max_seq=64, n_slots=2, use_pallas=True)
    with pytest.raises(ValueError, match="use_pallas"):
        ServeEngine(cfg, absorbed,
                    swan=SwanConfig(k_max=8, buffer=4, mode="truncate"),
                    projections=pj, max_seq=64, n_slots=2, use_pallas=True)
