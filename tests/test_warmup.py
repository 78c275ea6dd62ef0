"""Executable-family warmup (``repro.runtime.warmup``) and the overlapped
decode token fetch.

Contracts locked in here:

* ``ServeEngine.warmup()`` pre-compiles the engine's complete executable
  family — a randomized post-warmup workload (mixed prompt lengths,
  per-request k, greedy and temperature lanes, chunked prefill) triggers
  ZERO new XLA compiles on both the slab and the paged engine, with a
  stable ``executable_census()``.  Compiles are counted with the
  process-global ``repro.obs.compile_events`` listener, which also sees
  eager one-off executables the jit caches cannot.
* warmup is idempotent, token-transparent (warmed == never-warmed output)
  and covers at least the statically enumerated expected family.
* ``async_fetch=True`` overlaps the decode token transfer with host
  scheduling and is token-, step- and dispatch-identical to sync.
* decode/prefill/insert donate the serve state: after a step the previous
  state's device buffers are deleted, so steady-state decode allocates no
  second cache copy.
* pool growth on the paged engine re-warms the refreshed executable
  family (the pool leaf shape changes stale every state-keyed
  executable).

Engines warm a deliberately small family (tiny model, short ``max_seq``,
``max_prompt_len`` trim) and are shared module-wide to keep runtime sane.
"""
import jax
import numpy as np
import pytest

from repro.configs import SwanConfig, get_smoke_config
from repro.launch.io import make_batch
from repro.models import get_model
from repro.obs import compile_events
from repro.runtime.serve_engine import Request, ServeEngine
from repro.runtime.serve_loop import calibrate_swan

MAX_SEQ = 32
N_SLOTS = 2
CHUNK = 8
PREFILL_SLOTS = 2
PROMPT_CAP = 8
PAGE_SIZE = 16


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, dtype="float32", param_dtype="float32")
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    pj = calibrate_swan(api, cfg, params, make_batch(cfg, 2, 24, seed=3))
    absorbed = api.absorb(params, cfg, pj)
    swan = SwanConfig(k_max=8, buffer=4, mode="topk")
    return cfg, absorbed, swan, pj


def _engine(setup, **kw):
    cfg, absorbed, swan, pj = setup
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("n_slots", N_SLOTS)
    return ServeEngine(cfg, absorbed, swan=swan, projections=pj, **kw)


def _chunked(setup, **kw):
    return _engine(setup, prefill_chunk=CHUNK, prefill_slots=PREFILL_SLOTS,
                   **kw)


def _workload(cfg, seed=0, n=6):
    """Randomized mixed workload with every prompt prebuilt — building a
    prompt via make_batch traces eager slice ops, which must happen BEFORE
    any compile-count snapshot."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(1, PROMPT_CAP + 1))
        toks = [int(t) for t in
                make_batch(cfg, 1, plen, seed=300 + i)["tokens"][0]]
        reqs.append(Request(
            uid=f"req{i}", tokens=toks,
            max_new_tokens=int(rng.randint(2, 5)),
            temperature=float(rng.choice([0.0, 0.0, 0.7, 1.3])),
            seed=int(rng.randint(0, 2**31 - 1)),
            k=[None, 4, 8][int(rng.randint(0, 3))]))
    return reqs


def _clone(reqs):
    return [Request(uid=r.uid, tokens=list(r.tokens),
                    max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature, seed=r.seed, k=r.k)
            for r in reqs]


@pytest.fixture(scope="module")
def warmed_slab(setup):
    eng = _chunked(setup)
    report = eng.warmup(max_prompt_len=PROMPT_CAP)
    return eng, report


@pytest.fixture(scope="module")
def warmed_paged(setup):
    eng = _chunked(setup, paged=True, page_size=PAGE_SIZE)
    report = eng.warmup(max_prompt_len=PROMPT_CAP)
    return eng, report


# ---------------------------------------------------------------------------
# Census + family coverage
# ---------------------------------------------------------------------------

def test_census_requires_jit(setup):
    eng = _engine(setup, jit=False)
    with pytest.raises(RuntimeError, match="jit"):
        eng.executable_census()
    # the cache-size properties degrade to 0 (not a silent -1)
    assert eng.decode_cache_size == 0
    assert eng.prefill_cache_size == 0


def test_warmup_requires_jit(setup):
    eng = _engine(setup, jit=False)
    with pytest.raises(RuntimeError, match="jit"):
        eng.warmup()


def test_warmup_covers_expected_family(warmed_slab):
    eng, report = warmed_slab
    census, expected = report["census"], report["expected"]
    assert census["decode"] >= expected["decode"]
    assert census["prefill"] >= expected["prefill"]
    assert census["insert"] >= expected["insert"]
    for bucket, n in expected["chunk"].items():
        assert census["chunk"].get(bucket, 0) >= n, (bucket, census["chunk"])
    assert report["compiles"] > 0
    assert report["warmup_ms"] > 0
    # warmup stamps its gauge and phase-labelled compile counter
    assert eng.metrics.value("serve_warmup_ms") > 0
    assert eng.metrics.value("serve_compile_total", phase="warmup",
                             kind="chunk") > 0


def test_warmup_covers_paged_family(warmed_paged):
    eng, report = warmed_paged
    census, expected = report["census"], report["expected"]
    assert census["decode"] >= expected["decode"]
    # paged decode buckets by page-table prefix width (pow2 family)
    assert expected["decode"] >= 2
    for bucket, n in expected["chunk"].items():
        assert census["chunk"].get(bucket, 0) >= n


def test_warmup_idempotent(warmed_slab, warmed_paged):
    for eng, _ in (warmed_slab, warmed_paged):
        census0 = eng.executable_census()
        rep2 = eng.warmup(max_prompt_len=PROMPT_CAP)
        assert rep2["compiles"] == 0, [
            r for r in rep2["items"] if r["compiles"]]
        assert eng.executable_census() == census0


# ---------------------------------------------------------------------------
# Zero steady-state compiles
# ---------------------------------------------------------------------------

def _assert_zero_compile_workload(eng, cfg):
    reqs = _workload(cfg)
    census0 = eng.executable_census()
    c0 = compile_events.total()
    comps = eng.run(reqs)
    assert compile_events.total() - c0 == 0
    assert eng.executable_census() == census0
    assert len(comps) == len(reqs)
    return comps


def test_zero_compiles_after_warmup_slab(warmed_slab, setup):
    cfg = setup[0]
    comps = _assert_zero_compile_workload(warmed_slab[0], cfg)
    # warmup is token-transparent: a never-warmed engine on the same
    # workload produces identical output
    fresh = _chunked(setup)
    fresh_comps = fresh.run(_clone(_workload(cfg)))
    assert ({c.uid: c.tokens for c in comps}
            == {c.uid: c.tokens for c in fresh_comps})


def test_zero_compiles_after_warmup_paged(warmed_paged, setup):
    _assert_zero_compile_workload(warmed_paged[0], setup[0])


# ---------------------------------------------------------------------------
# Async token fetch
# ---------------------------------------------------------------------------

def test_async_fetch_identical_to_sync(setup):
    cfg = setup[0]
    reqs = _workload(cfg, seed=7)
    e_sync = _chunked(setup)
    e_async = _chunked(setup, async_fetch=True)
    c1 = e_sync.run(_clone(reqs))
    c2 = e_async.run(_clone(reqs))
    assert e_async.done and e_async._pending is None
    assert ({c.uid: c.tokens for c in c1}
            == {c.uid: c.tokens for c in c2})
    assert ({c.uid: (c.admitted_step, c.first_token_step, c.finished_step)
             for c in c1}
            == {c.uid: (c.admitted_step, c.first_token_step, c.finished_step)
                for c in c2})
    # the overlap changes WHEN tokens are resolved, not what is dispatched
    assert e_sync.dispatches == e_async.dispatches


def test_async_fetch_greedy_only(setup):
    cfg = setup[0]
    reqs = [Request(uid=f"g{i}",
                    tokens=[int(t) for t in
                            make_batch(cfg, 1, 4 + i, seed=i)["tokens"][0]],
                    max_new_tokens=3) for i in range(3)]
    e_sync = _engine(setup)
    e_async = _engine(setup, async_fetch=True)
    t1 = {c.uid: c.tokens for c in e_sync.run(_clone(reqs))}
    t2 = {c.uid: c.tokens for c in e_async.run(_clone(reqs))}
    assert t1 == t2


# ---------------------------------------------------------------------------
# Buffer donation
# ---------------------------------------------------------------------------

def _backend_donates():
    x = jax.numpy.ones((4,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


@pytest.mark.parametrize("paged", [False, True])
def test_decode_donates_all_state_leaves(setup, paged):
    if not _backend_donates():
        pytest.skip("backend does not honour buffer donation")
    cfg = setup[0]
    kw = dict(paged=True, page_size=PAGE_SIZE) if paged else {}
    eng = _engine(setup, **kw)
    eng.submit(Request(uid="a", tokens=_workload(cfg)[0].tokens,
                       max_new_tokens=4))
    eng.step()                      # admission (prefill + insert)
    leaves = jax.tree_util.tree_leaves(eng.state)
    eng.step()                      # pure decode: state donated in full
    assert all(leaf.is_deleted() for leaf in leaves), (
        "decode left stale state buffers alive — a donation leaf was missed")


def test_chunked_prefill_donates_state(setup):
    if not _backend_donates():
        pytest.skip("backend does not honour buffer donation")
    cfg = setup[0]
    eng = _chunked(setup)
    long_prompt = [int(t) for t in
                   make_batch(cfg, 1, 3 * CHUNK, seed=11)["tokens"][0]]
    eng.submit(Request(uid="a", tokens=long_prompt, max_new_tokens=2))
    eng.step()                      # first chunk lands
    leaves = jax.tree_util.tree_leaves(eng.state)
    eng.step()                      # next chunk: state donated through
    assert all(leaf.is_deleted() for leaf in leaves)


# ---------------------------------------------------------------------------
# Pool growth re-warms
# ---------------------------------------------------------------------------

def test_pool_growth_rewarms_family(setup):
    cfg = setup[0]
    # tiny pool: the workload's generated tokens force at least one grow
    eng = _engine(setup, paged=True, page_size=4, n_pages=4, pool_grow=True)
    eng.warmup(max_prompt_len=PROMPT_CAP)
    assert eng._warmed
    reqs = [Request(uid=f"r{i}",
                    tokens=[int(t) for t in
                            make_batch(cfg, 1, 8, seed=40 + i)["tokens"][0]],
                    max_new_tokens=8, k=4) for i in range(2)]
    comps = eng.run(reqs)
    assert len(comps) == 2
    census = eng.executable_census()
    assert census["pool_grow_total"] >= 1
    # the post-growth re-warm restored full coverage: a same-shape rerun
    # compiles nothing even though every state-keyed executable was staled
    c0 = compile_events.total()
    eng.run([Request(uid="again", tokens=list(reqs[0].tokens),
                     max_new_tokens=8, k=4)])
    assert compile_events.total() - c0 == 0


# ---------------------------------------------------------------------------
# Persistent compile cache location
# ---------------------------------------------------------------------------

def _record_config(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_compilation_cache_uses_env_dir(monkeypatch, tmp_path):
    from repro.runtime.warmup import enable_compilation_cache
    seen = _record_config(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() == str(tmp_path)
    assert seen["jax_compilation_cache_dir"] == str(tmp_path)
    assert seen["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_compilation_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    import os
    from repro.runtime.warmup import DEFAULT_CACHE_DIR, \
        enable_compilation_cache
    seen = _record_config(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compilation_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == str(DEFAULT_CACHE_DIR) == os.path.join(repo, ".jax_cache")
    assert seen["jax_compilation_cache_dir"] == path
    assert enable_compilation_cache() == path          # same path every call
    with open(os.path.join(repo, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_persistent_cache_events_are_counted():
    import jax.monitoring
    compile_events.install()
    c0 = compile_events.persistent_cache()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    c1 = compile_events.persistent_cache()
    assert (c1["hits"] - c0["hits"], c1["writes"] - c0["writes"]) == (2, 1)


def test_compile_seconds_follow_compile_events():
    compile_events.install()
    n0, s0 = compile_events.total(), compile_events.seconds()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert compile_events.total() - n0 >= 1
    assert compile_events.seconds() - s0 > 0.0
