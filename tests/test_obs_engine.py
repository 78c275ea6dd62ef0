"""Observability x ServeEngine integration: the contract the subsystem
must keep is that it OBSERVES the engine without participating in it —
metrics/tracing on vs off produces identical tokens and identical dispatch
counts — plus per-request latency accounting (``first_token_step`` set
exactly once, inter-token gaps matching the trace) and byte-accounting
consistency (``cache_report()`` == the per-step gauges; both read
``ServeEngine._cache_bytes()``)."""
import jax
import numpy as np
import pytest

from repro.configs import SwanConfig, get_smoke_config
from repro.launch.io import make_batch
from repro.models import get_model
from repro.obs import (EventTrace, MetricsRegistry, clear_spans,
                       compile_events, parse_prometheus, record_spans,
                       recorded_spans)
from repro.runtime.serve_engine import Request, ServeEngine
from repro.runtime.serve_loop import ServeSession, calibrate_swan

PAGE = 16


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3-8b").replace(dtype="float32",
                                                param_dtype="float32")
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    calib = make_batch(cfg, 2, 24, seed=3)
    pj = calibrate_swan(api, cfg, params, calib)
    absorbed = api.absorb(params, cfg, pj)
    return cfg, api, params, absorbed, pj


def _prompt(cfg, n, seed=0):
    return np.asarray(make_batch(cfg, 1, n, seed=seed)["tokens"][0]).tolist()


def _swan(**kw):
    kw.setdefault("k_max", 8)
    kw.setdefault("buffer", 4)
    kw.setdefault("mode", "topk")
    return SwanConfig(**kw)


_SPEC = [(6, 8, 8, 0), (11, 5, 4, 0), (17, 9, None, 2), (9, 6, 2, 4)]


def _mixed_trace(cfg):
    """Mixed prompt lengths, mixed per-request k, staggered arrivals."""
    return [Request(uid=f"m{i}", tokens=_prompt(cfg, n, seed=20 + i),
                    max_new_tokens=g, k=k, arrival_step=a)
            for i, (n, g, k, a) in enumerate(_SPEC)]


_ENGINE_KW = dict(max_seq=64, n_slots=2, paged=True, page_size=PAGE,
                  prefill_chunk=8, prefill_slots=2)


@pytest.fixture(scope="module")
def obs_run(setup):
    """One drained, fully instrumented engine on the full serving feature
    surface: paged pool + chunked + batched concurrent prefill."""
    cfg, api, params, absorbed, pj = setup
    trace = EventTrace()
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      trace=trace, **_ENGINE_KW)
    comps = eng.run(_mixed_trace(cfg))
    return cfg, eng, trace, comps


# ---------------------------------------------------------------------------
# The contract: observation never participates
# ---------------------------------------------------------------------------

def test_metrics_on_vs_off_token_and_dispatch_identity(setup, obs_run):
    """The tentpole regression gate: the fully instrumented engine and a
    metrics=False, trace=None engine produce IDENTICAL tokens, dispatch
    counts and step counts on the same trace."""
    cfg, api, params, absorbed, pj = setup
    _, on, _, on_comps = obs_run
    off = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      metrics=False, **_ENGINE_KW)
    off_comps = off.run(_mixed_trace(cfg))
    assert {c.uid: c.tokens for c in off_comps} \
        == {c.uid: c.tokens for c in on_comps}
    assert dict(off.dispatches) == dict(on.dispatches)
    assert off.step_count == on.step_count
    assert [(c.uid, c.admitted_step, c.first_token_step, c.finished_step)
            for c in off_comps] \
        == [(c.uid, c.admitted_step, c.first_token_step, c.finished_step)
            for c in on_comps]
    # off really is off: the null registry never accumulates state
    assert not off.metrics.enabled
    assert off.metrics.snapshot() == {"metrics": {}}
    # spans recorded vs not: the same tokens, dispatches and steps
    clear_spans()
    record_spans(True)
    try:
        spanned = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                              metrics=False, **_ENGINE_KW)
        sp_comps = spanned.run(_mixed_trace(cfg))
    finally:
        record_spans(False)
    spans = recorded_spans()
    clear_spans()
    assert {c.uid: c.tokens for c in sp_comps} \
        == {c.uid: c.tokens for c in on_comps}
    assert dict(spanned.dispatches) == dict(on.dispatches)
    assert spanned.step_count == on.step_count
    names = [s.name for s in spans]
    assert names.count("engine.step") == on.step_count
    assert names.count("engine.dispatch.decode") == on.dispatches["decode"]
    assert names.count("engine.dispatch.chunk") == on.dispatches["chunk"]
    assert names.count("engine.submit") == len(_SPEC)


def test_step_children_cover_the_step(setup):
    """Every span of a step nests under its ``engine.step``; the named
    phases follow one another in the order ``step()`` runs them."""
    cfg, api, params, absorbed, pj = setup
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      metrics=False, async_fetch=True, **_ENGINE_KW)
    for r in _mixed_trace(cfg):
        eng.submit(r)
    clear_spans()
    record_spans(True)
    try:
        while not eng.done:
            eng.step()
    finally:
        record_spans(False)
    spans = recorded_spans()
    clear_spans()
    steps = {s.id: s for s in spans if s.name == "engine.step"}
    assert len(steps) == eng.step_count
    order = ["engine.resolve", "engine.apply", "engine.admit",
             "engine.prefill", "engine.decode", "engine.fetch",
             "engine.gauges"]
    for st in steps.values():
        kids = sorted((s for s in spans if s.parent == st.id),
                      key=lambda s: s.t0_ns)
        assert [k.name for k in kids] == [n for n in order
                                          if n in {k.name for k in kids}]
        assert all(k.step == st.step for k in kids)
        assert all(st.t0_ns <= k.t0_ns <= k.t1_ns <= st.t1_ns for k in kids)
    by_id = {s.id: s for s in spans}
    resolves = [s for s in spans if s.name == "engine.resolve"]
    assert resolves
    for r in resolves:
        # the wait ends where sampling starts, inside the resolve
        (smp,) = [s for s in spans if s.parent == r.id]
        assert smp.name == "engine.sample"
        assert r.t0_ns <= smp.t0_ns <= smp.t1_ns <= r.t1_ns
    for s in spans:
        if s.name.startswith("engine.dispatch."):
            assert by_id[s.parent].name == {
                "chunk": "engine.prefill",
                "decode": "engine.decode"}[s.name.rsplit(".", 1)[1]]


def test_decode_grid_counts_match_hand_count(setup):
    """``grid_pages`` / ``live_pages`` on the decode dispatch span: two
    monolithic admissions of 40 and 10 prompt tokens, ring 4, pages of
    16.  The first decode winnows positions up to 40 - 4 = 36 (37 tokens,
    3 pages) and 10 - 4 = 6 (7 tokens, 1 page): 4 live pages; the bucket
    covers the longer lane's 3 pages as 4, over 2 slots: 8 cells."""
    cfg, api, params, absorbed, pj = setup
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      max_seq=64, n_slots=2, paged=True, page_size=PAGE,
                      metrics=False)
    eng.submit(Request(uid="a", tokens=_prompt(cfg, 40, seed=1),
                       max_new_tokens=8))
    eng.submit(Request(uid="b", tokens=_prompt(cfg, 10, seed=2),
                       max_new_tokens=8))
    clear_spans()
    record_spans(True)
    try:
        eng.step()
        # the next decode: positions 41 and 11 winnow 38 and 8 tokens
        eng.step()
    finally:
        record_spans(False)
    dec = [s for s in recorded_spans() if s.name == "engine.dispatch.decode"]
    clear_spans()
    assert [(s.stats["grid_pages"], s.stats["live_pages"]) for s in dec] \
        == [(8, 4), (8, 4)]
    assert all(s.stats["kernel"] == "xla" for s in dec)


def test_decode_grid_count_follows_the_kernel_grid(setup, monkeypatch):
    """``grid_pages`` is read from the paged decode kernel's own grid
    function, so a kernel whose grid changes changes the count with it."""
    import repro.runtime.serve_engine as se
    cfg, api, params, absorbed, pj = setup
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      max_seq=64, n_slots=2, paged=True, page_size=PAGE,
                      metrics=False)
    eng.submit(Request(uid="a", tokens=_prompt(cfg, 40, seed=1),
                       max_new_tokens=4))
    monkeypatch.setattr(se, "paged_decode_grid",
                        lambda n_slots, n_kv, n_pages: (1, n_kv, n_pages))
    clear_spans()
    record_spans(True)
    try:
        eng.step()
    finally:
        record_spans(False)
    (dec,) = [s for s in recorded_spans()
              if s.name == "engine.dispatch.decode"]
    clear_spans()
    # one lane's 3 live pages in a bucket of 4, over a grid of one slot
    assert (dec.stats["grid_pages"], dec.stats["live_pages"]) == (4, 3)


def test_compile_timeline_within_warmup_wall(setup):
    """The tracing, lowering, compile and cache-load seconds received
    during a warmup sum to at most its wall time: nested events are not
    counted twice."""
    import time
    cfg, api, params, absorbed, pj = setup
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      metrics=False, **_ENGINE_KW)
    clear_spans()
    record_spans(True)
    try:
        t0 = time.perf_counter_ns()
        report = eng.warmup(max_prompt_len=16)
        t1 = time.perf_counter_ns()
    finally:
        record_spans(False)
    items = [s for s in recorded_spans() if s.name == "warmup.item"]
    clear_spans()
    assert [(s.stats["family"], s.stats["shape"]) for s in items] \
        == [(r["kind"], r["detail"]) for r in report["items"]]
    lower = compile_events.phase_seconds(("trace", "lower"), t0, t1)
    compiled = compile_events.phase_seconds(("compile", "cache_load"), t0,
                                            t1)
    assert lower > 0 and compiled > 0
    assert lower + compiled <= (t1 - t0) * 1e-9


# ---------------------------------------------------------------------------
# Per-request latency accounting
# ---------------------------------------------------------------------------

def test_first_token_step_set_exactly_once_concurrent(obs_run):
    """Concurrent chunked prefill (the greedy first-token-from-chunk
    path): one ``first_token`` event per request, at the completion's
    ``first_token_step``, with TTFT = first_token_step - arrival_step."""
    cfg, eng, trace, comps = obs_run
    arrivals = {f"m{i}": a for i, (_, _, _, a) in enumerate(_SPEC)}
    for c in comps:
        evs = trace.select("first_token", uid=c.uid)
        assert len(evs) == 1, f"{c.uid}: first_token emitted {len(evs)}x"
        assert evs[0]["step"] == c.first_token_step
        assert evs[0]["ttft_steps"] == c.first_token_step - arrivals[c.uid]
        assert c.admitted_step <= c.first_token_step <= c.finished_step
        # the index-0 token event coincides with prefill completion
        tok0 = trace.select("token", uid=c.uid, index=0)
        assert len(tok0) == 1 and tok0[0]["step"] == c.first_token_step
        assert tok0[0]["token"] == c.tokens[0]
    ttft = eng.metrics.get("serve_ttft_steps")
    assert ttft.count == len(comps)
    assert ttft.sum == sum(c.first_token_step - arrivals[c.uid]
                           for c in comps)


@pytest.mark.parametrize("chunk", [None, 8],
                         ids=["monolithic", "chunked_serial"])
def test_first_token_step_set_exactly_once(setup, chunk):
    """Monolithic admission and serial (one-slot) chunked prefill keep the
    same first-token invariants as the concurrent path."""
    cfg, api, params, absorbed, pj = setup
    trace = EventTrace()
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      max_seq=64, n_slots=2, prefill_chunk=chunk,
                      trace=trace)
    comps = eng.run(_mixed_trace(cfg))
    assert len(comps) == len(_SPEC)
    for c in comps:
        evs = trace.select("first_token", uid=c.uid)
        assert len(evs) == 1
        assert evs[0]["step"] == c.first_token_step >= c.admitted_step
    assert eng.metrics.get("serve_ttft_steps").count == len(comps)


def test_inter_token_gaps_match_trace(obs_run):
    """The ``serve_inter_token_steps`` histogram must agree exactly with
    the per-request gaps reconstructed from ``token`` trace events."""
    cfg, eng, trace, comps = obs_run
    gaps = []
    for c in comps:
        steps = [e["step"] for e in trace.select("token", uid=c.uid)]
        assert len(steps) == len(c.tokens)
        assert steps == sorted(steps)
        gaps += [b - a for a, b in zip(steps, steps[1:])]
    h = eng.metrics.get("serve_inter_token_steps")
    assert h.count == len(gaps)
    assert h.sum == sum(gaps)
    # gap 0 is legal: a slot can finish prefill and join the decode
    # dispatch within the same engine step
    assert all(g >= 0 for g in gaps)
    assert eng.metrics.value("serve_tokens_generated_total") \
        == sum(len(c.tokens) for c in comps)


def test_retire_events_match_completions(obs_run):
    cfg, eng, trace, comps = obs_run
    for c in comps:
        (ev,) = trace.select("retire", uid=c.uid)
        assert ev["n_tokens"] == len(c.tokens)
        assert ev["step"] == c.finished_step
        assert ev["first_token_step"] == c.first_token_step
        assert ev["reason"] in ("eos", "max_tokens", "max_seq")
    done = sum(s.value for s in
               eng.metrics._families["serve_completions_total"]
               ["series"].values())
    assert done == len(comps)
    assert eng.metrics.get("serve_request_steps").count == len(comps)


# ---------------------------------------------------------------------------
# Byte accounting: one source of truth
# ---------------------------------------------------------------------------

def test_cache_report_matches_gauges_paged(obs_run):
    """cache_report() and the per-step gauges read the SAME
    _cache_bytes() — after the drain they must agree exactly."""
    cfg, eng, trace, comps = obs_run
    rep = eng.cache_report()
    m = eng.metrics
    assert m.value("kv_cache_reserved_bytes") == rep["reserved_bytes"]
    assert m.value("kv_cache_live_bytes") == rep["live_bytes"]
    assert m.value("page_table_shipped_bytes") \
        == rep["page_table_shipped_bytes"]
    assert m.value("page_pool_live_pages") == rep["live_pages"] == 0
    assert m.value("shard_kv_cache_reserved_bytes", shard=0) \
        == rep["shards"][0]["reserved_bytes"]
    assert m.value("shard_kv_cache_live_bytes", shard=0) \
        == rep["shards"][0]["live_bytes"]
    # per-shard entries still sum exactly to the totals
    assert sum(s["reserved_bytes"] for s in rep["shards"]) \
        == rep["reserved_bytes"]
    assert m.value("serve_engine_steps") == eng.step_count


def test_slab_gauges_reserved_equals_live(setup):
    """Slab engines commit worst case up front: the gauges show
    reserved == live every step, matching cache_report()."""
    cfg, api, params, absorbed, pj = setup
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      max_seq=64, n_slots=2)
    eng.run(_mixed_trace(cfg))
    rep = eng.cache_report()
    assert rep["reserved_bytes"] == rep["live_bytes"]
    assert eng.metrics.value("kv_cache_reserved_bytes") \
        == eng.metrics.value("kv_cache_live_bytes") == rep["live_bytes"]


# ---------------------------------------------------------------------------
# Page pool: allocator counters and events
# ---------------------------------------------------------------------------

def test_page_counters_balance_after_drain(obs_run):
    cfg, eng, trace, comps = obs_run
    m = eng.metrics
    mapped = m.value("page_pool_pages_mapped_total")
    freed = m.value("page_pool_pages_freed_total")
    assert mapped > 0
    assert mapped == freed, "drained pool must free every mapped page"
    assert len(trace.select("page_map")) == mapped
    assert sum(e["n_pages"] for e in trace.select("page_free")) == freed


def test_pool_grow_counter_and_event(setup):
    cfg, api, params, absorbed, pj = setup
    trace = EventTrace()
    eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                      max_seq=64, n_slots=2, paged=True, page_size=PAGE,
                      n_pages=2, pool_grow=True, trace=trace)
    eng.run(_mixed_trace(cfg))
    grows = eng.metrics.value("page_pool_grows_total")
    assert grows >= 1
    evs = trace.select("pool_grow")
    assert len(evs) == grows
    assert all(e["pages_per_shard_new"] > e["pages_per_shard_old"]
               for e in evs)


# ---------------------------------------------------------------------------
# Exporters over a real engine registry
# ---------------------------------------------------------------------------

def test_engine_registry_round_trips(obs_run):
    cfg, eng, trace, comps = obs_run
    snap = eng.metrics.snapshot()
    assert MetricsRegistry.from_snapshot(snap).snapshot() == snap
    parsed = parse_prometheus(eng.metrics.to_prometheus())
    for name in eng.metrics.names():
        assert name in parsed["types"], f"{name} missing from exposition"


def test_shared_registry_across_engines(setup):
    """Passing one MetricsRegistry into several engines aggregates their
    series instead of overwriting (counters just keep counting)."""
    cfg, api, params, absorbed, pj = setup
    reg = MetricsRegistry()
    for _ in range(2):
        eng = ServeEngine(cfg, absorbed, swan=_swan(), projections=pj,
                          max_seq=64, n_slots=2, metrics=reg)
        assert eng.metrics is reg
        eng.run(_mixed_trace(cfg)[:2])
    assert reg.value("serve_requests_submitted_total") == 4


# ---------------------------------------------------------------------------
# ServeSession (lockstep) metrics
# ---------------------------------------------------------------------------

def test_serve_session_metrics(setup):
    cfg, api, params, absorbed, pj = setup
    sess = ServeSession(cfg, params, max_seq=64, batch=2, metrics=True)
    out = sess.generate(make_batch(cfg, 2, 8, seed=7), 5)
    assert out.shape == (2, 5)
    m = sess.metrics
    assert m.value("session_prefill_total") == 1
    assert m.value("session_decode_total") == 4      # n_tokens - 1 decodes
    assert m.value("session_tokens_generated_total") == 10
    assert m.get("session_decode_call_ms").count == 4
    # default stays off — no registry unless asked
    off = ServeSession(cfg, params, max_seq=64, batch=1, jit=False)
    assert not off.metrics.enabled
