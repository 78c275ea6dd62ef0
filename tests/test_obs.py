"""repro.obs unit tests: instrument semantics, exporter round-trips and
the schema-drift guard (every registered series must survive the JSON
snapshot round-trip AND appear in the Prometheus text exposition)."""
import json
import math

import pytest

from repro.obs import (EventTrace, MetricsRegistry, NULL_REGISTRY,
                       NullRegistry, StepProfiler, clear_spans,
                       compile_events, parse_prometheus, profile_options,
                       record_spans, recorded_spans, recording, span,
                       step_span)


# ---------------------------------------------------------------------------
# Instruments
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(3)
    assert reg.value("reqs_total") == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    g.inc()
    assert reg.value("depth") == 6
    # missing series reads the default, never registers
    assert reg.value("nope", default=-1) == -1
    assert reg.get("nope") is None


def test_labels_are_distinct_series():
    reg = MetricsRegistry()
    reg.counter("dispatches", "by kind", kind="chunk").inc(2)
    reg.counter("dispatches", "by kind", kind="decode").inc(5)
    assert reg.value("dispatches", kind="chunk") == 2
    assert reg.value("dispatches", kind="decode") == 5
    # idempotent getter: same (name, labels) -> same instrument
    assert reg.counter("dispatches", kind="chunk") is \
        reg.counter("dispatches", kind="chunk")


def test_kind_and_bucket_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("x", "c")
    with pytest.raises(ValueError):
        reg.gauge("x")
    reg.histogram("h", (1, 2, 4))
    with pytest.raises(ValueError):
        reg.histogram("h", (1, 2, 8))
    with pytest.raises(ValueError):
        reg.histogram("bad", ())
    with pytest.raises(ValueError):
        reg.histogram("bad", (4, 2, 1))


def test_histogram_observe_quantile_mean():
    reg = MetricsRegistry()
    h = reg.histogram("ttft", (1, 2, 4, 8), "steps")
    for v in (1, 1, 3, 5, 100):        # 100 -> overflow bucket
        h.observe(v)
    assert h.count == 5
    assert h.sum == 110
    assert h.counts == [2, 0, 1, 1, 1]
    assert h.quantile(0.0) == 1
    assert h.quantile(0.4) == 1        # rank 2 lands in the first bucket
    assert h.quantile(0.5) == 4        # rank 2.5 -> 3rd observation, le=4
    assert h.quantile(1.0) == math.inf
    assert h.mean == 22
    empty = reg.histogram("empty", (1,))
    assert math.isnan(empty.quantile(0.5))
    assert math.isnan(empty.mean)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_value_raises():
    reg = MetricsRegistry()
    reg.histogram("h", (1, 2)).observe(1)
    with pytest.raises(TypeError):
        reg.value("h")


# ---------------------------------------------------------------------------
# Exporters — the schema-drift guard
# ---------------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", "submitted").inc(12)
    reg.counter("serve_dispatches_total", "by kind", kind="chunk").inc(4)
    reg.counter("serve_dispatches_total", "by kind", kind="decode").inc(9)
    reg.gauge("queue_depth", "pending").set(3)
    reg.gauge("shard_lanes", "by shard", shard=0).set(2)
    reg.gauge("shard_lanes", "by shard", shard=1).set(1)
    h = reg.histogram("ttft_steps", (1, 2, 4, 8), "ttft")
    for v in (1, 3, 3, 9):
        h.observe(v)
    reg.histogram("wall_ms", (0.5, 2.0), "span").observe(0.75)
    reg._family("registered_but_empty", "counter", "no series yet", None)
    return reg


def test_json_snapshot_round_trip_exact():
    reg = _populated_registry()
    snap = reg.snapshot()
    # snapshot is pure JSON (no tuples/sets leak through)
    snap2 = json.loads(reg.to_json())
    assert snap2 == snap
    back = MetricsRegistry.from_snapshot(snap)
    assert back.snapshot() == snap
    # values really came back, not just structure
    assert back.value("serve_dispatches_total", kind="decode") == 9
    h = back.get("ttft_steps")
    assert (h.counts, h.sum, h.count) == ([1, 0, 2, 0, 1], 16.0, 4)
    # zero-series families survive too (schema, not just data)
    assert "registered_but_empty" in back.names()


def test_prometheus_contains_every_registered_series():
    reg = _populated_registry()
    parsed = parse_prometheus(reg.to_prometheus())
    for name in reg.names():
        fam = reg._families[name]
        assert parsed["types"].get(name) == fam["kind"], name
        for key, inst in fam["series"].items():
            if fam["kind"] == "histogram":
                labels = dict(key)
                assert parsed["samples"][
                    (f"{name}_count", tuple(sorted(labels.items())))] \
                    == inst.count
                assert parsed["samples"][
                    (f"{name}_sum", tuple(sorted(labels.items())))] \
                    == inst.sum
                # +Inf bucket is cumulative == count
                inf_key = tuple(sorted({**labels, "le": "+Inf"}.items()))
                assert parsed["samples"][(f"{name}_bucket", inf_key)] \
                    == inst.count
            else:
                assert parsed["samples"][(name, key)] == inst.value, name


def test_prometheus_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", (1, 2, 4), "l")
    for v in (1, 2, 2, 3, 99):
        h.observe(v)
    text = reg.to_prometheus()
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="2"} 3' in text
    assert 'lat_bucket{le="4"} 4' in text
    assert 'lat_bucket{le="+Inf"} 5' in text
    assert "lat_sum 107" in text
    assert "lat_count 5" in text


# ---------------------------------------------------------------------------
# Null registry
# ---------------------------------------------------------------------------

def test_null_registry_is_inert():
    null = NullRegistry()
    assert not null.enabled
    assert NULL_REGISTRY.enabled is False
    c = null.counter("x", "h", kind="a")
    c.inc(5)
    g = null.gauge("y")
    g.set(3)
    h = null.histogram("z", (1, 2))
    h.observe(9)
    assert math.isnan(h.quantile(0.5))
    assert null.get("x") is None
    assert null.snapshot() == {"metrics": {}}
    assert null.to_prometheus().strip() == ""
    # the same shared instrument absorbs everything — no state anywhere
    assert c.value == 0 and h.count == 0


# ---------------------------------------------------------------------------
# Event trace, spans, profiler hook
# ---------------------------------------------------------------------------

def test_event_trace_memory_and_select():
    tr = EventTrace()
    tr.emit("admit", step=3, uid="r0", slot=1)
    tr.emit("admit", step=4, uid="r1", slot=0)
    tr.emit("retire", step=9, uid="r0", slot=1)
    assert [e["uid"] for e in tr.select("admit")] == ["r0", "r1"]
    assert tr.select("admit", uid="r1")[0]["step"] == 4
    assert tr.select("nope") == []


def test_event_trace_file_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with EventTrace(path) as tr:
        tr.emit("submit", step=0, uid="a", prompt_len=7)
        tr.emit("token", step=2, uid="a", index=0, token=42)
        assert tr.events == []            # keep defaults to False with path
    back = EventTrace.read(path)
    # every event carries the program clock at emit time, in order
    stamps = [e.pop("t_ns") for e in back]
    assert all(isinstance(t, int) for t in stamps)
    assert stamps == sorted(stamps)
    assert back == [
        {"event": "submit", "step": 0, "uid": "a", "prompt_len": 7},
        {"event": "token", "step": 2, "uid": "a", "index": 0, "token": 42},
    ]


def test_span_emits_wall_ms_and_none_is_noop():
    with span("nothing"):
        pass                              # must not raise
    tr = EventTrace()
    with span("prefill", step=5, uid="r0", trace=tr):
        pass
    (ev,) = tr.select("span")
    assert ev["name"] == "prefill" and ev["uid"] == "r0" and ev["step"] == 5
    assert ev["wall_ms"] >= 0.0


def test_step_profiler_brackets_exactly_n_steps():
    calls = []
    tr = EventTrace()
    prof = StepProfiler("/tmp/prof", 3, trace=tr,
                        start=lambda d: calls.append(("start", d)),
                        stop=lambda: calls.append(("stop",)))
    for step in range(10):
        prof.step_start(step)
        prof.step_end(step + 1)
    assert calls == [("start", "/tmp/prof"), ("stop",)]
    assert prof.done and not prof.active
    assert tr.select("profile_start")[0]["n_steps"] == 3
    assert tr.select("profile_stop")[0]["step"] == 3
    with pytest.raises(ValueError):
        StepProfiler("/tmp/prof", 0)


def test_no_session_records_nothing():
    """Outside a profiler session a span is one shared no-op object and
    nothing is buffered; a timed span still reads the clock."""
    clear_spans()
    assert not recording()
    assert span("a", 1) is span("b", 2, uid="r") is step_span("s", 3)
    with span("engine.admit", 1):
        pass
    with span("engine.resolve", 1, timed=True) as sp:
        pass
    assert sp.ms >= 0.0
    assert recorded_spans() == []


def test_recorded_spans_nest_with_parents():
    clear_spans()
    record_spans(True)
    try:
        with step_span("engine.step", 4):
            with span("engine.decode", 4):
                with span("engine.dispatch.decode", 4,
                          stats={"grid_pages": 8, "live_pages": 3}):
                    pass
            with span("engine.submit", 4, uid="r9"):
                pass
    finally:
        record_spans(False)
    by = {s.name: s for s in recorded_spans()}
    assert [s.name for s in recorded_spans()] == [
        "engine.dispatch.decode", "engine.decode", "engine.submit",
        "engine.step"]
    assert by["engine.step"].parent == -1
    assert by["engine.decode"].parent == by["engine.step"].id
    assert by["engine.submit"].parent == by["engine.step"].id
    assert by["engine.dispatch.decode"].parent == by["engine.decode"].id
    assert by["engine.submit"].uid == "r9"
    assert by["engine.dispatch.decode"].stats["live_pages"] == 3
    step = by["engine.step"]
    assert all(step.t0_ns <= s.t0_ns <= s.t1_ns <= step.t1_ns
               for s in by.values())
    assert {s.step for s in by.values()} == {4}
    clear_spans()


def test_span_lands_in_profiler_host_plane(tmp_path):
    """In a profiled block every span is an annotation on the profiler's
    host plane, and the step annotation's ``host_ns`` maps each buffered
    span onto it to within the span's own duration."""
    import glob
    import time

    import jax
    import jax.numpy as jnp

    clear_spans()
    jax.profiler.start_trace(str(tmp_path), profiler_options=profile_options())
    try:
        for step in range(2):
            with step_span("engine.step", step):
                with span("engine.resolve", step, timed=True):
                    jnp.ones(64).sum().block_until_ready()
                    time.sleep(0.003)
                with span("engine.submit", step, uid=f"r{step}"):
                    time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    assert not recording()
    mine = recorded_spans()
    clear_spans()
    assert len(mine) == 6
    (pb,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(pb)
    events = [(e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats))
              for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events
              if e.name.startswith("engine.")]
    steps = [e for e in events if e[0] == "engine.step"]
    assert sorted(e[3]["step_num"] for e in steps) == [0, 1]
    offsets = {e[3]["host_ns"] - e[1] for e in steps}
    assert max(offsets) - min(offsets) < 1_000_000      # one clock offset
    off = min(offsets)
    assert {e[3].get("uid") for e in events
            if e[0] == "engine.submit"} == {"r0", "r1"}
    for s in mine:
        dur = s.t1_ns - s.t0_ns
        ev = min((e for e in events if e[0] == s.name),
                 key=lambda e: abs(e[1] + off - s.t0_ns))
        assert abs(ev[1] + off - s.t0_ns) < dur
        assert abs(ev[1] + ev[2] + off - s.t1_ns) < dur


def test_profile_options_match_the_benchmark():
    opts = profile_options()
    assert opts.python_tracer_level == 0 and opts.host_tracer_level == 1


def test_compile_timeline_folds_nested_and_splits_cache_loads():
    """A trace event that ended inside a later one of its group (an inner
    jit traced while the outer one traced) is folded into it; a backend
    compile the persistent cache answered is a cache load; phase_seconds
    reads a window of receipt times."""
    import time
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    lower_ev = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    compile_ev = "/jax/core/compile/backend_compile_duration"
    t0 = time.perf_counter_ns()
    time.sleep(0.002)
    compile_events._listener(trace_ev, 0.001)       # the inner jit's trace
    time.sleep(0.002)
    compile_events._listener(trace_ev, 0.05)        # the outer trace
    time.sleep(0.002)
    compile_events._listener(lower_ev, 0.001)
    total0 = compile_events.total()
    compile_events._cache_listener("/jax/compilation_cache/cache_hits")
    compile_events._listener(compile_ev, 0.0005)
    time.sleep(0.002)
    compile_events._listener(compile_ev, 0.001)
    t1 = time.perf_counter_ns()
    mine = [(p, s) for p, t, s in compile_events.timeline() if t0 < t <= t1]
    assert mine == [("trace", 0.05), ("lower", 0.001),
                    ("cache_load", 0.0005), ("compile", 0.001)]
    assert compile_events.total() == total0 + 2
    assert compile_events.phase_seconds(("trace", "lower"), t0, t1) \
        == pytest.approx(0.051)
    assert compile_events.phase_seconds(("compile", "cache_load"), t0, t1) \
        == pytest.approx(0.0015)
    assert compile_events.phase_seconds(("compile",), t1) == 0.0
