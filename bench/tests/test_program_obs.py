"""The per-layer metrics read from the program's spans and compile-phase
timeline: their values on a synthetic run, and None where there is
nothing to read."""
from types import SimpleNamespace

import pytest

from bench import program_obs
from bench.cell import load_reader
from repro.obs import compile_events
from repro.obs.trace import SpanRecord

MS = 1_000_000


def _span(name, step, t0_ms, t1_ms, stats=None):
    return SpanRecord(name, step, None, 0, -1, int(t0_ms * MS),
                      int(t1_ms * MS), stats)


def _ctx(steps=(), open_t=None):
    return SimpleNamespace(traced_steps=[SimpleNamespace(step=s)
                                         for s in steps],
                           record=SimpleNamespace(open_t=open_t))


# step 1: resolve ends at 12 ms, the chunk dispatch starts at 15 ms;
# step 2: resolve ends at 32 ms, the decode dispatch starts at 36 ms;
# step 3 (outside the traced steps): a gap of 100 ms
SPANS = [
    _span("engine.step", 1, 10, 30),
    _span("engine.resolve", 1, 10, 12),
    _span("engine.dispatch.chunk", 1, 15, 16),
    _span("engine.dispatch.decode", 1, 20, 21,
          {"kernel": "pallas", "grid_pages": 16, "live_pages": 5}),
    _span("engine.resolve", 2, 31, 32),
    _span("engine.dispatch.decode", 2, 36, 37,
          {"kernel": "pallas", "grid_pages": 16, "live_pages": 7}),
    _span("engine.resolve", 3, 40, 41),
    _span("engine.dispatch.decode", 3, 141, 142,
          {"kernel": "pallas", "grid_pages": 16, "live_pages": 16}),
    # a step with no resolve (nothing was in flight) is left out
    _span("engine.dispatch.decode", 4, 150, 151,
          {"kernel": "pallas", "grid_pages": 32, "live_pages": 0}),
]


def test_host_gap_is_the_median_over_traced_steps():
    assert program_obs.host_gap_ms(_ctx([1, 2]), SPANS) == pytest.approx(3.5)
    assert program_obs.host_gap_ms(_ctx([1, 2, 3, 4]),
                                   SPANS) == pytest.approx(4.0)


def test_decode_live_share_sums_the_traced_dispatches():
    assert program_obs.decode_live_share(_ctx([1, 2]),
                                         SPANS) == pytest.approx(37.5)
    assert program_obs.decode_live_share(_ctx([1, 2, 3, 4]),
                                         SPANS) == pytest.approx(35.0)


TIMELINE = [("trace", 1 * 10**9, 0.5), ("lower", 2 * 10**9, 0.25),
            ("compile", 3 * 10**9, 1.0), ("cache_load", 4 * 10**9, 0.125),
            ("trace", 9 * 10**9, 2.0), ("compile", 9 * 10**9, 3.0)]


def _timeline(monkeypatch, entries):
    """Give the program's compile-phase timeline these entries."""
    monkeypatch.setattr(compile_events, "timeline", lambda: list(entries))


def test_host_gap_starts_where_the_wait_ends():
    """With an ``engine.sample`` inside ``engine.resolve`` the gap starts
    where sampling starts: the tokens are on the host from there on."""
    spans = [
        SpanRecord("engine.resolve", 5, None, 11, 10, 10 * MS, 30 * MS, None),
        SpanRecord("engine.sample", 5, None, 12, 11, 14 * MS, 29 * MS, None),
        # a chunk's first tokens, sampled under another parent
        SpanRecord("engine.sample", 5, None, 13, 14, 31 * MS, 32 * MS, None),
        SpanRecord("engine.dispatch.decode", 5, None, 15, 16, 40 * MS,
                   41 * MS, {"grid_pages": 4, "live_pages": 1}),
    ]
    assert program_obs.host_gap_ms(_ctx([5]), spans) == pytest.approx(26.0)


def test_setup_phases_stop_at_the_window_open(monkeypatch):
    _timeline(monkeypatch, TIMELINE)
    ctx = _ctx(open_t=5.0)
    assert program_obs.setup_lower_s(ctx) == pytest.approx(0.75)
    assert program_obs.setup_compile_s(ctx) == pytest.approx(1.125)


@pytest.mark.parametrize("reader, timeline", [
    (lambda c: program_obs.host_gap_ms(c, []), TIMELINE),
    (lambda c: program_obs.host_gap_ms(_ctx([7]), SPANS), TIMELINE),
    (lambda c: program_obs.decode_live_share(c, []), TIMELINE),
    (lambda c: program_obs.decode_live_share(_ctx([7]), SPANS), TIMELINE),
    (lambda c: program_obs.setup_lower_s(c), []),
    (lambda c: program_obs.setup_compile_s(_ctx(open_t=0.5)), TIMELINE),
    (lambda c: program_obs.setup_lower_s(_ctx(open_t=None)), TIMELINE),
])
def test_nothing_to_read_gives_none(reader, timeline, monkeypatch):
    _timeline(monkeypatch, timeline)
    assert reader(_ctx([1, 2], open_t=5.0)) is None


@pytest.mark.parametrize("name", ["host_gap_ms.tok", "decode_live_share.tok"])
def test_span_readers_read_the_program_buffer(name, monkeypatch):
    """The reader files read the program's own buffer: empty, nothing to
    read; with the spans above, the synthetic values."""
    read = load_reader(name)
    monkeypatch.setattr(program_obs, "program_spans", lambda: [])
    assert read(_ctx([1, 2])) is None
    monkeypatch.setattr(program_obs, "program_spans", lambda: SPANS)
    assert read(_ctx([1, 2])) == pytest.approx(
        {"host_gap_ms.tok": 3.5, "decode_live_share.tok": 37.5}[name])


@pytest.mark.parametrize("name", ["setup_lower_s", "setup_compile_s"])
def test_setup_readers_read_the_program_timeline(name, monkeypatch):
    read = load_reader(name)
    _timeline(monkeypatch, [])
    assert read(_ctx(open_t=5.0)) is None
    _timeline(monkeypatch, TIMELINE)
    assert read(_ctx(open_t=5.0)) == pytest.approx(
        {"setup_lower_s": 0.75, "setup_compile_s": 1.125}[name])


def test_a_program_without_spans_gives_none(monkeypatch):
    """A program from before the spans and the timeline existed: the
    imports fail, and the readers give None instead of raising."""
    import repro.obs.trace as tr
    monkeypatch.delattr(tr, "recorded_spans")
    monkeypatch.delattr(compile_events, "phase_seconds")
    assert program_obs.program_spans() is None
    assert program_obs.program_phase_seconds() is None
    ctx = _ctx([1, 2], open_t=5.0)
    for name in ("host_gap_ms.tok", "decode_live_share.tok",
                 "setup_lower_s", "setup_compile_s"):
        assert load_reader(name)(ctx) is None
