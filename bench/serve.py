"""Drive the serve engine with a traffic plan, on the host clock.

The engine's event trace (``repro.obs.EventTrace``) is indexed by engine
step and carries no wall time, so the driver puts its own clock on every
``step()`` call: an event emitted during a call gets that call's end
time, except ``admit``, which happens at the top of ``step()`` and gets
its start.  A token's time is therefore the end of the ``step()`` call in
which its value reached the host; with ``--async-fetch`` that is the call
after the one that dispatched it, which is when a client would see it.

Open loop: requests are submitted when the schedule says, between
``step()`` calls, and timed from when they were due.  Arrivals stop when
the window closes; the requests that arrived in the window are then
followed to completion, within ``drain_seconds``.  Closed loop: each
client sends its next request as soon as its last completes; the window
opens once ``preroll.completions`` requests have completed.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from bench.traffic import Plan, Req

clock = time.perf_counter


@dataclass
class ReqLog:
    req: Req
    due: float                        # when it was due to be sent
    sent: float = 0.0
    admit: Optional[float] = None
    slot: Optional[int] = None
    times: List[float] = field(default_factory=list)   # token deliveries
    tokens: List[int] = field(default_factory=list)
    done: Optional[float] = None
    prefilled: int = 0


@dataclass
class StepLog:
    step: int
    t0: float
    t1: float
    decode: List[Tuple[int, int]] = field(default_factory=list)   # (pos, k)
    chunk: List[Tuple[int, int, int]] = field(default_factory=list)  # (start, n, k)


@dataclass
class Record:
    logs: Dict[str, ReqLog]
    steps: Dict[int, StepLog]
    t_start: float
    open_t: float
    close_t: float
    end_t: float
    compiles_in_window: int
    trace_span: Optional[Tuple[float, float]] = None
    window_step_range: Tuple[int, int] = (0, 0)


class Driver:
    def __init__(self, eng, events, plan: Plan, chunk: int, *,
                 profile_dir: Optional[str] = None,
                 trace_offset_s: float = 0.0, trace_s: float = 0.0):
        self.eng, self.events, self.plan, self.chunk = eng, events, plan, chunk
        self.logs: Dict[str, ReqLog] = {}
        self.steps: Dict[int, StepLog] = {}
        self.by_slot: Dict[int, ReqLog] = {}
        self.completions = 0
        self.profile_dir = profile_dir
        self.trace_offset_s, self.trace_s = trace_offset_s, trace_s
        self._tracing = False
        self.trace_span: Optional[Tuple[float, float]] = None
        # called once when the window closes; its result is kept
        self.on_close: Optional[Callable[[], Any]] = None
        self.closed_snapshot: Any = None

    def _close(self) -> None:
        if self.on_close is not None:
            self.closed_snapshot = self.on_close()

    # -- events -> host times -------------------------------------------
    def _absorb(self, t0: float, t1: float) -> List[ReqLog]:
        evs, self.events.events = self.events.events, []
        finished = []
        for e in evs:
            kind = e["event"]
            if kind == "admit":
                log = self.logs[e["uid"]]
                log.admit, log.slot = t0, e["slot"]
                self.by_slot[e["slot"]] = log
            elif kind == "chunk_dispatch":
                st = self.steps[e["step"]]
                for slot in e["slots"]:
                    log = self.by_slot[slot]
                    n = min(self.chunk, len(log.req.tokens) - log.prefilled)
                    st.chunk.append((log.prefilled, n, log.req.k))
                    log.prefilled += n
            elif kind == "token":
                log = self.logs[e["uid"]]
                log.times.append(t1)
                log.tokens.append(e["token"])
                if e["index"] > 0:      # a decode step's output
                    self.steps[e["step"]].decode.append(
                        (len(log.req.tokens) + e["index"] - 1, log.req.k))
            elif kind == "retire":
                log = self.logs[e["uid"]]
                log.done = t1
                self.completions += 1
                finished.append(log)
        return finished

    def _span(self, name: str, **kw):
        """A host annotation in the profiler's trace while it records."""
        import jax
        return (jax.profiler.TraceAnnotation(name, **kw) if self._tracing
                else contextlib.nullcontext())

    def _submit(self, req: Req, due: float) -> None:
        from repro.runtime.serve_engine import Request
        log = ReqLog(req=req, due=due, sent=clock())
        self.logs[req.uid] = log
        with self._span("bench.generate"):
            self.eng.submit(Request(uid=req.uid, tokens=req.tokens,
                                    max_new_tokens=req.max_new_tokens,
                                    temperature=req.temperature,
                                    seed=req.seed, k=req.k))

    def _step(self) -> List[ReqLog]:
        n = self.eng.step_count
        t0 = clock()
        with self._span("bench.step", step=n):
            self.eng.step()
        t1 = clock()
        self.steps[n] = StepLog(step=n, t0=t0, t1=t1)
        with self._span("bench.harness"):
            return self._absorb(t0, t1)

    def _maybe_trace(self, now: float, open_t: float) -> None:
        import jax
        if self.profile_dir is None:
            return
        if not self._tracing and self.trace_span is None \
                and now >= open_t + self.trace_offset_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.profile_dir,
                                     profiler_options=opts)
            self._tracing = True
            self.trace_span = (now, now)
        elif self._tracing and now >= self.trace_span[0] + self.trace_s:
            jax.profiler.stop_trace()
            self._tracing = False
            self.trace_span = (self.trace_span[0], now)

    # -- loops ------------------------------------------------------------
    def run(self, preroll: Dict[str, Any], window_s: float,
            drain_s: float) -> Record:
        if self.plan.loop == "open":
            rec = self._run_open(float(preroll["seconds"]), window_s, drain_s)
        else:
            target = preroll["completions"]
            target = (self.plan.clients if target == "n_slots"
                      else int(target))
            rec = self._run_closed(target, window_s)
        if self._tracing:                 # a window shorter than the trace
            import jax
            jax.profiler.stop_trace()
            self._tracing = False
            self.trace_span = (self.trace_span[0], clock())
        rec.trace_span = self.trace_span
        return rec

    def _run_open(self, preroll_s: float, window_s: float,
                  drain_s: float) -> Record:
        from repro.obs import compile_events
        reqs, arr = self.plan.requests, self.plan.arrivals
        t_start = clock()
        open_t, close_t = t_start + preroll_s, t_start + preroll_s + window_s
        due = [t_start + float(a) for a in arr]
        i, c_open, c_close = 0, None, None
        s_open = s_close = 0
        while True:
            now = clock()
            if c_open is None and now >= open_t:
                c_open, s_open = compile_events.total(), self.eng.step_count
            if c_close is None and now >= close_t:
                c_close, s_close = compile_events.total(), self.eng.step_count
                self._close()
            while i < len(reqs) and due[i] <= now and due[i] < close_t:
                self._submit(reqs[i], due[i])
                i += 1
            if now >= close_t:
                pending = [lg for lg in self.logs.values()
                           if lg.due >= open_t and lg.done is None]
                if not pending or now >= close_t + drain_s:
                    break
            self._maybe_trace(now, open_t)
            if self.eng.done:
                nxt = due[i] if i < len(reqs) else close_t
                time.sleep(max(0.0, min(nxt - clock(), 0.0005)))
                continue
            self._step()
        return Record(logs=self.logs, steps=self.steps, t_start=t_start,
                      open_t=open_t, close_t=close_t, end_t=clock(),
                      compiles_in_window=c_close - c_open,
                      window_step_range=(s_open, s_close))

    def _run_closed(self, preroll_completions: int,
                    window_s: float) -> Record:
        from repro.obs import compile_events
        reqs, n_clients = self.plan.requests, self.plan.clients
        nxt = 0
        client_of: Dict[str, int] = {}

        def send(client: int) -> None:
            nonlocal nxt
            req = reqs[nxt % len(reqs)]
            if nxt >= len(reqs):        # the pool wrapped: a fresh uid
                req = Req(uid=f"{req.uid}.{nxt // len(reqs)}",
                          tokens=req.tokens,
                          max_new_tokens=req.max_new_tokens, k=req.k,
                          temperature=req.temperature, seed=req.seed)
            nxt += 1
            client_of[req.uid] = client
            self._submit(req, clock())

        t_start = clock()
        for c in range(n_clients):
            send(c)
        open_t = close_t = None
        c_open = s_open = 0
        while True:
            for log in self._step():
                send(client_of[log.req.uid])
            now = clock()
            if open_t is None and self.completions >= preroll_completions:
                open_t, close_t = now, now + window_s
                c_open, s_open = compile_events.total(), self.eng.step_count
            if open_t is not None:
                self._maybe_trace(now, open_t)
                if now >= close_t:
                    self._close()
                    break
        return Record(logs=self.logs, steps=self.steps, t_start=t_start,
                      open_t=open_t, close_t=close_t, end_t=clock(),
                      compiles_in_window=compile_events.total() - c_open,
                      window_step_range=(s_open, self.eng.step_count))
