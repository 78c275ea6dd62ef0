"""What the per-layer metrics compute, from the traced window.

Each ``bench/layer_metrics/<metric>.py`` calls one of these with the run's
context and returns its number, or None when the run has nothing for it
to read (no such kernel in the trace, no such request in the window):
the harness then leaves the metric out of the result line.

``ctx`` carries ``trace`` (``trace_reduce.Reduced`` or None),
``traced_steps`` (the driver's ``StepLog`` of every ``step()`` call
inside the traced window), ``shapes`` (``costs.Shapes``), ``peaks``,
``record`` (the driver's ``Record``) and ``snapshot`` (the engine's
cache report and the live tokens when the window closed).
"""
from __future__ import annotations

import numpy as np


def _mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else 0.0


def decode_roofline(ctx):
    """Least time for the bytes the live decode lanes need (each lane's
    packed sparse prefix at its own length and k, its ring, q and o) at
    the chip's HBM bandwidth, over the summed device time of the paged
    decode kernel.  The trace holds one kernel call per layer per decode
    step; a call's bytes are averaged over the traced decode steps (lane
    lengths grow by one token a step, so edge steps change the mean by
    far less than a percent)."""
    durs = ctx.trace.kernels.get("decode") if ctx.trace else None
    steps = [s for s in ctx.traced_steps if s.decode]
    if not durs or not steps:
        return None
    per_call = _mean(ctx.shapes.decode_call_bytes(s.decode) for s in steps)
    least = per_call * len(durs) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(durs)


def chunk_roofline(ctx):
    """The same for the paged chunk-stats kernel, each call bounded by the
    larger of its bytes over HBM bandwidth and its operations over the
    bf16 peak."""
    durs = ctx.trace.kernels.get("chunk") if ctx.trace else None
    steps = [s for s in ctx.traced_steps if s.chunk]
    if not durs or not steps:
        return None
    bw, fl = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["bf16_flops"]

    def least(step):
        b, ops = ctx.shapes.chunk_call_cost(step.chunk)
        return max(b / bw, ops / fl)

    return 100.0 * _mean(least(s) for s in steps) * len(durs) / sum(durs)


def mfu(ctx):
    """Model operations of every token the traced steps processed
    (decoded tokens and prompt chunks), over the traced seconds and the
    chip's bf16 peak."""
    if ctx.trace is None or not ctx.traced_steps:
        return None
    sh = ctx.shapes
    ops = 0
    for s in ctx.traced_steps:
        ops += sum(sh.decode_token_flops(pos, k) for pos, k in s.decode)
        ops += sum(sh.chunk_flops(st, n, k) for st, n, k in s.chunk)
    if ops == 0:
        return None
    return 100.0 * ops / ctx.trace.window_s / ctx.peaks["bf16_flops"]


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the
    device."""
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def queue_wait_p95_ms(ctx):
    """95th percentile of scheduled arrival to admission into a slot, over
    the requests that arrived in the window."""
    rec = ctx.record
    waits = [(lg.admit - lg.due) * 1e3 for lg in rec.logs.values()
             if rec.open_t <= lg.due < rec.close_t and lg.admit is not None]
    return float(np.percentile(waits, 95)) if waits else None


def kv_bytes_per_token(ctx):
    """The engine's live cache bytes (``cache_report()``) over the tokens
    its live requests held when the window closed."""
    snap = ctx.snapshot
    if not snap or not snap.get("live_tokens"):
        return None
    return snap["cache_report"]["live_bytes"] / snap["live_tokens"]
