"""End-to-end metrics, from the driver's host-clock record.

* ``tok_per_s``: output tokens delivered to clients in the window, over
  the window's seconds.
* ``ttft_p95_ms``: 95th percentile, over every request that arrived in
  the window, of the time from its scheduled arrival to its first token.
  A request that never got one counts with the time until the drain
  ended (a lower bound).
* ``itl_p95_ms``: 95th percentile of every gap between consecutive tokens
  of a request whose later token was delivered in the window.
* ``setup_s``: process start to the window's opening (load, warmup,
  pre-roll).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def in_window(rec, t: float) -> bool:
    return rec.open_t < t <= rec.close_t


def window_arrivals(rec) -> List:
    return [lg for lg in rec.logs.values()
            if rec.open_t <= lg.due < rec.close_t]


def samples(rec) -> Dict[str, List[float]]:
    """Every sample behind the latency metrics, in milliseconds."""
    ttft = [((lg.times[0] if lg.times else rec.end_t) - lg.due) * 1e3
            for lg in window_arrivals(rec)]
    itl = [(b - a) * 1e3 for lg in rec.logs.values()
           for a, b in zip(lg.times, lg.times[1:]) if in_window(rec, b)]
    return {"ttft_ms": ttft, "itl_ms": itl}


def tokens_in_window(rec) -> int:
    return sum(1 for lg in rec.logs.values() for t in lg.times
               if in_window(rec, t))


def compute(rec, setup_s: float) -> Dict[str, float]:
    s = samples(rec)
    out = {"setup_s": setup_s,
           "tok_per_s": tokens_in_window(rec) / (rec.close_t - rec.open_t)}
    if s["ttft_ms"]:
        out["ttft_p95_ms"] = float(np.percentile(s["ttft_ms"], 95))
    if s["itl_ms"]:
        out["itl_p95_ms"] = float(np.percentile(s["itl_ms"], 95))
    return out


def attempted_failed(rec, loop: str):
    """Open loop: the requests that arrived in the window, and those of
    them that had not finished when the drain ended.  Closed loop: the
    requests in flight at any time in the window; none can fail, since a
    request still running when the window closes is not late."""
    if loop == "open":
        arr = window_arrivals(rec)
        return len(arr), sum(1 for lg in arr if lg.done is None)
    live = [lg for lg in rec.logs.values() if lg.sent < rec.close_t
            and (lg.done is None or lg.done > rec.open_t)]
    return len(live), 0
