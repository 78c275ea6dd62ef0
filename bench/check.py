"""Decide ``correct``: what the timed path served, against the plain
reference, once the window has closed.

A sample of the requests that the engine finished, drawn from the seed,
with the longest among them, is run through the configuration's plain
reference (float32, ``highest`` precision): each request's prompt with
the tokens it was served, teacher-forced.  For every served (greedy)
token the reference gives the gap by which that token's logit lies below
the reference's best at its position.  Two numbers are made of them:

* ``widest_gap``: the largest such gap over the sample (nats);
* ``mean_gap``: their mean.

Those that ``bench/limits/<cell>.json`` gives a limit are compared; the
others are printed beside them.

A correct bf16 engine serves the reference's best token or a near tie
of it, so both stay small; a wrong cache read, page, mask or token moves
served tokens far below the best.  The reference runs after the engine
is freed, so it neither sets the run's memory peak nor shares anything
with the program.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench.traffic import rng_for


def sample(logs, seed: int, since: float, spec: Dict[str, Any]) -> List:
    """The longest greedy request finished at or after ``since``, then
    others in a seeded order, until ``spec["tokens"]`` served tokens or
    ``spec["max_requests"]`` requests."""
    done = [lg for lg in logs.values() if lg.done is not None
            and lg.done >= since and lg.req.temperature == 0.0]
    if not done:
        return []
    done.sort(key=lambda lg: lg.req.uid)
    longest = max(done, key=lambda lg: (len(lg.tokens), len(lg.req.tokens)))
    rest = [done[i] for i in rng_for(seed, 2).permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.tokens)
    for lg in rest:
        if n >= spec["tokens"] or len(out) >= spec["max_requests"]:
            break
        out.append(lg)
        n += len(lg.tokens)
    return out


def _padded(prompt: List[int], served: List[int], t_pad: int):
    """tokens / next-token targets of one sequence, padded to ``t_pad``."""
    seq = list(prompt) + list(served)
    tokens = np.zeros((t_pad,), np.int32)
    targets = np.zeros((t_pad,), np.int32)
    tokens[:len(seq) - 1] = seq[:-1]
    targets[:len(seq) - 1] = seq[1:]
    return tokens, targets


def served_gaps(scorer, W, P, reqs, t_pad: int) -> List[np.ndarray]:
    """Per request, the reference gap of each served token."""
    out = []
    for prompt, served, k in reqs:
        tokens, targets = _padded(prompt, served, t_pad)
        gap, _ = scorer(W, P, tokens, targets, np.int32(len(prompt)),
                        np.int32(k))
        n = len(prompt)
        out.append(np.asarray(gap)[n - 1:n - 1 + len(served)])
    return out


def control_gaps(scorer, low, W, P, reqs, t_pad: int) -> List[np.ndarray]:
    """Per request, the reference gap of the token that the
    lower-precision reference (``low``) puts first at each served
    position: the same prompts and tokens, no decoding."""
    out = []
    for prompt, served, k in reqs:
        tokens, targets = _padded(prompt, served, t_pad)
        n = np.int32(len(prompt))
        _, top = low(W, P, tokens, targets, n, np.int32(k))
        gap, _ = scorer(W, P, tokens, np.asarray(top), n, np.int32(k))
        out.append(np.asarray(gap)[len(prompt) - 1:
                                   len(prompt) - 1 + len(served)])
    return out


def numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    allg = np.concatenate(gaps) if gaps else np.zeros((0,))
    if allg.size == 0:
        return {}
    return {"widest_gap": float(allg.max()), "mean_gap": float(allg.mean())}


def judge(nums: Dict[str, float], limits: Dict[str, Any]
          ) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit."""
    return {name: {"value": nums[name], "limit": float(spec["limit"])}
            for name, spec in limits["numbers"].items() if name in nums}
