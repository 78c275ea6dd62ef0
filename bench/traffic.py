"""The one traffic generator: reads a mix's parameters and makes its
requests and arrival times from ``--seed``.

A mix file (``bench/traffic/<mix>.json``) gives:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last completes) or ``"open"`` (arrivals on a schedule,
  ``arrivals``: ``{"process": "poisson" | "gamma", "rate_per_s", "cv"}``);
* ``prompt_tokens`` / ``output_tokens``: a length distribution
  (``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``);
* ``k``: the per-request SWAN retentions, in equal shares;
* ``sampled_share`` / ``temperature``: the share of requests that sample
  (the rest are greedy);
* ``preroll``, ``drain_seconds``, ``trace``, ``check``: read by the
  driver and the correctness check.

Every seed gets the same requests' sizes and the same gaps, in the same
order: lengths and inter-arrival gaps are the distribution's quantiles
at ``(i + 0.5) / n``, and prompt and output lengths, ``k``, sampling and
the gaps are paired and ordered once for all seeds.  The seed makes the
prompts' tokens (and, in ``bench/model.py``, the weights).  A run's
window holds a few dozen of the plan's requests; were they dealt in each
seed's own order, the window of one seed would hold more long requests
than another's, and runs of different seeds would spread far more than
runs of one seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Req:
    uid: str
    tokens: List[int]
    max_new_tokens: int
    k: int
    temperature: float
    seed: int


@dataclass
class Plan:
    loop: str
    requests: List[Req]
    arrivals: Optional[np.ndarray]   # open loop: seconds from the start
    clients: int                     # closed loop: concurrent callers


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one seed (any non-negative int)."""
    return np.random.default_rng([int(seed), stream])


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 0.5) / n, clipped to
    ``[min, max]``, in ascending order."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in u])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def gaps(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) at the quantiles of an
    exponential (Poisson arrivals) or gamma (bursty, coefficient of
    variation ``cv``) law with mean ``1 / rate_per_s``."""
    u = (np.arange(n) + 0.5) / n
    mean = 1.0 / float(spec["rate_per_s"])
    if spec["process"] == "poisson":
        return -np.log1p(-u) * mean
    if spec["process"] == "gamma":
        from scipy.stats import gamma
        shape = 1.0 / float(spec["cv"]) ** 2
        return gamma.ppf(u, shape, scale=mean / shape)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def make_plan(traffic: Dict[str, Any], seed: int, vocab: int, *,
              n_slots: int, horizon_s: float) -> Plan:
    """The requests of one run.  Open loop: every arrival in
    ``[0, horizon_s)``.  Closed loop: a pool of ``traffic["pool"]``
    requests that the clients take in turn."""
    order = rng_for(0, 98)            # one order for every seed
    if traffic["loop"] == "open":
        rate = float(traffic["arrivals"]["rate_per_s"])
        n = max(1, math.ceil(rate * horizon_s))
        arrivals = np.cumsum(order.permutation(gaps(traffic["arrivals"], n)))
        arrivals = arrivals[arrivals < horizon_s]
        n = len(arrivals)
        clients = 0
    elif traffic["loop"] == "closed":
        n = int(traffic["pool"])
        arrivals = None
        c = traffic["clients"]
        clients = n_slots if c == "n_slots" else int(c)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")

    # one pairing of prompt, output, k and sampling for all seeds (a
    # fixed stream), dealt out in one order
    pair = rng_for(0, 99)
    prompt = quantiles(traffic["prompt_tokens"], n)
    output = pair.permutation(quantiles(traffic["output_tokens"], n))
    ks = list(traffic["k"])
    k = pair.permutation(np.array([ks[i % len(ks)] for i in range(n)]))
    n_sampled = int(round(float(traffic.get("sampled_share", 0.0)) * n))
    sampled = pair.permutation(np.arange(n) < n_sampled)
    deal = order.permutation(n)
    prompt, output, k, sampled = (prompt[deal], output[deal], k[deal],
                                  sampled[deal])
    temp = float(traffic.get("temperature", 0.0))

    toks = rng_for(seed, 1)
    reqs = [Req(uid=f"r{i}",
                tokens=toks.integers(0, vocab, int(prompt[i])).tolist(),
                max_new_tokens=int(output[i]), k=int(k[i]),
                temperature=temp if sampled[i] else 0.0, seed=i)
            for i in range(n)]
    return Plan(loop=traffic["loop"], requests=reqs, arrivals=arrivals,
                clients=clients)
