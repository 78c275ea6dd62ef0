"""The traffic generator: deterministic per seed, inside its bounds, and
the same work for every seed."""
import numpy as np
import pytest

from bench.traffic import gaps, make_plan, quantiles
from bench.tests.tiny import TRAFFIC

BIG_SEED = 2**31 + 987654321          # the driver's seeds exceed 32 bits


def _plan(mix, seed, horizon=30.0):
    return make_plan(TRAFFIC[mix], seed, 256, n_slots=4, horizon_s=horizon)


@pytest.mark.parametrize("mix", ["closed", "open"])
def test_same_seed_same_plan(mix):
    a, b = _plan(mix, BIG_SEED), _plan(mix, BIG_SEED)
    assert [(r.uid, r.tokens, r.max_new_tokens, r.k) for r in a.requests] \
        == [(r.uid, r.tokens, r.max_new_tokens, r.k) for r in b.requests]
    if mix == "open":
        np.testing.assert_array_equal(a.arrivals, b.arrivals)


@pytest.mark.parametrize("mix", ["closed", "open"])
def test_lengths_and_k_within_bounds(mix):
    spec = TRAFFIC[mix]
    for r in _plan(mix, 5).requests:
        assert spec["prompt_tokens"]["min"] <= len(r.tokens) \
            <= spec["prompt_tokens"]["max"]
        assert spec["output_tokens"]["min"] <= r.max_new_tokens \
            <= spec["output_tokens"]["max"]
        assert r.k in spec["k"] and r.temperature == 0.0
        assert all(0 <= t < 256 for t in r.tokens)


@pytest.mark.parametrize("mix", ["closed", "open"])
def test_seeds_share_the_work_in_another_order(mix):
    """Seeds share the sizes and their order; only the tokens differ."""
    a, b = _plan(mix, 1), _plan(mix, BIG_SEED)
    key = lambda p: [(len(r.tokens), r.max_new_tokens, r.k, r.temperature)
                     for r in p.requests]
    assert key(a) == key(b)
    assert len(set(key(a))) > 1                 # not one size repeated
    assert [r.tokens for r in a.requests] != [r.tokens for r in b.requests]
    if mix == "open":
        np.testing.assert_array_equal(a.arrivals, b.arrivals)


def test_open_loop_rate_and_horizon():
    p = _plan("open", 3, horizon=50.0)
    assert p.arrivals.max() < 50.0
    assert np.all(np.diff(p.arrivals) > 0)
    rate = TRAFFIC["open"]["arrivals"]["rate_per_s"]
    assert abs(len(p.arrivals) - rate * 50.0) <= 2


def test_quantiles_follow_the_distribution():
    q = quantiles({"dist": "lognormal", "median": 384, "sigma": 0.5,
                   "min": 128, "max": 1024}, 1001)
    assert q[500] == 384 and q.min() >= 128 and q.max() <= 1024
    u = quantiles({"dist": "uniform", "min": 256, "max": 1024}, 769)
    assert sorted(set(u.tolist())) == list(range(256, 1025))


@pytest.mark.parametrize("process,cv", [("poisson", 1.0), ("gamma", 2.0)])
def test_gaps_mean_and_burstiness(process, cv):
    g = gaps({"process": process, "rate_per_s": 4.0, "cv": cv}, 20000)
    assert g.mean() == pytest.approx(0.25, rel=0.02)
    assert g.std() / g.mean() == pytest.approx(cv, rel=0.05)
