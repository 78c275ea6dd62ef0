"""The comparison that decides ``correct`` fails when the timed path is
broken underneath: a token altered where it is produced, a decode step
that returns its cache unchanged, and lanes routed to another slot's
pages.  Each drives a whole tiny run with the harness's look for a chip
skipped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.tiny import run_cell, write_tree
from repro.launch import serve
from repro.runtime.serve_engine import ServeEngine


def _wrap_engine(monkeypatch, patch):
    real = serve.build_engine

    def build(*a, **kw):
        eng = real(*a, **kw)
        patch(eng)
        return eng
    monkeypatch.setattr(serve, "build_engine", build)


def token_altered(monkeypatch):
    real = ServeEngine._resolve_tokens

    def resolve(self, pending):
        toks = real(self, pending)
        if pending.step % 4 == 1:          # every request decodes >= 5 steps
            toks = [(t + 1) % self.cfg.vocab_size for t in toks]
        return toks
    monkeypatch.setattr(ServeEngine, "_resolve_tokens", resolve)


class _StateUnchanged:
    """The engine's decode dispatch, returning the cache it was given."""

    def __init__(self, real):
        self.real = real
        self._cache_size = real._cache_size     # the executable census

    def __call__(self, *a):
        before = jax.tree_util.tree_map(jnp.copy, a[5])
        logits, greedy, _ = self.real(*a)
        return logits, greedy, before


def state_unchanged(monkeypatch):
    def patch(eng):
        eng._decode = _StateUnchanged(eng._decode)
    _wrap_engine(monkeypatch, patch)


def pages_misrouted(monkeypatch):
    def patch(eng):
        real = eng._device_table

        def table(width):
            return jnp.roll(real(width), 1, axis=0)
        eng._device_table = table
    _wrap_engine(monkeypatch, patch)


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   pages_misrouted])
def test_fault_is_not_correct(fault, tmp_path, capsys, monkeypatch,
                              own_compile_cache):
    fault(monkeypatch)
    line = run_cell(write_tree(tmp_path), "tiny.closed", capsys)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
    assert np.isfinite(line["checks"]["mean_gap"]["value"])
