"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  The dry-run sets XLA_FLAGS before any jax import to get 512
placeholder host devices; smoke tests and benchmarks see the real device
count (1 CPU here).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4))."""
    return _mk(tuple(shape), tuple(axes))


def make_serve_mesh(data_parallel: int):
    """1-axis ('data',) mesh for the sharded serve engine
    (repro.runtime.serve_engine with mesh=): the engine's batched state —
    and the paged pool's page axis — shard over 'data'; model weights are
    replicated across it."""
    return _mk((int(data_parallel),), ("data",))
