"""Activation-sharding hooks.

Model code is mesh-agnostic: it calls ``shard(x, kind)`` at well-known
points; the launcher installs a ``ShardingRules`` mapping kinds to
``NamedSharding``s.  With no rules installed (CPU tests) the hooks are
no-ops, so the same model code runs everywhere.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


class ShardingRules:
    def __init__(self, mesh: Mesh, kinds: Dict[str, P]):
        self.mesh = mesh
        self.kinds = kinds

    def sharding(self, kind: str) -> Optional[NamedSharding]:
        spec = self.kinds.get(kind)
        if spec is None:
            return None
        return NamedSharding(self.mesh, spec)


def current_rules() -> Optional[ShardingRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    prev = current_rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def shard(x, kind: str):
    """Apply a sharding constraint if rules are installed; else identity."""
    rules = current_rules()
    if rules is None:
        return x
    s = rules.sharding(kind)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the serve and
    split-S bodies are lane-local, so their outputs' replication is the
    caller's contract, not something the tracer can prove."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
