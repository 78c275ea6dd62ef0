"""Jitted wrapper for the swan_prune kernel.  ``interpret=None`` resolves
from the backend like every other kernel (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.dispatch import resolve_interpret
from repro.kernels.swan_prune.swan_prune import swan_prune_pallas


def swan_prune(x, p_rot, k_max: int, tile: int = 256,
               interpret: Optional[bool] = None):
    return _swan_prune(x, p_rot, k_max, tile, resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("k_max", "tile", "interpret"))
def _swan_prune(x, p_rot, k_max: int, tile: int, interpret: bool):
    return swan_prune_pallas(x, p_rot, k_max, tile=tile, interpret=interpret)
