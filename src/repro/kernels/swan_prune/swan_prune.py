"""Pallas TPU kernel: SWAN winnowing — rotate + magnitude top-k + pack.

Fuses the three steps of Algorithm 1 lines 7-11 for a tile of T vectors:

  1. rotate:   x̂ = x @ P         (one [T,dh]x[dh,dh] MXU matmul)
  2. top-k:    iterative argmax over |x̂| (k VPU passes of [T,dh] work —
               TPU has no hardware sort; k·T·dh compare/select ops are
               cheap relative to the rotation matmul for k ≤ dh)
  3. pack:     vals [T,k] (x̂ at the selected dims) + idx [T,k] int8

The selection loop keeps a running "taken" mask instead of sorting —
deterministic ties (lowest index wins, matching jax.lax.top_k) so the
kernel is bit-compatible with the pure-JAX reference path.

Grid: (B, Kv, S/T).  Tile defaults T=256: x tile 128 KB + P 64 KB + outputs
≈ 96 KB — far under VMEM limits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _prune_kernel(x_ref, p_ref, vals_ref, idx_ref, *, t: int, dh: int,
                  k_max: int):
    x = x_ref[0, 0].astype(jnp.float32)            # [T, dh]
    P = p_ref[0].astype(jnp.float32)               # [dh, dh]
    xh = jax.lax.dot_general(x, P, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    mag = jnp.abs(xh)
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, dh), 1)

    def body(j, carry):
        mag_live, vals, idx = carry
        mx = mag_live.max(axis=1, keepdims=True)                  # [T,1]
        # lowest index among maxima (deterministic, matches lax.top_k)
        is_max = mag_live == mx
        sel = jnp.min(jnp.where(is_max, iota, dh), axis=1, keepdims=True)
        chosen = iota == sel                                       # [T,dh]
        v = jnp.sum(jnp.where(chosen, xh, 0.0), axis=1, keepdims=True)
        vals = jax.lax.dynamic_update_slice(vals, v, (0, j))
        idx = jax.lax.dynamic_update_slice(idx, sel, (0, j))
        mag_live = jnp.where(chosen, -1.0, mag_live)
        return mag_live, vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k_max, body,
        (mag, jnp.zeros((t, k_max), jnp.float32),
         jnp.zeros((t, k_max), jnp.int32)))
    vals_ref[0, 0] = vals.astype(vals_ref.dtype)
    idx_ref[0, 0] = idx.astype(jnp.int8)


def swan_prune_pallas(x, p_rot, k_max: int, *, tile: int = 256,
                      interpret: bool = True):
    """x [B,Kv,S,dh] (post-RoPE k or v), p_rot [Kv,dh,dh] ->
    (vals [B,Kv,S,k_max] x.dtype, idx [B,Kv,S,k_max] int8)."""
    B, Kv, S, dh = x.shape
    t = min(tile, S)
    assert S % t == 0, (S, t)
    kernel = functools.partial(_prune_kernel, t=t, dh=dh, k_max=k_max)
    return pl.pallas_call(
        kernel,
        name="swan_prune",
        grid=(B, Kv, S // t),
        in_specs=[
            pl.BlockSpec((1, 1, t, dh), lambda b, j, s: (b, j, s, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, j, s: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t, k_max), lambda b, j, s: (b, j, s, 0)),
            pl.BlockSpec((1, 1, t, k_max), lambda b, j, s: (b, j, s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Kv, S, k_max), x.dtype),
            jax.ShapeDtypeStruct((B, Kv, S, k_max), jnp.int8),
        ],
        interpret=interpret,
    )(x, p_rot)
