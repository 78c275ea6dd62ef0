"""Pallas TPU kernel: bulk chunk-prefill reads of the packed SWAN cache.

The chunked-prefill attention (`swan_chunk_prefill_attention`) splits per
query into [winnowed sparse prefix ‖ ring ‖ chunk]; the sparse-prefix part
is the bandwidth-bound bulk read this kernel fuses.  Each grid step DMAs
one packed tile (vals [BS,k] + idx int8, optionally int8 vals + f32
scales), expands it ONCE in VMEM via the same one-hot fori-loop as the
decode kernel, and runs all Q = S_chunk·G chunk queries against it through
two MXU matmuls with online-softmax scratch carried across tiles — the
multi-query analogue of ``swan_decode``.  The pure-JAX fallback
(`_sparse_stats_bulk`) expands into an HBM transient instead.

Outputs are MERGEABLE partial stats (m_safe [B,Kv,Q], l [B,Kv,Q],
o_unnorm [B,Kv,Q,dh], all f32): the dense [ring ‖ chunk] side and the
exact merge stay outside (they touch fresh chunk tensors, not the cache).
``m_safe`` follows the `_sparse_stats_bulk` convention — 0.0 where a lane
saw no valid sparse position (empty prefix / dead lane), so the outer
merge is bit-compatible with the pure-JAX stats.

Grid: (B, Kv, S/BS) slab, (B, Kv, Pg) paged — the sequence axis innermost
so scratch carries.  The paged variant takes each lane's page-table row as
a scalar-prefetch operand and gathers pool pages directly into VMEM tiles
(no materialised logical view), exactly like ``swan_decode_paged_pallas``.
Blocks follow the TPU compiler's layout rules as in ``swan_decode``; the
per-query ``m``/``l`` columns leave the kernel as the two columns of one
``[B, Kv, Q, 2]`` output, whose block ends in the array's own dims.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.swan_decode.swan_decode import (
    NEG_INF, SMEM_WHOLE, expand_packed, kernel_name, scale_spec,
    softmax_scratch, unit_axis)


def _chunk_stats_body(meta_ref, q_ref, kv_ref, ki_ref, vv_ref, vi_ref,
                      ks_ref, vs_ref, ml_ref, oo_ref,
                      m_sc, l_sc, acc_sc, *, bs: int, dh: int,
                      n_sblocks: int, quantized: bool):
    lane, sb = pl.program_id(0), pl.program_id(2)
    Q = q_ref.shape[2]
    scale = 1.0 / math.sqrt(dh)
    sp_len = meta_ref[lane]       # this lane's valid sparse-prefix length

    @pl.when(sb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)                        # [Q, dh]
    k_dense = expand_packed(kv_ref[0, 0].astype(jnp.float32),
                            ki_ref[0, 0], dh)                  # [BS, dh]
    v_dense = expand_packed(vv_ref[0, 0].astype(jnp.float32),
                            vi_ref[0, 0], dh)

    s = jax.lax.dot_general(q, k_dense, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if quantized:                     # per-token scales of this head
        s = s * ks_ref[0, 0]                                   # [1, BS]
    t_pos = sb * bs + jax.lax.broadcasted_iota(jnp.int32, (Q, bs), 1)
    s = jnp.where(t_pos < sp_len, s, NEG_INF)

    m_prev, l_prev = m_sc[...], l_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(t_pos < sp_len, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
    if quantized:
        p = p * vs_ref[0, 0]
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v_dense, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(sb == n_sblocks - 1)
    def _write():
        m = m_sc[...]
        # empty-prefix convention of _sparse_stats_bulk: m_safe = 0.0 when
        # no position was valid (all scores stayed at the NEG_INF floor)
        m_safe = jnp.where(m > NEG_INF * 0.5, m, 0.0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Q, 2), 1)
        ml_ref[0, 0] = jnp.where(col == 0, m_safe, l_sc[...])
        oo_ref[0, 0] = acc_sc[...]


def _chunk_kernel(*refs, quantized: bool, **static):
    """Positional-ref adapter for the optional scale operands."""
    meta_ref, q_ref, kv_ref, ki_ref, vv_ref, vi_ref = refs[:6]
    i = 6
    if quantized:
        ks_ref, vs_ref = refs[i:i + 2]
        i += 2
    else:
        ks_ref = vs_ref = None
    ml_ref, oo_ref, m_sc, l_sc, acc_sc = refs[i:i + 5]
    _chunk_stats_body(meta_ref, q_ref, kv_ref, ki_ref, vv_ref, vi_ref,
                      ks_ref, vs_ref, ml_ref, oo_ref,
                      m_sc, l_sc, acc_sc, quantized=quantized, **static)


def _paged_chunk_kernel(tab_ref, *refs, quantized: bool, **static):
    """Scalar-prefetch adapter: the page-table row feeds index maps only."""
    _chunk_kernel(*refs, quantized=quantized, **static)


def _chunk_call(kernel, q, tiles, scales, sp_len, tile_map, lane_map, *,
                bs: int, n_sblocks: int, name: str, prefetch=None,
                interpret=None):
    """The pallas_call shared by the slab and paged stats kernels.  Returns
    (m_safe [B,Kv,Q], l [B,Kv,Q], o_unnorm [B,Kv,Q,dh]); m and l leave the
    kernel as the two columns of one [B,Kv,Q,2] block."""
    from repro.kernels.dispatch import resolve_interpret
    B, Kv, Q, dh = q.shape
    k_max = tiles[0].shape[-1]
    meta = jnp.broadcast_to(jnp.asarray(sp_len, jnp.int32), (B,))
    tile = pl.BlockSpec((1, 1, bs, k_max), tile_map)
    specs = [SMEM_WHOLE, pl.BlockSpec((1, 1, Q, dh), lane_map)] + [tile] * 4
    if scales:
        specs += [scale_spec(tile_map, bs)] * 2
    out_specs = [pl.BlockSpec((1, 1, Q, 2), lane_map),
                 pl.BlockSpec((1, 1, Q, dh), lane_map)]
    out_shape = (jax.ShapeDtypeStruct((B, Kv, Q, 2), jnp.float32),
                 jax.ShapeDtypeStruct((B, Kv, Q, dh), jnp.float32))
    kernel = functools.partial(kernel, bs=bs, dh=dh, n_sblocks=n_sblocks,
                               quantized=bool(scales))
    scratch = softmax_scratch(Q, dh)
    grid = (B, Kv, n_sblocks)
    operands = [meta, q, *tiles, *map(unit_axis, scales)]
    name = kernel_name(name, bool(scales))
    if prefetch is None:
        call = pl.pallas_call(kernel, grid=grid, in_specs=specs,
                              out_specs=out_specs, out_shape=out_shape,
                              scratch_shapes=scratch, name=name,
                              interpret=resolve_interpret(interpret))
    else:
        call = pl.pallas_call(
            kernel, out_shape=out_shape, name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=specs,
                out_specs=out_specs, scratch_shapes=scratch),
            interpret=resolve_interpret(interpret))
        operands = [prefetch] + operands
    ml, o = call(*operands)
    return ml[..., 0], ml[..., 1], o


def swan_chunk_stats_pallas(q, k_vals, k_idx, v_vals, v_idx, sp_len,
                            k_scale=None, v_scale=None, *,
                            block_s: int = 256,
                            interpret: Optional[bool] = None):
    """q [B,Kv,Q,dh] (Q = S_chunk·G flattened queries); packed sparse
    [B,Kv,S,k]; per-lane ``sp_len [B]``.  Returns (m_safe [B,Kv,Q],
    l [B,Kv,Q], o_unnorm [B,Kv,Q,dh]) — drop-in for
    ``swan_attention._sparse_stats_bulk``."""
    S = k_vals.shape[2]
    bs = min(block_s, S)
    assert S > 0 and S % bs == 0, (S, bs)
    scales = [] if k_scale is None else [k_scale, v_scale]
    return _chunk_call(_chunk_kernel, q, [k_vals, k_idx, v_vals, v_idx],
                       scales, sp_len, lambda b_, j, s: (b_, j, s, 0),
                       lambda b_, j, s: (b_, j, 0, 0), bs=bs,
                       n_sblocks=S // bs, name="swan_chunk_stats_slab",
                       interpret=interpret)


def swan_chunk_stats_paged_pallas(q, pool_k_vals, pool_k_idx, pool_v_vals,
                                  pool_v_idx, sp_len, page_rows,
                                  pool_k_scale=None, pool_v_scale=None, *,
                                  interpret: Optional[bool] = None):
    """Paged bulk-chunk stats: pool sides [n_pages,Kv,ps,k] + per-lane
    ``page_rows [B,Pg]`` gathered into VMEM tiles inside the kernel —
    the chunk path's replacement for ``paged_logical_view`` +
    ``_sparse_stats_bulk``."""
    B, Pg = q.shape[0], page_rows.shape[1]
    assert page_rows.shape == (B, Pg), page_rows.shape
    assert Pg >= 1, "empty page-table prefix: caller must short-circuit"
    scales = [] if pool_k_scale is None else [pool_k_scale, pool_v_scale]
    return _chunk_call(_paged_chunk_kernel, q,
                       [pool_k_vals, pool_k_idx, pool_v_vals, pool_v_idx],
                       scales, sp_len,
                       lambda b_, j, s, tab: (tab[b_, s], j, 0, 0),
                       lambda b_, j, s, tab: (b_, j, 0, 0),
                       bs=pool_k_vals.shape[2], n_sblocks=Pg,
                       name="swan_chunk_stats_paged", prefetch=page_rows,
                       interpret=interpret)
