"""Pallas TPU kernel: SWAN hybrid-cache decode attention.

The kernel consumes the *compressed* cache directly (paper's
"decompression-free" claim, TPU-native): each grid step DMAs one packed
sparse tile (vals [BS,k] + idx [BS,k] int8, optionally int8 vals + f32
scales) from HBM into VMEM and reads it there, never materialising a dense
cache in HBM.  The K side is never expanded: the scores gather each query
row along lanes at the tile's indices (:func:`gather_scores`).  The V side
is expanded by one broadcast compare of the indices against a lane iota
(:func:`expand_packed`) and feeds the weighted-value MXU matmul.  Both go
through a flash-style online-softmax accumulator held in VMEM scratch.
The final grid step folds in the dense ring buffer.

Neither reader loops over the packed columns: Mosaic lowers no dynamic
lane slice, so reading column ``j`` takes a masked cross-lane reduction,
and inside a carried loop the 2k reductions of a tile run one after
another (14.7 us per (lane, KV head, page) cell on a v5e, whatever the
tile's bytes).  Decode has G <= 8 query rows, so gathering q (a lane
permute per vreg and query row) is cheaper than expanding K for an MXU
matmul; the chunk kernel, with Q = 256·G rows, expands both sides once
and keeps its two matmuls.

Grid: (B, Kv, S/BS) — the sequence axis iterates innermost so the scratch
accumulators carry across sparse tiles.

Layout rules of the TPU compiler (Mosaic), which interpret mode does not
enforce: the last two dims of every VMEM block are multiples of (8, 128)
or equal the array's dims.  So the per-lane scalars (decode position,
sparse length) live whole in SMEM, and ``buf_pos`` and the int8 scales
get a unit axis (``[B, 1, b]``, ``[.., Kv, 1, S]``), so each grid step
fetches one ``(1, BS)`` scale row of its own head; the scales are applied
to the score and probability rows after the matmuls, where they are
lane-major already.
On a slab cache that makes a quantized ``block_s`` a multiple of 128 (or
all of S); a page is always a whole block.  Whether a shape fits VMEM is
decided by the compile
(``tests/test_tpu_compile.py`` compiles every serve kernel for a v5e at
OLMo-1B widths); ``precheck`` only mirrors the block rules.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

LANE_WIDTH = 128        # last-dim tiling unit
SUBLANE = 8             # second-to-last-dim tiling unit


def precheck(*, S: int, k_max: int, dh: int, block_s: int = 256,
             quantized: bool = False, paged: bool = False) -> dict:
    """Static grid and block-layout validation for the SWAN kernels
    (decode and chunk stats share their packed-tile and scale BlockSpecs).

    Returns ``{"errors": [...], "warnings": [...]}``; errors are shapes the
    kernel asserts on or the TPU compiler refuses, warnings are perf
    hazards (a ``dh`` below the lane width pads every tile).  For a paged
    kernel pass ``S = Pg * page_size``, ``block_s = page_size`` and
    ``paged=True``: a page is a whole block, so it always conforms.  VMEM
    is not modelled: the compile is the authority."""
    bs = min(block_s, S)
    errors = []
    if bs <= 0 or S % bs:
        errors.append(f"sparse length S={S} not divisible by block bs={bs}")
    elif not paged and bs != S:
        if bs % SUBLANE:
            errors.append(f"block bs={bs} is neither S={S} nor a multiple "
                          f"of the sublane tile {SUBLANE}")
        if quantized and bs % LANE_WIDTH:
            errors.append(f"quantized block bs={bs} is neither S={S} nor a "
                          f"multiple of the lane tile {LANE_WIDTH} (scale "
                          "blocks are lane-major)")
    if k_max > dh:
        errors.append(f"k_max={k_max} exceeds dh={dh}: one-hot expansion "
                      "would scatter out of range")
    warnings = []
    if dh % LANE_WIDTH:
        warnings.append(f"dh={dh} not a multiple of lane width "
                        f"{LANE_WIDTH}: tiles pad to 128 lanes")
    return {"errors": errors, "warnings": warnings}


def expand_packed(vals, idx, dh: int):
    """One-hot expansion in VMEM: packed [BS,k] vals (f32) + idx -> dense
    [BS,dh] f32, loop-free.  The indices, as ``[BS, k, 1]``, are compared
    once against a lane iota ``[1, 1, dh]``; a chain of selects over the
    k axis's 8-row sublane groups keeps the hit value, and a sublane sum
    folds the last 8.  Exact: a row's indices are distinct (``topk_pack``
    sorts a full iota), so an output element has at most one hit; an
    index outside ``[0, dh)`` matches no lane."""
    bs, k_max = vals.shape
    idx = idx.astype(jnp.int32)
    pad = -k_max % SUBLANE
    if pad:                           # zero values at an index none matches
        vals = jnp.concatenate([vals, jnp.zeros((bs, pad), jnp.float32)], 1)
        idx = jnp.concatenate([idx, jnp.full((bs, pad), -1, jnp.int32)], 1)
    idx, vals = idx[:, :, None], vals[:, :, None]
    lane_d = jax.lax.broadcasted_iota(jnp.int32, (bs, SUBLANE, dh), 2)
    out = 0.0
    for j in range(0, k_max + pad, SUBLANE):
        group = functools.partial(jax.lax.slice_in_dim, start_index=j,
                                  limit_index=j + SUBLANE, axis=1)
        out = jnp.where(group(idx) == lane_d, group(vals), out)
    return out.sum(axis=1)


def gather_scores(q, vals, idx):
    """Scores of G query rows against a packed tile, never expanded:
    ``s[g, t] = sum_j vals[t, j] * q[g, idx[t, j]]`` (f32 [G, BS]).  Row
    ``g`` of ``q`` is gathered along lanes at the tile's indices, times the
    values, summed over the tile's lanes.  The tile is padded to ``dh``
    lanes with zero values, and an index outside ``[0, dh)`` (a trash-page
    tile holds whatever was last written there) reads lane 0: the gather
    is promised in bounds, and the ``sp_len`` mask drops such rows."""
    bs, k_max = vals.shape
    G, dh = q.shape
    idx = idx.astype(jnp.int32)
    idx = jnp.where((idx >= 0) & (idx < dh), idx, 0)
    if k_max < dh:
        vals = jnp.concatenate(
            [vals, jnp.zeros((bs, dh - k_max), jnp.float32)], axis=1)
        idx = jnp.concatenate(
            [idx, jnp.zeros((bs, dh - k_max), jnp.int32)], axis=1)
    cols = [jnp.sum(vals * jnp.take_along_axis(
                jnp.broadcast_to(q[g:g + 1], (bs, dh)), idx, axis=1,
                mode="promise_in_bounds"), axis=1, keepdims=True)
            for g in range(G)]                                 # G x [BS, 1]
    return jnp.concatenate(cols, axis=1).T


def _swan_decode_body(meta_ref, q_ref, kv_ref, ki_ref, vv_ref, vi_ref,
                      ks_ref, vs_ref, bk_ref, bv_ref, bp_ref, o_ref,
                      m_sc, l_sc, acc_sc, *, bs: int, dh: int,
                      n_sblocks: int, quantized: bool):
    lane, sb = pl.program_id(0), pl.program_id(2)
    G = q_ref.shape[2]
    scale = 1.0 / math.sqrt(dh)
    pos = meta_ref[2 * lane]          # this sequence's decode position
    sp_len = meta_ref[2 * lane + 1]   # this sequence's valid sparse length

    @pl.when(sb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)                        # [G, dh]

    # ---- sparse tile ------------------------------------------------------
    s = gather_scores(q, kv_ref[0, 0].astype(jnp.float32),
                      ki_ref[0, 0]) * scale                    # [G, BS]
    v_dense = expand_packed(vv_ref[0, 0].astype(jnp.float32),
                            vi_ref[0, 0], dh)                  # [BS, dh]
    if quantized:                     # per-token scales of this head
        s = s * ks_ref[0, 0]                                   # [1, BS]
    t_pos = sb * bs + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
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

    # ---- final step: dense ring buffer + write-out -------------------------
    @pl.when(sb == n_sblocks - 1)
    def _finalize():
        bk = bk_ref[0, 0].astype(jnp.float32)                  # [b, dh]
        bv = bv_ref[0, 0].astype(jnp.float32)
        bpos = bp_ref[0]                                       # [1, b]
        s_b = jax.lax.dot_general(q, bk, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * scale
        valid = (bpos >= 0) & (bpos <= pos)
        s_b = jnp.where(valid, s_b, NEG_INF)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_fin = jnp.maximum(m_prev, s_b.max(axis=1, keepdims=True))
        p_b = jnp.where(valid, jnp.exp(s_b - m_fin), 0.0)
        corr = jnp.exp(m_prev - m_fin)
        l_fin = l_prev * corr + p_b.sum(axis=1, keepdims=True)
        acc = acc_sc[...] * corr + jax.lax.dot_general(
            p_b, bv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, 0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


def _decode_kernel(*refs, quantized: bool, **static):
    """Positional-ref adapter: the scale refs exist only for quantized
    caches (dummy f32 scale streams would double the packed-tile HBM
    traffic for nothing), so the pallas_call operand list — and hence the
    kernel signature — is built conditionally."""
    meta_ref, q_ref, kv_ref, ki_ref, vv_ref, vi_ref = refs[:6]
    i = 6
    if quantized:
        ks_ref, vs_ref = refs[i:i + 2]
        i += 2
    else:
        ks_ref = vs_ref = None
    bk_ref, bv_ref, bp_ref, o_ref, m_sc, l_sc, acc_sc = refs[i:i + 7]
    _swan_decode_body(meta_ref, q_ref, kv_ref, ki_ref, vv_ref, vi_ref,
                      ks_ref, vs_ref, bk_ref, bv_ref, bp_ref, o_ref,
                      m_sc, l_sc, acc_sc, quantized=quantized, **static)


def _decode_meta(pos, sp_len, B: int):
    """Per-lane scalars, interleaved ``[pos_0, sp_0, pos_1, sp_1, ...]``
    (int32 [2B], whole in SMEM)."""
    return jnp.stack([
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)),
        jnp.broadcast_to(jnp.asarray(sp_len, jnp.int32), (B,)),
    ], axis=1).reshape(2 * B)


SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def kernel_name(base: str, quantized: bool) -> str:
    """The stable ``pallas_call`` name a profile shows for a kernel
    variant: ``base``, plus ``_int8`` over an int8-quantized cache."""
    return base + "_int8" if quantized else base


def paged_decode_grid(n_slots: int, n_kv: int, n_pages: int) -> tuple:
    """The paged decode kernel's grid: one step per (slot, KV head, page
    of the shipped table prefix), idle slots and unmapped pages included.
    The serve engine counts the kernel's (slot, page) cells from it."""
    return (n_slots, n_kv, n_pages)


def softmax_scratch(rows: int, dh: int) -> list:
    """Online-softmax VMEM scratch for ``rows`` query rows: running max m,
    running sum l, unnormalised output acc."""
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, dh), jnp.float32)]


def scale_spec(tile_map, bs: int):
    """int8 scales as ``[.., Kv, 1, S]`` (see :func:`unit_axis`): the
    lane-major ``(1, bs)`` row of the packed tile's head and sequence
    block."""
    def sc_map(*a):
        t = tile_map(*a)
        return (t[0], t[1], 0, t[2])
    return pl.BlockSpec((1, 1, 1, bs), sc_map)


def unit_axis(x):
    """``[.., n]`` -> ``[.., 1, n]``: a row block then equals the array's
    second-to-last dim, which the block rules accept."""
    return x.reshape(x.shape[:-1] + (1, x.shape[-1]))


def _specs(tile_map, lane_map, *, G, dh, bs, k_max, b, quantized):
    """in_specs shared by the slab and paged decode kernels: ``tile_map``
    addresses a packed tile (and its scale block), ``lane_map`` the
    per-lane q / ring blocks."""
    tile = pl.BlockSpec((1, 1, bs, k_max), tile_map)
    specs = [SMEM_WHOLE,                                       # meta
             pl.BlockSpec((1, 1, G, dh), lane_map),            # q
             tile, tile, tile, tile]                           # k/v vals, idx
    if quantized:
        specs += [scale_spec(tile_map, bs)] * 2                # k/v scale
    ring = pl.BlockSpec((1, 1, b, dh), lane_map)
    pos_map = lambda *a: (lane_map(*a)[0], 0, 0)               # noqa: E731
    specs += [ring, ring, pl.BlockSpec((1, 1, b), pos_map)]    # buf k/v/pos
    return specs


def swan_decode_pallas(q, k_vals, k_idx, v_vals, v_idx, buf_k, buf_v,
                       buf_pos, pos, sp_len, k_scale=None, v_scale=None,
                       *, block_s: int = 256,
                       interpret: Optional[bool] = None):
    """q [B,Kv,G,dh]; packed sparse [B,Kv,S,k]; buffer [B,Kv,b,dh];
    buf_pos [B,b].  ``pos``/``sp_len`` are scalars or per-sequence [B]
    (continuous batching: each sequence masks its own ring + sparse prefix).

    Returns o [B,Kv,G,dh].  ``interpret=None`` resolves from the backend
    (compiled on TPU, interpreter elsewhere — repro.kernels.dispatch).
    """
    from repro.kernels.dispatch import resolve_interpret
    B, Kv, G, dh = q.shape
    S, k_max = k_vals.shape[2], k_vals.shape[3]
    b = buf_k.shape[2]
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    assert buf_pos.shape == (B, b), buf_pos.shape
    n_sblocks = S // bs
    quantized = k_scale is not None

    kernel = functools.partial(
        _decode_kernel, bs=bs, dh=dh, n_sblocks=n_sblocks,
        quantized=quantized)
    specs = _specs(lambda b_, j, s: (b_, j, s, 0),
                   lambda b_, j, s: (b_, j, 0, 0), G=G, dh=dh, bs=bs,
                   k_max=k_max, b=b, quantized=quantized)
    operands = [_decode_meta(pos, sp_len, B), q, k_vals, k_idx, v_vals,
                v_idx]
    if quantized:
        operands += [unit_axis(k_scale), unit_axis(v_scale)]
    operands += [buf_k, buf_v, unit_axis(buf_pos)]
    return pl.pallas_call(
        kernel,
        name=kernel_name("swan_decode_slab", quantized),
        grid=(B, Kv, n_sblocks),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b_, j, s: (b_, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, dh), q.dtype),
        scratch_shapes=softmax_scratch(G, dh),
        interpret=resolve_interpret(interpret),
    )(*operands)


def _paged_decode_kernel(tab_ref, *refs, quantized: bool, **static):
    """Scalar-prefetch adapter: ``tab_ref`` (the page-table prefix) is
    consumed by the BlockSpec index maps only — the body sees exactly the
    slab tile layout (VMEM tiles don't care which HBM page they came
    from)."""
    _decode_kernel(*refs, quantized=quantized, **static)


def swan_decode_paged_pallas(q, pool_k_vals, pool_k_idx, pool_v_vals,
                             pool_v_idx, buf_k, buf_v, buf_pos, pos, sp_len,
                             page_tab, pool_k_scale=None, pool_v_scale=None,
                             *, interpret: Optional[bool] = None):
    """Paged-pool decode: the packed sparse sides live in a shared page
    pool ``[n_pages, Kv, ps, k]`` and each sequence's logical prefix is
    named by ``page_tab [B, Pg]`` (a power-of-two table prefix, unmapped
    entries -> trash page 0).

    The gather happens INSIDE the kernel: ``page_tab`` rides as a
    scalar-prefetch operand (SMEM, shipped before the grid runs) and the
    pool BlockSpec index maps read it — grid step (b, j, s) DMAs physical
    page ``page_tab[b, s]`` straight into the VMEM tile.  No
    ``[B, Pg*ps, k]`` logical view is ever materialised in HBM (that XLA
    gather is exactly the re-inflation `paged_logical_view` pays on the
    pure-JAX path).  Trash-page tiles DMA garbage that the per-sequence
    ``sp_len`` mask zeroes: logical positions >= sp_len are masked no
    matter what physical page backs them.

    Returns o [B,Kv,G,dh] — same contract as ``swan_decode_pallas`` over
    ``paged_logical_view``.
    """
    from repro.kernels.dispatch import resolve_interpret
    B, Kv, G, dh = q.shape
    n_pages, _, ps, k_max = pool_k_vals.shape
    b = buf_k.shape[2]
    Pg = page_tab.shape[1]
    assert page_tab.shape == (B, Pg), page_tab.shape
    assert Pg >= 1, "empty page-table prefix: caller must ship >= 1 page"
    assert buf_pos.shape == (B, b), buf_pos.shape
    quantized = pool_k_scale is not None

    kernel = functools.partial(
        _paged_decode_kernel, bs=ps, dh=dh, n_sblocks=Pg,
        quantized=quantized)
    # index-map signatures: (grid indices..., scalar-prefetch refs...);
    # pool tiles are the paged VMEM gather — physical page from the table
    specs = _specs(lambda b_, j, s, tab: (tab[b_, s], j, 0, 0),
                   lambda b_, j, s, tab: (b_, j, 0, 0), G=G, dh=dh, bs=ps,
                   k_max=k_max, b=b, quantized=quantized)
    operands = [_decode_meta(pos, sp_len, B), q, pool_k_vals, pool_k_idx,
                pool_v_vals, pool_v_idx]
    if quantized:
        operands += [unit_axis(pool_k_scale), unit_axis(pool_v_scale)]
    operands += [buf_k, buf_v, unit_axis(buf_pos)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=paged_decode_grid(B, Kv, Pg),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, 1, G, dh),
                               lambda b_, j, s, tab: (b_, j, 0, 0)),
        scratch_shapes=softmax_scratch(G, dh),
    )
    return pl.pallas_call(
        kernel,
        name=kernel_name("swan_decode_paged", quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_tab, *operands)
