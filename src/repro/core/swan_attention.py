"""SWAN attention over the hybrid cache (paper Algorithm 1, lines 13-17).

The decode step attends to the *compressed* cache directly:

  scores = [ q̂ · expand(sparse) ‖ q̂ · buffer ] ;  o = softmax(scores) · V

The pure-JAX path computes scores as a gather over q̂ at the packed indices
and the value side as a scatter-add — no dense [S, dh] tensor is ever
materialised (the paper's sparse-dense matvec, TPU-translated per
DESIGN.md §2).  Under sequence sharding the sparse part runs as an
explicit split-S ``shard_map`` (flash-decoding): local gather/scatter per
shard plus one pmax/psum stat merge.  The Pallas kernel in
``repro.kernels.swan_decode`` performs the same computation with explicit
VMEM tiles and in-register expansion.

In ``truncate`` mode no gather/scatter happens at all: scores are a dense
low-rank dot over the leading k dims (pure MXU).

Batch-shardability (audited for the mesh-sharded serve engine): every
attention path here — decode, paged decode, and the bulk chunk-prefill
reads — is lane-local: gathers/scatters index each lane's own cache rows
(or its own page-table row), softmax stats reduce over sequence/k dims
only, and the ONLY collectives in this module are the opt-in split-S
pmax/psum merge above, which fires solely when sharding rules place the
sequence dim on a mesh axis.  The serve engine shards the BATCH axis via
``shard_map``, under which these functions run unchanged per shard.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.hybrid_cache import per_seq_pos, sparse_len
from repro.core.winnow import dequantize_int8, unpack_dense

Params = Dict[str, Any]


def _deq(side: Params) -> jnp.ndarray:
    """Packed values ready for matmul.  Non-quantized caches stay in their
    storage dtype (bf16): converting the whole cache to f32 would double the
    HBM bytes the decode step streams (§Perf iteration 1) — instead every
    contraction below accumulates in f32 via preferred_element_type."""
    vals = side["vals"]
    if "scale" in side:
        return dequantize_int8(vals, side["scale"], jnp.float32)
    if vals.dtype == jnp.float8_e4m3fn:   # paper's 8-bit float: direct cast
        return vals.astype(jnp.bfloat16)
    return vals


def _dot_f32(subscripts: str, a, b) -> jnp.ndarray:
    return jnp.einsum(subscripts, a, b, preferred_element_type=jnp.float32)


def _sparse_stats(qf: jnp.ndarray, k_side: Params, v_side: Params, swan,
                  sp_len, s_offset) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flash-decoding partial stats over (a shard of) the sparse cache.

    Decompression-free (paper Algorithm 1 line 15, TPU-adapted):
      scores[t] = Σ_j k_vals[t,j] · q̂[k_idx[t,j]]          (gather over q̂)
      o[d]      = Σ_t Σ_j (p[t]·v_vals[t,j]) δ[v_idx[t,j]=d]  (scatter-add)
    No dense [S, dh] tensor is ever materialised.  In truncate mode the
    score collapses to a dense low-rank dot (pure MXU).

    ``sp_len`` is per-sequence [B]: each sequence masks its own valid
    sparse prefix (continuous batching decodes mixed-length sequences).

    Returns (m [B,Kv,G], l [B,Kv,G], o_unnorm [B,Kv,G,dh]) — mergeable
    partial softmax statistics.
    """
    B, Kv, G, dh = qf.shape
    S = k_side["vals"].shape[2]
    k_max = swan.k_max
    scale = 1.0 / math.sqrt(dh)
    trunc = "idx" not in k_side

    kv_ = _deq(k_side)                                 # [B,Kv,S,k]
    vv_ = _deq(v_side)
    if trunc:
        s_sp = _dot_f32("bjgk,bjtk->bjgt",
                        qf[..., :k_max].astype(kv_.dtype), kv_) * scale
    else:
        kidx = k_side["idx"].astype(jnp.int32)         # [B,Kv,S,k]
        # gather q̂ in the CACHE dtype: the [B,Kv,G,S,k] gather result is the
        # largest intermediate on the score side — keeping it bf16 halves
        # its traffic (f32 accumulation happens inside the dot)
        q_b = jnp.broadcast_to(qf.astype(kv_.dtype)[:, :, :, None, :],
                               (B, Kv, G, S, dh))
        q_at = jnp.take_along_axis(
            q_b, jnp.broadcast_to(kidx[:, :, None], (B, Kv, G, S, k_max)),
            axis=-1)
        s_sp = _dot_f32("bjgtk,bjtk->bjgt", q_at, kv_) * scale
    valid = ((s_offset + jnp.arange(S))[None, None, None, :]
             < sp_len[:, None, None, None])
    s_sp = jnp.where(valid, s_sp, -jnp.inf)

    m = s_sp.max(-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid, jnp.exp(s_sp - m_safe[..., None]), 0.0)
    l = p.sum(-1)

    if trunc:
        o = _dot_f32("bjgt,bjtk->bjgk", p.astype(vv_.dtype), vv_)
        o = jnp.pad(o, ((0, 0),) * 3 + ((0, dh - k_max),))
    else:
        vidx = v_side["idx"].astype(jnp.int32)
        w = p[..., None] * vv_[:, :, None]             # [B,Kv,G,S,k]
        o = jnp.zeros((B, Kv, G, dh), jnp.float32)
        bi, ji, gi = jnp.meshgrid(jnp.arange(B), jnp.arange(Kv),
                                  jnp.arange(G), indexing="ij")
        bi = jnp.broadcast_to(bi[..., None, None], w.shape)
        ji = jnp.broadcast_to(ji[..., None, None], w.shape)
        gi = jnp.broadcast_to(gi[..., None, None], w.shape)
        di = jnp.broadcast_to(vidx[:, :, None], w.shape)
        o = o.at[bi, ji, gi, di].add(w)
    return m_safe, l, o


def _sparse_stats_sharded(qf, cache, swan, sp_len, mesh, seq_axis: str):
    """Split-S across ``seq_axis``: each shard computes local stats over its
    sequence slice (everything local — gather/scatter stay single-device),
    then the O(dh) stats are merged with one pmax + two psums.  This is the
    flash-decoding schedule, written explicitly with shard_map so GSPMD
    cannot fall back to gathering the compressed cache."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.api import shard_map_unchecked

    B = qf.shape[0]
    S = cache["k"]["vals"].shape[2]
    n_shard = mesh.shape[seq_axis]
    s_local = S // n_shard

    # batch stays sharded over the remaining (data-parallel) axes
    dp = tuple(a for a in mesh.axis_names if a != seq_axis)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    bspec = dp if (dp and B % n_dp == 0 and B >= n_dp) else None

    side_spec = {"vals": P(bspec, None, seq_axis, None)}
    if "idx" in cache["k"]:
        side_spec["idx"] = P(bspec, None, seq_axis, None)
    if "scale" in cache["k"]:
        side_spec["scale"] = P(bspec, None, seq_axis)

    def local_fn(q, k_side, v_side, sp_len_):
        off = jax.lax.axis_index(seq_axis) * s_local
        m, l, o = _sparse_stats(q, k_side, v_side, swan, sp_len_, off)
        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        o_g = jax.lax.psum(o * corr[..., None], seq_axis)
        return m_g, l_g, o_g

    return shard_map_unchecked(
        local_fn, mesh,
        (P(bspec, None, None, None), side_spec, side_spec, P(bspec)),
        (P(bspec, None, None), P(bspec, None, None),
         P(bspec, None, None, None)),
    )(qf, cache["k"], cache["v"], jnp.asarray(sp_len))


def swan_decode_attention(q_hat: jnp.ndarray, cache: Params, swan, cfg,
                          pos, mesh=None, seq_axis: Optional[str] = None
                          ) -> jnp.ndarray:
    """q̂ [B, Kv, G, dh] (rotated, grouped) -> o [B, Kv, G, dh] (rotated).

    Joint exact softmax over [winnowed sparse ‖ dense buffer].  ``pos`` may
    be a scalar (lockstep) or per-sequence [B] (continuous batching).  When
    ``mesh``/``seq_axis`` are given the sparse part runs as an explicit
    split-S shard_map (flash-decoding)."""
    B, Kv, G, dh = q_hat.shape
    S = cache["k"]["vals"].shape[2]
    qf = q_hat.astype(jnp.float32)
    pos = per_seq_pos(pos, B)
    sp_len = sparse_len(swan, pos)                     # [B]
    scale = 1.0 / math.sqrt(dh)

    if (mesh is not None and seq_axis in mesh.axis_names
            and S % mesh.shape[seq_axis] == 0 and S >= mesh.shape[seq_axis]):
        m_sp, l_sp, o_sp = _sparse_stats_sharded(qf, cache, swan, sp_len,
                                                 mesh, seq_axis)
    else:
        m_sp, l_sp, o_sp = _sparse_stats(qf, cache["k"], cache["v"], swan,
                                         sp_len, 0)

    if cache["buf_k"].shape[2] == 0:    # bt=0 ablation: sparse-only softmax
        denom = jnp.maximum(l_sp, 1e-30)
        return (o_sp / denom[..., None]).astype(q_hat.dtype)

    # ---- dense buffer part + exact merge ------------------------------------
    bk = cache["buf_k"]                                # [B,Kv,b,dh] storage dtype
    bv = cache["buf_v"]
    s_b = _dot_f32("bjgd,bjtd->bjgt", qf.astype(bk.dtype), bk) * scale
    b_valid = (cache["buf_pos"] >= 0) & (cache["buf_pos"] <= pos[:, None])
    s_b = jnp.where(b_valid[:, None, None], s_b, -jnp.inf)
    m_b = s_b.max(-1)
    m_b = jnp.where(jnp.isfinite(m_b), m_b, 0.0)
    p_b = jnp.where(b_valid[:, None, None], jnp.exp(s_b - m_b[..., None]), 0.0)
    l_b = p_b.sum(-1)
    o_b = _dot_f32("bjgt,bjtd->bjgd", p_b.astype(bv.dtype), bv)

    m = jnp.maximum(m_sp, m_b)
    c_sp = jnp.exp(m_sp - m)
    c_b = jnp.exp(m_b - m)
    denom = jnp.maximum(l_sp * c_sp + l_b * c_b, 1e-30)
    o = (o_sp * c_sp[..., None] + o_b * c_b[..., None]) / denom[..., None]
    return o.astype(q_hat.dtype)


def _sparse_stats_bulk(qf: jnp.ndarray, k_side: Params, v_side: Params,
                       swan, sp_len, dh: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partial softmax stats of MANY queries ``qf [B, Kv, Q, dh]`` against
    (the valid prefix of) a packed sparse cache — the chunked-prefill bulk
    read.

    The decode-shaped gather/scatter in ``_sparse_stats`` touches
    O(Q · S · k) elements; with a chunk's Q = S_chunk · G queries that is
    the wrong kernel shape.  Here each packed vector is expanded ONCE
    (O(S · k) scatter, amortised over every query) into a chunk-local dense
    transient and both sides become plain MXU dots — the multi-query
    analogue.  The CACHE stays packed end to end and single-token decode
    never takes this path, so the decompression-free serving property is
    untouched; the [S, dh] view is the same transient scale a monolithic
    prefill's fresh k̂/v̂ occupy.
    """
    B, Kv, Q, _ = qf.shape
    S = k_side["vals"].shape[2]
    k_max = swan.k_max
    scale = 1.0 / math.sqrt(dh)
    kv_ = _deq(k_side)                                 # [B,Kv,S,k]
    vv_ = _deq(v_side)
    if "idx" in k_side:
        kd = unpack_dense(kv_, k_side["idx"], dh)      # [B,Kv,S,dh]
        s_sp = _dot_f32("bjqd,bjtd->bjqt", qf.astype(kd.dtype), kd) * scale
    else:                                              # truncate: low-rank dot
        s_sp = _dot_f32("bjqk,bjtk->bjqt",
                        qf[..., :k_max].astype(kv_.dtype), kv_) * scale
    valid = jnp.arange(S)[None, None, None, :] < sp_len[:, None, None, None]
    s_sp = jnp.where(valid, s_sp, -jnp.inf)
    m = s_sp.max(-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid, jnp.exp(s_sp - m_safe[..., None]), 0.0)
    l = p.sum(-1)
    if "idx" in v_side:
        vd = unpack_dense(vv_, v_side["idx"], dh)
        o = _dot_f32("bjqt,bjtd->bjqd", p.astype(vd.dtype), vd)
    else:
        o = _dot_f32("bjqt,bjtk->bjqk", p.astype(vv_.dtype), vv_)
        o = jnp.pad(o, ((0, 0),) * 3 + ((0, dh - k_max),))
    return m_safe, l, o


def swan_chunk_prefill_attention(q_hat: jnp.ndarray, k_hat: jnp.ndarray,
                                 v_new: jnp.ndarray, cache: Params, swan,
                                 cfg, start, true_len,
                                 sparse_stats=None) -> jnp.ndarray:
    """Attention for a prefill CHUNK resuming from a populated hybrid cache.

    ``q_hat [B, S, Kv, G, dh]`` / ``k_hat [B, S, Kv, dh]`` / ``v_new
    [B, S, Kv, dh]`` are the chunks' fresh rotated projections; ``start``
    may be a scalar or per-lane [B] — the batched concurrent prefill packs
    several slots' chunks into one call, lane ``p`` at absolute positions
    [start_p, start_p + S) against a ``cache`` holding its tokens
    [0, start_p) (a slab layout, or a ``paged_logical_view`` of each lane's
    pages).  Joint exact softmax per query over

        [ winnowed sparse prefix [0, start-b) ‖ ring [start-b, start) ‖
          chunk (causal) ]

    — i.e. the chunk sees older tokens exactly as a decode step at the same
    position would, and recent tokens (ring + chunk) dense.  Ring entries
    are additionally masked to positions < start so a just-freed slot's
    dirty ring (from the previous occupant, positions that may exceed
    ``start``) never leaks into a new prompt's first chunks.  Chunk padding
    keys sit at positions >= start + true_len > every real query position,
    so the causal mask hides them; padded queries (and whole dead lanes)
    produce garbage rows the caller discards.

    ``sparse_stats``: optional precomputed (m_safe, l, o_unnorm) partial
    stats over the sparse prefix, each in the bulk [B, Kv, S·G(, dh)]
    query-flattened layout — the Pallas bulk-chunk kernel
    (``repro.kernels.flash_prefill.swan_chunk``) supplies these and
    ``cache["k"]/["v"]`` are then never touched (the paged caller skips
    materialising the logical view entirely).
    """
    B, S, Kv, G, dh = q_hat.shape
    scale = 1.0 / math.sqrt(dh)
    start = per_seq_pos(start, B)                            # [B]
    qf = q_hat.astype(jnp.float32).transpose(0, 2, 1, 3, 4)  # [B,Kv,S,G,dh]

    if sparse_stats is not None:
        m_sp, l_sp, o_sp = sparse_stats
    else:
        sp_len = jnp.maximum(start - swan.buffer, 0)         # [B]
        m_sp, l_sp, o_sp = _sparse_stats_bulk(qf.reshape(B, Kv, S * G, dh),
                                              cache["k"], cache["v"], swan,
                                              sp_len, dh)
    m_sp = m_sp.reshape(B, Kv, S, G)
    l_sp = l_sp.reshape(B, Kv, S, G)
    o_sp = o_sp.reshape(B, Kv, S, G, dh)

    # ---- dense side: [old ring ‖ chunk] -------------------------------------
    kt = k_hat.transpose(0, 2, 1, 3)                         # [B,Kv,S,dh]
    vt = v_new.transpose(0, 2, 1, 3)
    bk = jnp.concatenate([cache["buf_k"], kt.astype(cache["buf_k"].dtype)],
                         axis=2)                             # [B,Kv,b+S,dh]
    bv = jnp.concatenate([cache["buf_v"], vt.astype(cache["buf_v"].dtype)],
                         axis=2)
    qpos = start[:, None] + jnp.arange(S)[None]              # [B, S]
    kpos = jnp.concatenate([cache["buf_pos"], qpos], axis=1)
    in_seq = jnp.concatenate(                                # [B, b+S]
        [cache["buf_pos"] < start[:, None], jnp.ones((B, S), bool)], axis=1)
    valid = ((kpos[:, None, :] >= 0)
             & (kpos[:, None, :] <= qpos[:, :, None])
             & in_seq[:, None, :])                           # [B, S, b+S]
    s_b = _dot_f32("bjsgd,bjtd->bjsgt", qf.astype(bk.dtype), bk) * scale
    s_b = jnp.where(valid[:, None, :, None, :], s_b, -jnp.inf)
    m_b = s_b.max(-1)
    m_b = jnp.where(jnp.isfinite(m_b), m_b, 0.0)
    p_b = jnp.where(valid[:, None, :, None, :],
                    jnp.exp(s_b - m_b[..., None]), 0.0)
    l_b = p_b.sum(-1)
    o_b = _dot_f32("bjsgt,bjtd->bjsgd", p_b.astype(bv.dtype), bv)

    # ---- exact merge --------------------------------------------------------
    m = jnp.maximum(m_sp, m_b)
    c_sp = jnp.exp(m_sp - m)
    c_b = jnp.exp(m_b - m)
    denom = jnp.maximum(l_sp * c_sp + l_b * c_b, 1e-30)
    o = (o_sp * c_sp[..., None] + o_b * c_b[..., None]) / denom[..., None]
    return o.transpose(0, 2, 1, 3, 4).reshape(B, S, Kv * G, dh) \
            .astype(q_hat.dtype)


# ---------------------------------------------------------------------------
# Paged cache (repro.core.paged_cache): gather-via-page-table reads
# ---------------------------------------------------------------------------

def paged_logical_view(cache: Params, page_tab: jnp.ndarray) -> Params:
    """Assemble each sequence's logical sparse cache from the shared page
    pool by page-table gather: ``view[b, :, t] = pool[page_tab[b, t // ps],
    :, t % ps]``.  This is a page-granule gather of the PACKED payload —
    vectors stay (values, int8 indices) pairs end to end, so the
    decompression-free property is untouched; the gathered view feeds the
    exact same sparse gather/scatter attention as the slab layout.

    Unmapped logical pages gather the trash page (physical page 0); the
    per-sequence ``sp_len`` mask inside ``_sparse_stats`` hides them.

    ``page_tab`` may be a leading PREFIX of the full table (the engine
    ships a power-of-two bucket of >= the most pages any live sequence has
    mapped), so the gathered view — the step's transient memory — scales
    with live pages, not max_seq.
    """
    B, P = page_tab.shape

    def side_view(side: Params) -> Params:
        ps = side["vals"].shape[2]

        def g(x):
            v = x[page_tab]                        # [B, P, Kv, ps, ...]
            v = jnp.moveaxis(v, 2, 1)              # [B, Kv, P, ps, ...]
            return v.reshape((B, v.shape[1], P * ps) + v.shape[4:])

        return {name: g(x) for name, x in side.items()}

    return {"k": side_view(cache["pool"]["k"]),
            "v": side_view(cache["pool"]["v"]),
            "buf_k": cache["buf_k"], "buf_v": cache["buf_v"],
            "buf_pos": cache["buf_pos"]}


def swan_decode_attention_paged(q_hat: jnp.ndarray, cache: Params, swan, cfg,
                                pos, page_tab: jnp.ndarray, mesh=None,
                                seq_axis: Optional[str] = None) -> jnp.ndarray:
    """SWAN decode attention over the paged cache: page-table gather, then
    the identical joint softmax over [winnowed sparse ‖ dense buffer].
    Every position < sp_len lives in a mapped page of the shipped table
    prefix, and positions beyond the view were -inf-masked anyway — so the
    paged engine is token-identical to the slab engine."""
    return swan_decode_attention(q_hat, paged_logical_view(cache, page_tab),
                                 swan, cfg, pos, mesh=mesh, seq_axis=seq_axis)


# ---------------------------------------------------------------------------
# Reference (oracle) path: full decompression + dense softmax.  Used by tests
# and by the Pallas ref.py — NEVER by serving.
# ---------------------------------------------------------------------------

def swan_decode_attention_reference(q_hat: jnp.ndarray, cache: Params, swan,
                                    cfg, pos) -> jnp.ndarray:
    B, Kv, G, dh = q_hat.shape
    S = cache["k"]["vals"].shape[2]
    pos = per_seq_pos(pos, B)

    def side_dense(side):
        vals = side["vals"]
        if "scale" in side:
            vals = dequantize_int8(vals, side["scale"], jnp.float32)
        return unpack_dense(vals.astype(jnp.float32), side.get("idx"), dh)

    kd, vd = side_dense(cache["k"]), side_dense(cache["v"])
    qf = q_hat.astype(jnp.float32)
    s_sp = jnp.einsum("bjgd,bjtd->bjgt", qf, kd) / math.sqrt(dh)
    sp_valid = jnp.arange(S)[None, :] < sparse_len(swan, pos)[:, None]
    s_sp = jnp.where(sp_valid[:, None, None], s_sp, -jnp.inf)

    bk = cache["buf_k"].astype(jnp.float32)
    bv = cache["buf_v"].astype(jnp.float32)
    s_b = jnp.einsum("bjgd,bjtd->bjgt", qf, bk) / math.sqrt(dh)
    b_valid = (cache["buf_pos"] >= 0) & (cache["buf_pos"] <= pos[:, None])
    s_b = jnp.where(b_valid[:, None, None], s_b, -jnp.inf)

    s = jnp.concatenate([s_sp, s_b], axis=-1)
    w = jax.nn.softmax(s, axis=-1)
    v_all = jnp.concatenate([vd, bv], axis=2)
    o = jnp.einsum("bjgt,bjtd->bjgd", w, v_all)
    return o.astype(q_hat.dtype)
