"""Decoder-only transformer LM (dense / MoE / VLM families).

Pure-functional: parameters are nested dicts; homogeneous layer stacks are
scanned (stacked [L, ...] leaves, MaxText-style) so 126-layer configs lower
to compact HLO.  Supports:

  * training forward (+ MoE aux losses) with remat,
  * VLM prefix embeddings (internvl2: stub patch embeddings),
  * SWAN calibration capture (``collect_qkv``) and weight absorption,
  * serving: prefill + decode with dense or SWAN hybrid caches.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import absorb as absorb_mod
from repro.core import hybrid_cache as hc
from repro.core import paged_cache as pc
from repro.core import swan_attention as swa
from repro.core.winnow import rotate_k, rotate_q
from repro.kernels.dispatch import pallas_decode_supported
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.common import (apply_norm, embed_init, init_norm,
                                 split_keys)
from repro.sharding.api import shard

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _remat(body, cfg):
    """Remat policy: 'full' recomputes everything in bwd (min memory, but
    FSDP parameter all-gathers re-run in the bwd pass); 'dots' saves matmul
    operands (incl. gathered weights) — trades temp memory for collective
    traffic (§Perf cell B iteration)."""
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(body)


def init_layer_params(key, cfg, layer_idx: int = 0) -> Params:
    ks = split_keys(key, 4)
    p: Params = {
        "ln1": init_norm(ks[0], cfg, cfg.d_model),
        "attn": attn.init_attn_params(ks[1], cfg),
        "ln2": init_norm(ks[2], cfg, cfg.d_model),
    }
    if cfg.ffn_kind(layer_idx) == "moe":
        p["experts"] = moe_mod.init_moe_params(ks[3], cfg)
    else:
        p["mlp"] = mlp_mod.init_mlp_params(ks[3], cfg, cfg.d_ff)
    return p


def init_lm_params(key, cfg) -> Params:
    """All layers homogeneous here (dense / all-MoE); jamba overrides."""
    ks = split_keys(key, cfg.n_layers + 3)
    layers = [init_layer_params(ks[i], cfg, i) for i in range(cfg.n_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    p: Params = {
        "embed": embed_init(ks[-3], cfg.vocab_size, cfg.d_model,
                            jnp.dtype(cfg.param_dtype)),
        "layers": stacked,
        "ln_f": init_norm(ks[-2], cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = embed_init(ks[-1], cfg.vocab_size, cfg.d_model,
                               jnp.dtype(cfg.param_dtype)).T
    if cfg.pos == "learned":
        p["pos_embed"] = embed_init(ks[-1], cfg.max_position_learned(),
                                    cfg.d_model, jnp.dtype(cfg.param_dtype))
    return p


def abstract_params(cfg):
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return jax.eval_shape(lambda: init_lm_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Layer forward
# ---------------------------------------------------------------------------

def layer_forward(lp: Params, cfg, x: jnp.ndarray, positions: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-norm block.  Returns (x, moe_aux_scalar)."""
    h = apply_norm(lp["ln1"], cfg, x)
    h = attn.attn_forward(lp["attn"], cfg, h, positions)
    x = shard(x + h, "residual")
    h = apply_norm(lp["ln2"], cfg, x)
    if "experts" in lp:
        h, aux = moe_mod.moe_forward(lp["experts"], cfg, h)
        aux_sum = aux["moe_load_balance"] + aux["moe_router_z"]
    else:
        h = mlp_mod.mlp_forward(lp["mlp"], cfg, h)
        aux_sum = jnp.zeros((), jnp.float32)
    x = shard(x + h, "residual")
    return x, aux_sum


def _embed_inputs(p: Params, cfg, tokens: jnp.ndarray,
                  prefix_embeds: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos == "learned":
        x = x + jnp.take(p["pos_embed"], jnp.minimum(
            positions, p["pos_embed"].shape[0] - 1), axis=0).astype(x.dtype)
    return shard(x, "residual"), positions


def lm_forward(p: Params, cfg, tokens: jnp.ndarray,
               prefix_embeds: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] (+ optional prefix embeds [B, P, d]) -> (logits, aux)."""
    x, positions = _embed_inputs(p, cfg, tokens, prefix_embeds)

    def body(carry, lp):
        x, aux = carry
        x, a = layer_forward(lp, cfg, x, positions)
        return (x, aux + a), None

    body_fn = _remat(body, cfg)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                   p["layers"])
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], p["layers"])
            (x, aux), _ = body_fn((x, aux), lp)

    x = apply_norm(p["ln_f"], cfg, x)
    head = p["embed"].T if cfg.tie_embeddings else p["head"]
    logits = shard(x @ head.astype(x.dtype), "logits")
    return logits, aux


def lm_loss(p: Params, cfg, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (prefix positions excluded for VLM)."""
    tokens = batch["tokens"]
    logits, aux = lm_forward(p, cfg, tokens, batch.get("prefix_embeds"))
    n_prefix = 0 if batch.get("prefix_embeds") is None else batch["prefix_embeds"].shape[1]
    logits = logits[:, n_prefix:]
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(gold) if mask is None else mask[:, 1:].astype(jnp.float32)
    nll = ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    zloss = 1e-4 * ((logz ** 2) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = nll + zloss + aux
    return loss, {"nll": nll, "aux": aux, "z": zloss}


# ---------------------------------------------------------------------------
# SWAN calibration + absorption
# ---------------------------------------------------------------------------

def collect_qkv(p: Params, cfg, tokens: jnp.ndarray,
                prefix_embeds: Optional[jnp.ndarray] = None):
    """Run the model, capturing per-layer post-RoPE q/k and v (paper §4.1.1).

    Returns (q [L,B,S,H,dh], k [L,B,S,Kv,dh], v [L,B,S,Kv,dh], wo [L,H·dh,d]).
    """
    x, positions = _embed_inputs(p, cfg, tokens, prefix_embeds)

    def body(carry, lp):
        x, _ = carry
        h = apply_norm(lp["ln1"], cfg, x)
        q, k, v = attn.project_qkv(lp["attn"], cfg, h, positions)
        x, _ = layer_forward(lp, cfg, x, positions)
        return (x, jnp.zeros((), jnp.float32)), (q, k, v)

    (_, _), (q, k, v) = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                     p["layers"])
    return q, k, v, p["layers"]["attn"]["wo"]


def absorb_swan(p: Params, cfg, projections: Params) -> Params:
    """Fold P_VO into the stacked attention weights (lossless, Lemma A.2)."""
    out = dict(p)
    layers = dict(p["layers"])
    layers["attn"] = absorb_mod.absorb_vo(
        p["layers"]["attn"], projections["p_vo"],
        cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    out["layers"] = layers
    return out


# ---------------------------------------------------------------------------
# Serving: caches, prefill, decode
# ---------------------------------------------------------------------------

def init_caches(cfg, swan, batch: int, max_seq: int) -> Params:
    """Stacked [L, ...] caches; ``swan`` None -> dense baseline cache."""
    if swan is None or not swan.enabled:
        one = attn.init_dense_cache(cfg, batch, max_seq)
    else:
        one = hc.init_swan_cache(cfg, swan, batch, max_seq)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers, *x.shape)), one)


def init_paged_caches(cfg, swan, batch: int, max_seq: int, n_pages: int,
                      page_size: int) -> Params:
    """Paged serve state (repro.core.paged_cache): per-layer sparse sides
    become a shared page pool [L, n_pages, Kv, page_size, k]; the dense
    ring buffers stay per-slot.  The page table rides along as a separate
    traced operand (host-owned mapping, see repro.runtime.page_pool)."""
    if swan is None or not swan.enabled:
        raise ValueError("paged caches require SWAN (the sparse sides are "
                         "what gets paged); use init_caches for dense")
    if max_seq % page_size:
        raise ValueError(f"max_seq={max_seq} not divisible by "
                         f"page_size={page_size}")
    Kv, dh, b = cfg.n_kv_heads, cfg.d_head, swan.buffer
    one = {
        "pool": pc.init_paged_pool(cfg, swan, n_pages, page_size),
        "buf_k": jnp.zeros((batch, Kv, b, dh), jnp.dtype(cfg.dtype)),
        "buf_v": jnp.zeros((batch, Kv, b, dh), jnp.dtype(cfg.dtype)),
        "buf_pos": jnp.full((batch, b), -1, jnp.int32),
    }
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers, *x.shape)), one)


def _swan_seq_ctx():
    """(mesh, seq_axis) for split-S swan decode, from the installed rules."""
    from repro.sharding.api import current_rules
    rules = current_rules()
    if rules is None:
        return None, None
    spec = rules.kinds.get("swan_sparse")
    if spec is None or len(spec) < 3 or spec[2] is None:
        return None, None
    return rules.mesh, spec[2]


def _swan_layer_decode(lp: Params, p_qk_l: jnp.ndarray, cache_l: Params,
                       cfg, swan, x: jnp.ndarray, pos,
                       k_act=None, page_tab=None, use_pallas: bool = False,
                       pallas_interpret: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, Params]:
    """``use_pallas`` (STATIC bool) dispatches the attention read to the
    fused Pallas kernels (repro.kernels.swan_decode) instead of the
    pure-JAX gather/scatter path; cache INSERTION stays pure JAX either
    way (a tiny lane-local scatter XLA handles fine — only the bulk read
    is bandwidth-bound).  The kernel is lane-local, so it composes with
    the engine's batch-sharded shard_map; split-S sequence sharding keeps
    the pure-JAX flash-decoding path (the kernel has no cross-shard stat
    merge), as do the truncate mode and bt=0 ablations
    (``pallas_decode_supported``)."""
    B = x.shape[0]
    Kv, G, dh = cfg.n_kv_heads, cfg.q_group, cfg.d_head
    pos = hc.per_seq_pos(pos, B)                                 # [B]
    positions = pos[:, None]                                     # [B, 1]
    q, k, v = attn.project_qkv(lp["attn"], cfg, x, positions)   # v̂ already rotated (absorbed)
    q_hat = rotate_q(q, p_qk_l, Kv)[:, 0]                        # [B,Kv,G,dh]
    k_hat = rotate_k(k, p_qk_l)                                  # [B,1→S dim,Kv,dh]
    mesh, seq_axis = _swan_seq_ctx()
    kern = use_pallas and mesh is None and pallas_decode_supported(swan)
    if page_tab is None:
        with jax.named_scope("winnow"):
            cache_l = hc.swan_cache_insert_decode(cache_l, swan, cfg, k_hat,
                                                  v, pos, k_act=k_act)
        if kern and cache_l["k"]["vals"].shape[2] > 0:
            from repro.kernels.swan_decode import ops as sdk
            o = sdk.swan_decode_from_cache(q_hat, cache_l, swan, pos,
                                           interpret=pallas_interpret)
        else:
            o = swa.swan_decode_attention(q_hat, cache_l, swan, cfg, pos,
                                          mesh=mesh, seq_axis=seq_axis)
    else:
        with jax.named_scope("winnow"):
            cache_l = pc.paged_insert_decode(cache_l, swan, cfg, k_hat, v,
                                             pos, page_tab, k_act=k_act)
        if kern and page_tab.shape[1] > 0:
            from repro.kernels.swan_decode import ops as sdk
            o = sdk.swan_decode_paged_from_cache(q_hat, cache_l, swan, pos,
                                                 page_tab,
                                                 interpret=pallas_interpret)
        else:
            o = swa.swan_decode_attention_paged(q_hat, cache_l, swan, cfg,
                                                pos, page_tab, mesh=mesh,
                                                seq_axis=seq_axis)
    o = o.reshape(B, 1, Kv * G, dh)
    return attn.output_proj(lp["attn"], o), cache_l


def _swan_layer_prefill(lp: Params, p_qk_l, cache_l, cfg, swan,
                        x: jnp.ndarray, positions,
                        k_act=None, true_len=None) -> Tuple[jnp.ndarray, Params]:
    """Prefill: dense (lossless, Lemma A.1) attention on rotated q̂/k̂/v̂;
    hybrid cache populated for subsequent decode."""
    B, S, _ = x.shape
    Kv, G, dh = cfg.n_kv_heads, cfg.q_group, cfg.d_head
    q, k, v = attn.project_qkv(lp["attn"], cfg, x, positions)
    q_hat = rotate_q(q, p_qk_l, Kv).reshape(B, S, Kv * G, dh)
    k_hat = rotate_k(k, p_qk_l)
    cache_l = hc.swan_cache_insert_prefill(cache_l, swan, cfg, k_hat, v,
                                           k_act=k_act, true_len=true_len)
    if S > attn.DENSE_ATTN_MAX_SEQ:
        o = attn.blocked_attention(q_hat, k_hat, v, causal=True)
    else:
        o = attn.dense_attention(q_hat, k_hat, v, mask=None, causal=True)
    return attn.output_proj(lp["attn"], o), cache_l


def _swan_scan_xs(cfg, swan, projections, use_swan):
    """Per-layer scan inputs: projections + (adaptive) per-layer k_active.
    projections may carry 'k_layer' [L] from repro.core.adaptive."""
    if not use_swan:
        z = jnp.zeros((cfg.n_layers, 1), jnp.float32)
        return z, jnp.zeros((cfg.n_layers,), jnp.int32)
    pq = projections["p_qk"]
    k_layer = projections.get("k_layer")
    if k_layer is None:
        k_layer = jnp.full((cfg.n_layers,), swan.kk, jnp.int32)
    return pq, jnp.asarray(k_layer, jnp.int32)


def _layer_ffn(lp: Params, cfg, x: jnp.ndarray) -> jnp.ndarray:
    h = apply_norm(lp["ln2"], cfg, x)
    if "experts" in lp:
        # serving: no-drop dispatch (prefill ≡ incremental decode)
        h, _ = moe_mod.moe_forward(lp["experts"], cfg, h, no_drop=True)
    else:
        h = mlp_mod.mlp_forward(lp["mlp"], cfg, h)
    return x + h


def lm_prefill(p: Params, cfg, tokens: jnp.ndarray, caches: Params,
               swan=None, projections: Optional[Params] = None,
               prefix_embeds: Optional[jnp.ndarray] = None,
               k_active=None, true_len=None) -> Tuple[jnp.ndarray, Params]:
    """Process the prompt; fill caches.  Returns (last-token logits, caches).

    ``k_active``: optional traced scalar overriding the SWAN runtime
    retention for this whole prompt (per-request k — the serve engine
    prefills one request at a time, so a scalar suffices here).

    ``true_len``: optional traced scalar — the real prompt length when
    ``tokens`` is padded to a compile bucket (prompt-length bucketing).
    Logits are then taken at position true_len - 1, and the hybrid-cache
    ring is anchored at true_len; padding junk beyond it only ever lands
    in masked/invalid cache regions (causal masking keeps it out of the
    prefill attention)."""
    x, positions = _embed_inputs(p, cfg, tokens, prefix_embeds)
    use_swan = swan is not None and swan.enabled

    def body(x, xs):
        lp, cache_l, p_qk_l, k_l = xs
        h = apply_norm(lp["ln1"], cfg, x)
        if use_swan:
            h, cache_l = _swan_layer_prefill(lp, p_qk_l, cache_l, cfg, swan,
                                             h, positions, k_act=k_l,
                                             true_len=true_len)
        else:
            q, k, v = attn.project_qkv(lp["attn"], cfg, h, positions)
            cache_l = attn.dense_cache_insert(cache_l, k, v, 0)
            if x.shape[1] > attn.DENSE_ATTN_MAX_SEQ:
                o = attn.blocked_attention(q, k, v, causal=True)
            else:
                o = attn.dense_attention(q, k, v, mask=None, causal=True)
            h = attn.output_proj(lp["attn"], o)
        x = shard(x + h, "residual")
        x = shard(_layer_ffn(lp, cfg, x), "residual")
        return x, cache_l

    pq, k_arr = _swan_scan_xs(cfg, swan, projections, use_swan)
    if use_swan and k_active is not None:
        k_arr = jnp.minimum(k_arr, jnp.asarray(k_active, jnp.int32))
    x, caches = jax.lax.scan(body, x, (p["layers"], caches, pq, k_arr))
    if true_len is None:
        x = x[:, -1:]
    else:   # bucketed prompt: last REAL token, not the padding tail
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(true_len, jnp.int32) - 1, 1, axis=1)
    x = apply_norm(p["ln_f"], cfg, x)
    head = p["embed"].T if cfg.tie_embeddings else p["head"]
    return x @ head.astype(x.dtype), caches


def _swan_layer_prefill_chunk(lp: Params, p_qk_l, cache_l: Params, cfg, swan,
                              x: jnp.ndarray, slot, start, true_len,
                              positions, k_act=None, page_tab=None,
                              prefix_len: Optional[int] = None,
                              use_pallas: bool = False,
                              pallas_interpret: Optional[bool] = None
                              ) -> Tuple[jnp.ndarray, Params]:
    """One layer of BATCHED chunked prefill against the batched serve
    state: gather the P selected slots' lanes (traced ``slot [P]``), attend
    each lane to its [winnowed sparse prefix ‖ ring ‖ chunk], commit each
    chunk at its own offset, and scatter the lanes back.  Only the selected
    lanes (and, paged, their own pages) are touched — decode steps for
    other slots interleave freely between chunks.  Dead lanes (``slot >=
    n_slots``, padding of a partially filled prefill batch) gather clamped
    garbage that is computed but never written: slab/ring scatters drop
    out-of-range lanes, paged writes are redirected to the trash page."""
    Kv = cfg.n_kv_heads
    n_slots = cache_l["buf_pos"].shape[0]
    q, k, v = attn.project_qkv(lp["attn"], cfg, x, positions)
    q_hat = rotate_q(q, p_qk_l, Kv)                      # [P,S,Kv,G,dh]
    k_hat = rotate_k(k, p_qk_l)
    lane_ix = jnp.minimum(slot, n_slots - 1)             # clamped gather
    ring = {n: cache_l[n][lane_ix] for n in ("buf_k", "buf_v", "buf_pos")}
    out_l = dict(cache_l)
    kern = (use_pallas and pallas_decode_supported(swan)
            and _swan_seq_ctx()[0] is None)

    def bulk_q():
        # the bulk-stats kernel consumes the query-flattened layout that
        # swan_chunk_prefill_attention uses internally: [P, Kv, S·G, dh]
        P_, S_, Kv_, G_, dh_ = q_hat.shape
        qf = q_hat.astype(jnp.float32).transpose(0, 2, 1, 3, 4)
        return qf.reshape(P_, Kv_, S_ * G_, dh_)

    if page_tab is None:                                 # slab layout
        view = dict(ring)
        for n in ("k", "v"):
            # attend to a STATIC power-of-two prefix of the slab rows (the
            # caller buckets max(start)+S up): the bulk read's transient
            # then follows the prompts so far, not max_seq — one executable
            # per (P, chunk, prefix) bucket, O(log³) total
            pl = (min(prefix_len, cache_l[n]["vals"].shape[2])
                  if prefix_len is not None else cache_l[n]["vals"].shape[2])
            view[n] = jax.tree_util.tree_map(
                lambda a: jax.lax.slice_in_dim(a, 0, pl, axis=2)[lane_ix],
                cache_l[n])
        stats = None
        if kern and view["k"]["vals"].shape[2] > 0:
            from repro.kernels.flash_prefill import swan_chunk as sck
            sp_len = jnp.maximum(start - swan.buffer, 0)
            stats = sck.swan_chunk_stats_pallas(
                bulk_q(), view["k"]["vals"], view["k"]["idx"],
                view["v"]["vals"], view["v"]["idx"], sp_len,
                k_scale=view["k"].get("scale"),
                v_scale=view["v"].get("scale"),
                interpret=pallas_interpret)
        o = swa.swan_chunk_prefill_attention(q_hat, k_hat, v, view, swan,
                                             cfg, start, true_len,
                                             sparse_stats=stats)
        with jax.named_scope("winnow"):
            dest, packed_k, packed_v, upd = hc.chunk_evict_winnow(
                ring, swan, k_hat, v, start, true_len, k_act=k_act)
            ring_new = {**ring, **upd}
            out_l["k"] = hc.write_sparse_rows(cache_l["k"], packed_k, slot,
                                              dest)
            out_l["v"] = hc.write_sparse_rows(cache_l["v"], packed_v, slot,
                                              dest)
    else:                                                # paged layout
        page_rows = page_tab[lane_ix]                    # [P, Pg]
        lane = dict(ring)
        lane["pool"] = cache_l["pool"]
        if kern and page_rows.shape[1] > 0:
            # pool pages feed the kernel's VMEM tiles directly: no
            # paged_logical_view materialisation on the chunk path either
            from repro.kernels.flash_prefill import swan_chunk as sck
            pk, pv = cache_l["pool"]["k"], cache_l["pool"]["v"]
            sp_len = jnp.maximum(start - swan.buffer, 0)
            stats = sck.swan_chunk_stats_paged_pallas(
                bulk_q(), pk["vals"], pk["idx"], pv["vals"], pv["idx"],
                sp_len, page_rows,
                pool_k_scale=pk.get("scale"), pool_v_scale=pv.get("scale"),
                interpret=pallas_interpret)
            o = swa.swan_chunk_prefill_attention(q_hat, k_hat, v, ring,
                                                 swan, cfg, start, true_len,
                                                 sparse_stats=stats)
        else:
            view = swa.paged_logical_view(lane, page_rows)
            o = swa.swan_chunk_prefill_attention(q_hat, k_hat, v, view,
                                                 swan, cfg, start, true_len)
        with jax.named_scope("winnow"):
            lane = pc.paged_insert_prefill_chunk(lane, swan, cfg, k_hat, v,
                                                 start, true_len, page_rows,
                                                 k_act=k_act,
                                                 dead=slot >= n_slots)
        out_l["pool"] = lane["pool"]
        ring_new = {n: lane[n] for n in ("buf_k", "buf_v", "buf_pos")}
    for n in ("buf_k", "buf_v", "buf_pos"):
        out_l[n] = cache_l[n].at[slot].set(
            ring_new[n].astype(cache_l[n].dtype), mode="drop")
    return attn.output_proj(lp["attn"], o), out_l


def _dense_layer_prefill_chunk(lp: Params, cache_l: Params, cfg,
                               x: jnp.ndarray, slot, start, positions,
                               prefix_len: Optional[int] = None
                               ) -> Tuple[jnp.ndarray, Params]:
    """Batched chunked prefill for the dense-cache baseline: insert each
    lane's chunk K/V at [start_p, start_p+S) in its slot's lane, then
    causal attention of each chunk against its lane's first ``prefix_len``
    rows (a static bucket >= max(start) + S; rows past a lane's chunk are
    masked by the per-lane causal offset)."""
    n_slots = cache_l["k"].shape[0]
    q, k, v = attn.project_qkv(lp["attn"], cfg, x, positions)
    cache_l = attn.dense_cache_insert_rows(cache_l, k, v, slot, start)
    lane_ix = jnp.minimum(slot, n_slots - 1)
    pl = (min(prefix_len, cache_l["k"].shape[2])
          if prefix_len is not None else cache_l["k"].shape[2])
    view = jax.tree_util.tree_map(
        lambda a: jax.lax.slice_in_dim(a, 0, pl, axis=2)[lane_ix], cache_l)
    kc = view["k"].transpose(0, 2, 1, 3)                 # [P, pl, Kv, dh]
    vc = view["v"].transpose(0, 2, 1, 3)
    if kc.shape[1] > attn.DENSE_ATTN_MAX_SEQ:
        o = attn.blocked_attention(q, kc, vc, causal=True, q_offset=start)
    else:
        o = attn.dense_attention(q, kc, vc, mask=None, causal=True,
                                 q_offset=start)
    return attn.output_proj(lp["attn"], o), cache_l


def lm_prefill_chunk_batched(p: Params, cfg, tokens: jnp.ndarray,
                             caches: Params, slot, start, swan=None,
                             projections: Optional[Params] = None,
                             k_active=None, true_len=None, page_tab=None,
                             prefix_len: Optional[int] = None,
                             use_pallas: bool = False,
                             pallas_interpret: Optional[bool] = None
                             ) -> Tuple[jnp.ndarray, Params]:
    """Advance up to P slots' prefills by one chunk EACH against the
    engine's BATCHED serve state — ONE executable per step no matter how
    many prefills are in flight (batched concurrent chunked prefill).

    ``tokens [P, C]``: the packed chunks, one lane per in-flight prefill,
    padded to a power-of-two width C; ``slot`` / ``start`` / ``true_len``
    (and per-request ``k_active``) are traced int32 [P] — each lane's slot
    index in the batched state, the absolute position of its chunk's first
    token, and its number of real chunk tokens.  P is a power-of-two
    bucket: lanes past the selected prefills are DEAD (``slot = n_slots``,
    out of range) — they compute clamped garbage whose writes are dropped
    (slab/ring) or land on the trash page (paged).  One executable serves
    every (P, C) bucket pair, so admission bursts compile O(log n_slots ×
    log chunk) shapes, not one per combination of in-flight prefills.

    Each lane's chunk attends causally to [its already-cached tokens ‖
    chunk]: with SWAN, positions [0, start_p) are seen exactly as a decode
    step at the same position sees them (winnowed sparse prefix + dense
    ring) while in-chunk positions stay dense, and the hybrid cache is
    advanced so that after the chunk the ring holds [start + true_len - b,
    start + true_len) — indistinguishable at the boundary from a monolithic
    prefill of start + true_len tokens.  ``page_tab [n_slots, Pg]`` (a
    power-of-two page-table prefix; lanes gather their own rows by slot)
    routes sparse reads/writes through the shared page pool instead.

    ``prefix_len`` (STATIC python int >= max(start) + C,
    power-of-two-bucketed by the caller) bounds the attention read to each
    lane's first slab/dense rows, so the bulk-read transient follows the
    prompts so far instead of max_seq (the paged layout is already bounded
    by its shipped ``page_tab`` prefix).

    ``use_pallas`` / ``pallas_interpret`` (STATIC): run the sparse-prefix
    bulk read through the Pallas bulk-chunk kernel
    (repro.kernels.flash_prefill.swan_chunk) — packed vectors expand once
    in VMEM, and the paged variant gathers pool pages in-kernel instead of
    materialising ``paged_logical_view``.

    VLM prefix embeddings are not supported on the chunked path (the
    engine's monolithic admission handles those prompts).

    Batch-shardability (audited for the mesh-sharded serve engine, which
    runs this function inside ``shard_map`` over the data axis): every op
    here is lane-local — lanes only ever index the batched state through
    their own ``slot`` entry, all reductions run over sequence/head/vocab
    dims, and there are no cross-lane collectives.  The per-shard call is
    therefore bit-identical to a single-device call on the shard's local
    block, with ``slot`` given as SHARD-LOCAL lane indices: dead-lane
    parking stays correct per shard because the parking value and the
    clamped gather both derive from the LOCAL batch size
    (``cache_l["buf_pos"].shape[0]``), and paged trash redirection targets
    the shard's own local page 0.

    Returns (logits at each chunk's last real token [P, V], caches) —
    dead lanes' logits are garbage the caller discards.
    """
    P, S = tokens.shape
    start = hc.per_seq_pos(start, P)
    true_len = (jnp.full((P,), S, jnp.int32) if true_len is None
                else hc.per_seq_pos(true_len, P))
    use_swan = swan is not None and swan.enabled
    if page_tab is not None and not use_swan:
        raise ValueError("page_tab given but SWAN disabled — only the "
                         "sparse sides are paged")
    x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    positions = start[:, None] + jnp.arange(S)[None]     # [P, S]
    if cfg.pos == "learned":
        x = x + jnp.take(p["pos_embed"], jnp.minimum(
            positions, p["pos_embed"].shape[0] - 1), axis=0).astype(x.dtype)
    x = shard(x, "residual")
    k_req = None if k_active is None else hc.per_seq_pos(k_active, P)

    def body(x, xs):
        lp, cache_l, p_qk_l, k_l = xs
        with jax.named_scope("attn"):
            h = apply_norm(lp["ln1"], cfg, x)
            if use_swan:
                k_eff = k_l if k_req is None else jnp.minimum(k_l, k_req)
                h, cache_l = _swan_layer_prefill_chunk(
                    lp, p_qk_l, cache_l, cfg, swan, h, slot, start,
                    true_len, positions, k_act=k_eff, page_tab=page_tab,
                    prefix_len=prefix_len, use_pallas=use_pallas,
                    pallas_interpret=pallas_interpret)
            else:
                h, cache_l = _dense_layer_prefill_chunk(
                    lp, cache_l, cfg, h, slot, start, positions,
                    prefix_len=prefix_len)
        x = shard(x + h, "residual")
        with jax.named_scope("mlp"):
            x = shard(_layer_ffn(lp, cfg, x), "residual")
        return x, cache_l

    pq, k_arr = _swan_scan_xs(cfg, swan, projections, use_swan)
    x, caches = jax.lax.scan(body, x, (p["layers"], caches, pq, k_arr))
    x = jnp.take_along_axis(                             # last REAL token
        x, jnp.maximum(true_len - 1, 0)[:, None, None], axis=1)
    x = apply_norm(p["ln_f"], cfg, x)
    head = p["embed"].T if cfg.tie_embeddings else p["head"]
    return (x @ head.astype(x.dtype))[:, 0], caches


def lm_decode_step(p: Params, cfg, token: jnp.ndarray, pos, caches: Params,
                   swan=None, projections: Optional[Params] = None,
                   k_active=None, page_tab=None, use_pallas: bool = False,
                   pallas_interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, Params]:
    """token [B] -> (logits [B, V], updated caches).

    ``pos``: scalar int32 (lockstep batch) or per-sequence [B] (continuous
    batching).  ``k_active``: optional traced scalar or per-sequence [B]
    SWAN retention override — per-request runtime-tunable compression; a
    traced operand, so mixed-k batches share one compiled executable.

    ``page_tab``: optional int32 [B, max_pages] page table — ``caches`` is
    then the paged layout from ``init_paged_caches`` and sparse reads/writes
    go through the shared page pool (repro.core.paged_cache).

    ``use_pallas`` / ``pallas_interpret`` (STATIC): dispatch the per-layer
    attention read to the fused Pallas kernels — slab tiles or, paged, the
    in-kernel page-table gather (see docs/kernels.md for the policy; the
    pure-JAX path remains the reference and the fallback).

    Batch-shardability (audited for the mesh-sharded serve engine): the
    decode step is lane-local end to end — per-sequence ``pos``/``k_active``
    index nothing but their own lane, dead lanes (pos < 0) drop their
    writes locally, the paged gather goes through the lane's own table row
    into its shard's block of the pool, and no reduction crosses the batch
    axis.  Running it inside ``shard_map`` over the data axis is therefore
    bit-identical to the single-device step on each shard's local block
    (the optional split-S collectives in swan_attention only arise when
    sharding rules put the SEQUENCE dim on a mesh axis, which the serve
    engine does not)."""
    B = token.shape[0]
    pos = hc.per_seq_pos(pos, B)
    x = jnp.take(p["embed"], token[:, None], axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.pos == "learned":
        pe = jnp.take(p["pos_embed"],
                      jnp.minimum(pos, p["pos_embed"].shape[0] - 1), axis=0)
        x = x + pe[:, None].astype(x.dtype)
    use_swan = swan is not None and swan.enabled
    if page_tab is not None and not use_swan:
        raise ValueError("page_tab given but SWAN disabled — only the "
                         "sparse sides are paged")
    k_req = None if k_active is None else jnp.asarray(k_active, jnp.int32)

    def body(x, xs):
        lp, cache_l, p_qk_l, k_l = xs
        with jax.named_scope("attn"):
            h = apply_norm(lp["ln1"], cfg, x)
            if use_swan:
                k_eff = k_l if k_req is None else jnp.minimum(k_l, k_req)
                h, cache_l = _swan_layer_decode(
                    lp, p_qk_l, cache_l, cfg, swan, h, pos, k_act=k_eff,
                    page_tab=page_tab, use_pallas=use_pallas,
                    pallas_interpret=pallas_interpret)
            else:
                h, cache_l = attn.attn_decode_dense(lp["attn"], cfg, h, pos,
                                                    cache_l)
        x = x + h
        with jax.named_scope("mlp"):
            x = _layer_ffn(lp, cfg, x)
        return x, cache_l

    pq, k_arr = _swan_scan_xs(cfg, swan, projections, use_swan)
    x, caches = jax.lax.scan(body, x, (p["layers"], caches, pq, k_arr))
    x = apply_norm(p["ln_f"], cfg, x)
    head = p["embed"].T if cfg.tie_embeddings else p["head"]
    return (x @ head.astype(x.dtype))[:, 0], caches
