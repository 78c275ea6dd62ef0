"""Kernel microbenchmarks + per-kernel roofline table, on a TPU only.

Times the compiled kernels and gives each fused kernel a ROOFLINE row:
the ideal HBM byte / MXU flop model from ``repro.analysis.roofline`` and
the peaks of the device's ``device_kind`` give a memory- (or compute-)
bound floor time, and ``achieved_fraction`` = floor / measured is gated
against ``MIN_FRACTION``.  Rows cover the decode kernel
per (k, layout) — the paged layout per page bucket — and the bulk-chunk
prefill kernel.  With no TPU the benchmark refuses to run: a timing of
the Pallas interpreter says nothing about the chip.

CLI: ``python -m benchmarks.bench_kernels [--smoke]`` — smoke shrinks
shapes/iters (``BENCH_kernels.json`` lands in ``$REPRO_BENCH_OUT`` with
the roofline table under ``extra.roofline``).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.analysis import roofline as rl
from repro.configs import SwanConfig, get_smoke_config
from repro.core import hybrid_cache as hc
from repro.core import swan_attention as swa
from repro.core.analytical import sparse_vector_bytes
from repro.kernels.flash_prefill.ops import flash_attention, swan_chunk_stats
from repro.kernels.swan_decode.ops import (swan_decode_attention_kernel,
                                           swan_decode_attention_kernel_paged)
from repro.kernels.swan_prune.ops import swan_prune
from repro.core.projections import random_orthogonal
from benchmarks.common import bench_record, emit, gate, timeit_call

# minimum achieved fraction of the roofline: the fused kernels are
# memory-bound streams and must hit a substantial fraction of HBM peak
MIN_FRACTION = 0.4


def _device_kind() -> str:
    """The TPU's device kind; refuses another platform, and (through
    ``roofline.peaks``) a device with no published peaks."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"bench_kernels times compiled kernels and needs "
                           f"a TPU; found platform {dev.platform!r}")
    rl.peaks(dev.device_kind)
    return dev.device_kind


def _emit_roofline(rec, name, us, hbm_bytes, **tags) -> float:
    row = rl.roofline_row(name, us, hbm_bytes,
                          device_kind=rec.extra["device_kind"], **tags)
    rec.extra.setdefault("roofline", []).append(row)
    emit(f"roofline_{row['name']}", row["us_per_call"],
         f"bytes={row['hbm_bytes']}_floor_us={row['floor_us']:.2f}"
         f"_frac={row['achieved_fraction']:.2e}_bound={row['bound']}")
    return row["achieved_fraction"]


def _decode_rooflines(rec, cfg, smoke: bool):
    """Decode kernel rows: slab per k, paged per (k, page bucket)."""
    B, bt = 2, 16
    Kv, G, dh = cfg.n_kv_heads, cfg.q_group, cfg.d_head
    S = 128 if smoke else 256
    ps = 32
    ks = (8,) if smoke else (4, 8)
    buckets = (2, 4) if smoke else (4, 8)
    iters, warmup = (2, 1) if smoke else (3, 1)
    key = jax.random.PRNGKey(0)
    fracs = []
    for k in ks:
        swan = SwanConfig(k_max=k, buffer=bt, mode="topk")
        kh = jax.random.normal(key, (B, S - 8, Kv, dh))
        vh = jax.random.normal(jax.random.fold_in(key, 1), (B, S - 8, Kv, dh))
        cache = hc.init_swan_cache(cfg, swan, B, S)
        cache = hc.swan_cache_insert_prefill(cache, swan, cfg, kh, vh)
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, Kv, G, dh))
        pos = S - 9
        us = timeit_call(lambda: swan_decode_attention_kernel(
            q, cache, swan, cfg, pos, block_s=64), iters=iters, warmup=warmup)
        nb = rl.swan_decode_kernel_bytes(B=B, Kv=Kv, G=G, dh=dh, S=S,
                                         k_max=k, buffer=bt, quantized=False)
        fracs.append(_emit_roofline(
            rec, f"swan_decode_slab_k{k}", us, nb, kernel="swan_decode",
            layout="slab", k=k))
        for pb in buckets:
            n_pages = B * pb + 1
            pool_side = {
                "vals": jax.random.normal(jax.random.fold_in(key, 3),
                                          (n_pages, Kv, ps, k)),
                "idx": jax.random.randint(jax.random.fold_in(key, 4),
                                          (n_pages, Kv, ps, k), 0, dh,
                                          jnp.int8),
            }
            pcache = {
                "pool": {"k": pool_side, "v": dict(pool_side)},
                "buf_k": jax.random.normal(jax.random.fold_in(key, 5),
                                           (B, Kv, bt, dh)),
                "buf_v": jax.random.normal(jax.random.fold_in(key, 6),
                                           (B, Kv, bt, dh)),
                "buf_pos": (pb * ps
                            + jnp.arange(bt, dtype=jnp.int32)[None, :]
                            ).repeat(B, 0),
            }
            tab = (1 + jnp.arange(B * pb, dtype=jnp.int32)).reshape(B, pb)
            ppos = jnp.full((B,), pb * ps + bt - 1, jnp.int32)
            us = timeit_call(lambda: swan_decode_attention_kernel_paged(
                q, pcache, swan, cfg, ppos, tab), iters=iters, warmup=warmup)
            nb = rl.swan_decode_kernel_bytes(B=B, Kv=Kv, G=G, dh=dh,
                                             S=pb * ps, k_max=k, buffer=bt,
                                             quantized=False)
            fracs.append(_emit_roofline(
                rec, f"swan_decode_paged_k{k}_pg{pb}", us, nb,
                kernel="swan_decode_paged", layout="paged", k=k,
                page_bucket=pb, page_size=ps))
    return fracs


def _chunk_roofline(rec, cfg, smoke: bool):
    """Bulk-chunk prefill stats kernel row (the serve chunk dispatch)."""
    B, Q, k = 2, 8, 8
    Kv, dh = cfg.n_kv_heads, cfg.d_head
    S = 64 if smoke else 128
    iters, warmup = (2, 1) if smoke else (3, 1)
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, Kv, Q, dh))
    kv = jax.random.normal(jax.random.fold_in(key, 1), (B, Kv, S, k))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (B, Kv, S, k))
    ki = jax.random.randint(jax.random.fold_in(key, 3), (B, Kv, S, k),
                            0, dh, jnp.int8)
    sp = jnp.full((B,), S, jnp.int32)
    us = timeit_call(lambda: swan_chunk_stats(q, kv, ki, vv, ki, sp,
                                              block_s=32),
                     iters=iters, warmup=warmup)
    nb = rl.swan_chunk_kernel_bytes(B=B, Kv=Kv, Q=Q, dh=dh, S=S, k_max=k,
                                    quantized=False)
    return [_emit_roofline(
        rec, f"swan_chunk_stats_S{S}_k{k}", us, nb, kernel="swan_chunk_stats",
        layout="slab", k=k)]


def _flash_roofline(rec, smoke: bool):
    Sq = 128 if smoke else 256
    iters, warmup = (2, 1) if smoke else (3, 1)
    key = jax.random.PRNGKey(9)
    qf = jax.random.normal(key, (1, Sq, 4, 32), jnp.float32)
    kf = jax.random.normal(key, (1, Sq, 2, 32), jnp.float32)
    us = timeit_call(lambda: flash_attention(qf, kf, kf, block_q=64,
                                             block_k=64),
                     iters=iters, warmup=warmup)
    nb = rl.flash_kernel_bytes(B=1, H=4, Sq=Sq, Sk=Sq, dh=32)
    fl = rl.flash_kernel_flops(B=1, H=4, Sq=Sq, Sk=Sq, dh=32)
    return [_emit_roofline(
        rec, f"flash_prefill_Sq{Sq}", us, nb, flops=fl, kernel="flash_prefill",
        layout="dense")]


def _legacy_paths(cfg, smoke: bool) -> None:
    """The original XLA-vs-interpret comparison rows (kept: they track the
    pure-JAX reference paths the kernels replace)."""
    B, S, b, k = 2, 128 if smoke else 256, 16, 8
    swan = SwanConfig(k_max=k, buffer=b, mode="topk")
    key = jax.random.PRNGKey(0)
    kh = jax.random.normal(key, (B, S - 56, cfg.n_kv_heads, cfg.d_head))
    vh = jax.random.normal(jax.random.fold_in(key, 1),
                           (B, S - 56, cfg.n_kv_heads, cfg.d_head))
    cache = hc.init_swan_cache(cfg, swan, B, S)
    cache = hc.swan_cache_insert_prefill(cache, swan, cfg, kh, vh)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, cfg.n_kv_heads, cfg.q_group, cfg.d_head))
    pos = S - 57
    core = jax.jit(lambda q, c: swa.swan_decode_attention(q, c, swan, cfg,
                                                          pos))
    us = timeit_call(core, q, cache)
    sparse_b = 2 * B * cfg.n_kv_heads * S * sparse_vector_bytes(k)
    dense_b = 2 * B * cfg.n_kv_heads * S * cfg.d_head * 2
    emit("swan_decode_xla_ref", us,
         f"S={S}_k={k}_tpu_bytes={sparse_b}_vs_dense={dense_b}")

    from repro.models.attention import blocked_attention
    Sq = 128 if smoke else 256
    qf = jax.random.normal(key, (1, Sq, 4, 32), jnp.float32)
    kf = jax.random.normal(key, (1, Sq, 2, 32), jnp.float32)
    blk = jax.jit(lambda q, k_, v_: blocked_attention(q, k_, v_, causal=True,
                                                      block=64))
    us = timeit_call(blk, qf, kf, kf)
    flops = 4 * Sq * Sq * 32 * 4
    emit("flash_prefill_xla_blocked", us, f"Sq=Sk={Sq}_flops={flops}")

    x = jax.random.normal(key, (2, 2, 128, 32), jnp.float32)
    P = random_orthogonal(jax.random.fold_in(key, 5), (2,), 32)
    us = timeit_call(lambda: swan_prune(x, P, 8, tile=64), iters=3, warmup=1)
    emit("swan_prune_pallas", us, "T=128_dh=32_k=8")

    from repro.core.winnow import topk_pack, rotate_k
    prune_ref = jax.jit(
        lambda x, P: topk_pack(rotate_k(x.transpose(0, 2, 1, 3),
                                        P).transpose(0, 2, 1, 3), 8))
    us = timeit_call(prune_ref, x, P)
    emit("swan_prune_xla_ref", us, "T=128_dh=32_k=8")


def _run(rec, smoke: bool) -> None:
    cfg = get_smoke_config("llama3-8b").replace(dtype="float32")
    fracs = []
    fracs += _decode_rooflines(rec, cfg, smoke)
    fracs += _chunk_roofline(rec, cfg, smoke)
    fracs += _flash_roofline(rec, smoke)
    worst = min(fracs)
    gate("kernels_roofline_fraction", worst > MIN_FRACTION,
         f"{rec.extra['device_kind']}: worst achieved fraction {worst:.3e} "
         f"must exceed {MIN_FRACTION} over {len(fracs)} kernel rows")
    _legacy_paths(cfg, smoke)


def run(smoke: bool = False) -> None:
    kind = _device_kind()
    with bench_record("kernels") as rec:
        rec.extra["smoke"] = smoke
        rec.extra["device_kind"] = kind
        _run(rec, smoke)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few iters for CI")
    run(smoke=ap.parse_args().smoke)
