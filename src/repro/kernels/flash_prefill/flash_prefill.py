"""Pallas TPU kernel: causal flash attention (prefill), GQA-aware.

Grid (B, H, nQ, nK) with the K axis innermost; online-softmax stats live in
VMEM scratch across K steps.  Causality is exploited structurally: K blocks
strictly above the diagonal contribute nothing and are skipped via
``pl.when`` (their DMA still lands but the MXU work is saved; on real TPU
a dynamic grid bound would also skip the DMA).

BlockSpecs: q/o tiles [BQ, dh], kv tiles [BK, dh] with the KV head index
derived as h // G (GQA: query heads share KV tiles — the kernel reads each
KV tile G times but from the much smaller kv-head array).  dh=128 = lane
width; BQ/BK default 256 ≈ 512 KB/tile f32 — VMEM-safe with double
buffering.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
LANE_WIDTH = 128
SUBLANE_F32 = 8


def vmem_footprint(*, bq: int, bk: int, dh: int) -> int:
    """Per-grid-step VMEM working set in bytes (double-buffered q/k/v
    tiles + online-softmax scratch + output tile), matching the BlockSpecs
    in ``flash_attention_pallas``."""
    inputs = 2 * (bq * dh + 2 * bk * dh) * 4          # q + k/v, double-buf
    scratch = 2 * bq * 4 + bq * dh * 4                # m, l, acc
    out = bq * dh * 4
    return inputs + scratch + out


def precheck(*, B: int, H: int, Kv: int, Sq: int, Sk: int, dh: int,
             block_q: int = 256, block_k: int = 256,
             vmem_budget: int = VMEM_BYTES_PER_CORE) -> dict:
    """Static grid/VMEM validation for ``flash_attention_pallas`` —
    same contract as ``repro.kernels.swan_decode.precheck``."""
    errors, warnings = [], []
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if bq <= 0 or Sq % bq:
        errors.append(f"Sq={Sq} not divisible by query block bq={bq}")
    if bk <= 0 or Sk % bk:
        errors.append(f"Sk={Sk} not divisible by key block bk={bk}")
    if Kv <= 0 or H % Kv:
        errors.append(f"H={H} not divisible by Kv={Kv}: GQA head-group "
                      "index h // G would misalign KV tiles")
    vmem = vmem_footprint(bq=bq, bk=bk, dh=dh)
    if vmem > vmem_budget:
        errors.append(f"VMEM working set {vmem} B exceeds budget "
                      f"{vmem_budget} B (bq={bq}, bk={bk}, dh={dh})")
    if dh % LANE_WIDTH:
        warnings.append(f"dh={dh} not a multiple of lane width "
                        f"{LANE_WIDTH}: tiles pad to 128 lanes")
    if bq % SUBLANE_F32 or bk % SUBLANE_F32:
        warnings.append(f"bq={bq}/bk={bk} not multiples of f32 sublane "
                        f"{SUBLANE_F32}: tiles pad sublanes")
    return {"errors": errors, "warnings": warnings, "vmem_bytes": vmem}


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, bq: int, bk: int, dh: int, n_kblocks: int, causal: bool):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    scale = 1.0 / math.sqrt(dh)

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    run = (kb * bk <= qb * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                 # [BQ, dh]
        k = k_ref[0, 0].astype(jnp.float32)                 # [BK, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = k_pos <= q_pos
            s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(kb == n_kblocks - 1)
    def _write():
        o_ref[0, 0] = (acc_sc[...] /
                       jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 256, block_k: int = 256,
                           interpret=None):
    """q [B,Sq,H,dh]; k/v [B,Sk,Kv,dh] -> o [B,Sq,H,dh] (GQA-aware).
    ``interpret=None`` resolves from the backend (repro.kernels.dispatch)."""
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Sq, H, dh = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    qt = q.transpose(0, 2, 1, 3)      # [B, H, Sq, dh]
    kt = k.transpose(0, 2, 1, 3)      # [B, Kv, Sk, dh]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, dh=dh,
                               n_kblocks=nk, causal=causal)
    out = pl.pallas_call(
        kernel,
        name="flash_prefill",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
