"""Roofline terms from dry-run records and kernel timings.

  compute_s    = flops_per_device / peak bf16 FLOP/s
  memory_s     = hbm_bytes_per_device / peak HBM bytes/s
  collective_s = collective_bytes_per_device / ICI bytes/s per link

with the peaks of the device, looked up by ``device_kind`` in ``PEAKS``.
A device that is not in the table is an error, never a default.

All HLO-derived quantities are per-device (post-SPMD shapes), so the spec's
"X/(chips × bw)" is applied with per-chip numerators directly.  MODEL_FLOPS
uses the paper-spec formulas: 6·N·D (train) / 2·N·D (serve), N_active for
MoE; the ratio MODEL_FLOPS / (HLO_flops × chips) exposes remat/redundancy
waste (>1 means HLO under-counts — e.g. analyzer misses; <1 means extra
compiled compute such as recompute or attention FLOPs outside 6·N·D).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

# Published per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e
# (reported as "TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect (4 links x 50 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 50e9},
}
# the chip the multi-pod dry-run compiles for
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; raises on a device with no entry."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

_ADVICE = {
    "compute": ("increase arithmetic efficiency: larger per-chip tiles "
                "(reduce model-axis sharding), fuse elementwise chains, "
                "or drop remat recompute"),
    "memory": ("cut HBM traffic: SWAN-compress the KV cache / quantize "
               "weights / enlarge fusion regions so activations stay on-chip"),
    "collective": ("reshard to shrink collectives: move the sharded axis, "
                   "overlap collectives with compute, or compress the wire "
                   "format (int8 gradient sync)"),
}


def model_flops(cfg, shape, n_tokens: Optional[int] = None) -> float:
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_active * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n_active * toks
    toks = shape.global_batch           # one new token per sequence
    return 2.0 * n_active * toks


def kernel_model_bytes(cfg, shape, swan) -> int:
    """Per-device HBM bytes the fused Pallas decode kernel streams: the
    packed payload + ring buffer + params, each exactly once (BlockSpec-
    derived — every input tile is fetched once per grid point and the grid
    covers the cache once).  This is the TPU-target number the XLA ref path
    upper-bounds."""
    n_dev = 256
    n_attn = sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) == "attn")
    B, S = shape.global_batch, shape.seq_len
    per_vec = swan.k_max * (1 if swan.quantize else 2)
    if swan.mode == "topk":
        per_vec += swan.k_max                      # int8 indices
    if swan.quantize:
        per_vec += 4
    sparse = 2 * n_attn * B * cfg.n_kv_heads * S * per_vec
    buf = 2 * n_attn * B * cfg.n_kv_heads * swan.buffer * cfg.d_head * 2
    params = cfg.n_active_params() * 2
    return (sparse + buf + params) // n_dev


def _sparse_side_bytes(Kv: int, S: int, k_max: int, quantized: bool) -> int:
    """HBM bytes ONE sequence's packed sparse side (k + v) streams through
    the fused kernels: values (f32, or int8 + f32 per-vector scale when
    quantized) and int8 winnow indices, each touched exactly once — the
    BlockSpec grid covers every [block_s, k_max] tile once per (b, kv)."""
    val = k_max * (1 if quantized else 4)
    idx = k_max
    scale = 4 if quantized else 0
    return 2 * Kv * S * (val + idx + scale)


def swan_decode_kernel_bytes(*, B: int, Kv: int, G: int, dh: int, S: int,
                             k_max: int, buffer: int,
                             quantized: bool) -> int:
    """Ideal per-call HBM traffic of the fused SWAN decode kernel (slab or
    paged — the paged gather streams the same pool tiles, just via the
    prefetched page table): packed sparse prefix + dense ring buffer read
    once, q in, o out.  The pure-JAX path upper-bounds this (it
    additionally materialises expanded [S, dh] rows in HBM)."""
    sparse = B * _sparse_side_bytes(Kv, S, k_max, quantized)
    ring = 2 * B * Kv * buffer * dh * 4 + B * buffer * 4      # +buf_pos
    q = B * Kv * G * dh * 4
    o = B * Kv * G * dh * 4
    return sparse + ring + q + o


def swan_chunk_kernel_bytes(*, B: int, Kv: int, Q: int, dh: int, S: int,
                            k_max: int, quantized: bool) -> int:
    """Ideal per-call HBM traffic of the bulk-chunk prefill stats kernel:
    the packed sparse prefix once, Q query rows in, (m, l, o_unnorm)
    stats out."""
    sparse = B * _sparse_side_bytes(Kv, S, k_max, quantized)
    q = B * Kv * Q * dh * 4
    stats = B * Kv * Q * (2 + dh) * 4
    return sparse + q + stats


def flash_kernel_bytes(*, B: int, H: int, Sq: int, Sk: int, dh: int,
                       dtype_bytes: int = 4) -> int:
    """Ideal per-call HBM traffic of causal flash prefill: q/k/v in, o out,
    each once (GQA re-reads of kv tiles stay in VMEM in the ideal model)."""
    Kv_bytes = 2 * B * Sk * dh * dtype_bytes            # per kv head pair
    return B * H * Sq * dh * dtype_bytes * 2 + Kv_bytes


def flash_kernel_flops(*, B: int, H: int, Sq: int, Sk: int, dh: int,
                       causal: bool = True) -> float:
    """MXU flops of flash attention: 2 matmuls of [Sq, dh] x [dh, Sk],
    halved by the causal mask."""
    f = 4.0 * B * H * Sq * Sk * dh
    return f / 2 if causal else f


def roofline_row(name: str, us_per_call: float, hbm_bytes: int,
                 flops: float = 0.0, *, device_kind: str,
                 **tags) -> Dict[str, Any]:
    """One per-kernel roofline table row: the memory-bound (or
    compute-bound) floor time from the ideal byte/flop model vs the call
    time measured on ``device_kind``.  ``achieved_fraction`` is the
    roofline share."""
    pk = peaks(device_kind)
    mem_s = hbm_bytes / pk["hbm_bytes_per_s"]
    comp_s = flops / pk["bf16_flops"]
    bound = "compute" if comp_s > mem_s else "memory"
    floor_s = max(mem_s, comp_s)
    meas_s = us_per_call * 1e-6
    row = {"name": name, "device_kind": device_kind,
           "us_per_call": float(us_per_call),
           "hbm_bytes": int(hbm_bytes), "flops": float(flops),
           "bound": bound, "floor_us": floor_s * 1e6,
           "achieved_bw_gbs": (hbm_bytes / meas_s / 1e9) if meas_s else 0.0,
           "achieved_fraction": (floor_s / meas_s) if meas_s else 0.0}
    row.update(tags)
    return row


def roofline_report(record: Dict[str, Any], cfg, shape,
                    swan=None) -> Dict[str, Any]:
    hlo = record["hlo_cost"]
    n_dev = record["n_devices"]
    pk = peaks(DRYRUN_DEVICE_KIND)
    compute_s = hlo["flops"] / pk["bf16_flops"]
    memory_s = hlo["hbm_bytes"] / pk["hbm_bytes_per_s"]
    collective_s = hlo["collective_bytes"] / pk["ici_bytes_per_s"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    total_hlo_flops = hlo["flops"] * n_dev
    step_s = max(terms.values())
    ideal_s = mf / (n_dev * pk["bf16_flops"])
    out = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops": mf,
        "useful_flops_ratio": mf / total_hlo_flops if total_hlo_flops else 0.0,
        "roofline_fraction": ideal_s / step_s if step_s else 0.0,
        "advice": _ADVICE[bottleneck],
    }
    if swan is not None and shape.kind == "decode":
        kb = kernel_model_bytes(cfg, shape, swan)
        out["kernel_model_bytes"] = kb
        out["kernel_model_memory_s"] = kb / pk["hbm_bytes_per_s"]
    return out
