"""Per-Pallas-kernel validation: shape/dtype sweeps against the pure-jnp
oracles (interpret mode — the TPU target's semantics executed on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SwanConfig
from repro.core import swan_attention as swa
from repro.core import winnow
from repro.kernels.flash_prefill.flash_prefill import flash_attention_pallas
from repro.kernels.flash_prefill.ref import flash_attention_reference
from repro.kernels.flash_prefill.swan_chunk import (
    swan_chunk_stats_paged_pallas)
from repro.kernels.swan_decode.swan_decode import (
    expand_packed, gather_scores, swan_decode_paged_pallas,
    swan_decode_pallas)
from repro.kernels.swan_decode.ref import swan_decode_reference
from repro.kernels.swan_prune.swan_prune import swan_prune_pallas
from repro.kernels.swan_prune.ref import swan_prune_reference
from repro.core.projections import random_orthogonal


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _unique_idx(rng, B, Kv, S, k, dh):
    out = np.stack([rng.permutation(dh)[:k]
                    for _ in range(B * Kv * S)]).reshape(B, Kv, S, k)
    return jnp.asarray(out, jnp.int8)


TOL = {jnp.float32: 5e-5, jnp.bfloat16: 5e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Kv,G,dh,S,k,b,bs", [
    (1, 1, 1, 16, 32, 4, 8, 16),
    (2, 2, 4, 32, 64, 8, 16, 32),
    (1, 2, 2, 64, 48, 16, 8, 16),    # non-pow2 block count
])
def test_swan_decode_kernel(dtype, B, Kv, G, dh, S, k, b, bs):
    rng = np.random.default_rng(0)
    q = _rand(rng, (B, Kv, G, dh), dtype)
    kv = _rand(rng, (B, Kv, S, k), dtype)
    vv = _rand(rng, (B, Kv, S, k), dtype)
    ki = _unique_idx(rng, B, Kv, S, k, dh)
    vi = _unique_idx(rng, B, Kv, S, k, dh)
    bk = _rand(rng, (B, Kv, b, dh), dtype)
    bv = _rand(rng, (B, Kv, b, dh), dtype)
    # per-sequence ring state: stagger positions across the batch
    bpos = jnp.asarray(np.stack(
        [np.concatenate([np.arange(40 - i, 40 - i + b - 2), [-1, -1]])
         for i in range(B)]), jnp.int32)
    pos = jnp.asarray([45 - i for i in range(B)], jnp.int32)
    sp = jnp.asarray([S - 10 - i for i in range(B)], jnp.int32)
    o_k = swan_decode_pallas(q, kv, ki, vv, vi, bk, bv, bpos, pos, sp,
                             block_s=bs)
    o_r = swan_decode_reference(q, kv, ki, vv, vi, bk, bv, bpos, pos, sp)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=TOL[dtype])


def test_swan_decode_kernel_quantized():
    rng = np.random.default_rng(1)
    B, Kv, G, dh, S, k, b = 1, 2, 2, 32, 32, 8, 8
    kv8 = jnp.asarray(rng.integers(-127, 128, (B, Kv, S, k)), jnp.int8)
    vv8 = jnp.asarray(rng.integers(-127, 128, (B, Kv, S, k)), jnp.int8)
    ks = jnp.asarray(rng.random((B, Kv, S)) * 0.1 + 0.01, jnp.float32)
    vs = jnp.asarray(rng.random((B, Kv, S)) * 0.1 + 0.01, jnp.float32)
    ki = _unique_idx(rng, B, Kv, S, k, dh)
    vi = _unique_idx(rng, B, Kv, S, k, dh)
    q = _rand(rng, (B, Kv, G, dh), jnp.float32)
    bk = _rand(rng, (B, Kv, b, dh), jnp.float32)
    bv = _rand(rng, (B, Kv, b, dh), jnp.float32)
    bpos = jnp.broadcast_to(jnp.asarray(np.arange(20, 20 + b), jnp.int32),
                            (B, b))
    o_k = swan_decode_pallas(q, kv8, ki, vv8, vi, bk, bv, bpos, 27, 18,
                             k_scale=ks, v_scale=vs, block_s=16)
    o_r = swan_decode_reference(q, kv8, ki, vv8, vi, bk, bv, bpos, 27, 18,
                                k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=5e-5)


@pytest.mark.parametrize("k_active", [16, 32, 64])
def test_expand_packed_matches_unpack_dense(k_active):
    """The loop-free tile readers against the dense unpack, with the packed
    columns past ``k_active`` zeroed as ``topk_pack`` leaves them: the
    expansion exactly, the gather's scores for G = 8 query rows."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((128, 128)), jnp.bfloat16)
    vals, idx = winnow.topk_pack(x, 64, k_active=jnp.asarray(k_active))
    vals = vals.astype(jnp.float32)
    want = winnow.unpack_dense(vals, idx, 128)
    assert int((want != 0).sum(axis=1).max()) == k_active
    np.testing.assert_array_equal(np.asarray(expand_packed(vals, idx, 128)),
                                  np.asarray(want))
    q = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    s_ref = jnp.dot(q, want.T, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(gather_scores(q, vals, idx)),
                               np.asarray(s_ref), rtol=1e-5, atol=1e-4)


def _hostile_pool(rng, *, n_pages, Kv, ps, k, dh, quant):
    """A packed pool side whose pages hold distinct indices, except page 0
    (trash) and the last page, which hold random int8 indices (negatives
    and out-of-range ones included) and large values."""
    shape = (n_pages, Kv, ps, k)
    idx = np.array(_unique_idx(rng, n_pages, Kv, ps, k, dh))
    if quant:
        vals = rng.integers(-127, 128, shape).astype(np.int8)
    else:
        vals = rng.standard_normal(shape).astype(np.float32)
    scale = (rng.random(shape[:-1]) * 0.1 + 0.01).astype(np.float32)
    for page in (0, n_pages - 1):
        idx[page] = rng.integers(-128, 128, shape[1:])
        if quant:
            scale[page] = 1e4
        else:
            vals[page] = 1e4 * np.sign(rng.standard_normal(shape[1:]))
    side = {"vals": jnp.asarray(vals, jnp.int8 if quant else jnp.bfloat16),
            "idx": jnp.asarray(idx, jnp.int8)}
    if quant:
        side["scale"] = jnp.asarray(scale)
    return side


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "chunk"])
def test_paged_kernels_ignore_dead_and_garbage_tiles(kernel, quant):
    """Lane 0 reads a full prefix, lane 1 an empty one (every tile the trash
    page), lane 2 a prefix that ends inside its second page, with a third
    page mapped past ``sp_len``: the garbage there and on the trash page
    must leave the output equal to the references on the logical view."""
    rng = np.random.default_rng(9)
    B, Kv, G, dh, ps, n_log, k, b = 3, 2, 2, 64, 16, 3, 16, 8
    n_pages = 1 + 6
    sides = [_hostile_pool(rng, n_pages=n_pages, Kv=Kv, ps=ps, k=k, dh=dh,
                           quant=quant) for _ in range(2)]
    tab = jnp.asarray([[1, 2, 3], [0, 0, 0], [4, 5, n_pages - 1]], jnp.int32)
    sp = jnp.asarray([n_log * ps, 0, ps + 4], jnp.int32)
    view = swa.paged_logical_view(
        {"pool": {"k": sides[0], "v": sides[1]}, "buf_k": None,
         "buf_v": None, "buf_pos": None}, tab)
    pool = [sides[0]["vals"], sides[0]["idx"], sides[1]["vals"],
            sides[1]["idx"]]
    scales = ({"pool_k_scale": sides[0]["scale"],
               "pool_v_scale": sides[1]["scale"]} if quant else {})
    if kernel == "decode":
        q = _rand(rng, (B, Kv, G, dh), jnp.float32)
        bk = _rand(rng, (B, Kv, b, dh), jnp.float32)
        bv = _rand(rng, (B, Kv, b, dh), jnp.float32)
        bpos = sp[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
        pos = sp + b - 1
        o_k = swan_decode_paged_pallas(q, *pool, bk, bv, bpos, pos, sp, tab,
                                       **scales)
        vk, vv = view["k"], view["v"]
        o_r = swan_decode_reference(
            q, vk["vals"], vk["idx"], vv["vals"], vv["idx"], bk, bv, bpos,
            pos, sp, k_scale=vk.get("scale"), v_scale=vv.get("scale"))
        assert np.isfinite(np.asarray(o_k)).all()
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   atol=5e-5)
        return
    q = _rand(rng, (B, Kv, 8, dh), jnp.float32)
    swan = SwanConfig(k_max=k, buffer=b, mode="topk", quantize=quant)
    got = swan_chunk_stats_paged_pallas(q, *pool, sp, tab, **scales)
    if not quant:     # stats in f32: bf16 values would round q and p there
        view = {n: {**view[n], "vals": view[n]["vals"].astype(jnp.float32)}
                for n in ("k", "v")}
    want = swa._sparse_stats_bulk(q, view["k"], view["v"], swan, sp, dh)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,H,Kv,dh,bq,bk", [
    (1, 32, 2, 2, 16, 16, 16),
    (2, 64, 4, 2, 32, 16, 32),     # GQA + rectangular blocks
    (1, 48, 6, 1, 16, 16, 16),     # MQA-ish, non-pow2 seq
])
def test_flash_prefill_kernel(dtype, B, Sq, H, Kv, dh, bq, bk):
    rng = np.random.default_rng(2)
    q = _rand(rng, (B, Sq, H, dh), dtype)
    k = _rand(rng, (B, Sq, Kv, dh), dtype)
    v = _rand(rng, (B, Sq, Kv, dh), dtype)
    o_k = flash_attention_pallas(q, k, v, causal=True, block_q=bq, block_k=bk)
    o_r = flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               atol=TOL[dtype], rtol=1e-2)


def test_flash_prefill_noncausal():
    rng = np.random.default_rng(3)
    q = _rand(rng, (1, 32, 2, 16), jnp.float32)
    k = _rand(rng, (1, 32, 2, 16), jnp.float32)
    v = _rand(rng, (1, 32, 2, 16), jnp.float32)
    o_k = flash_attention_pallas(q, k, v, causal=False, block_q=16, block_k=16)
    o_r = flash_attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Kv,S,dh,k,tile", [
    (1, 2, 16, 16, 4, 8),
    (2, 2, 32, 32, 12, 16),
])
def test_swan_prune_kernel(dtype, B, Kv, S, dh, k, tile):
    rng = np.random.default_rng(4)
    x = _rand(rng, (B, Kv, S, dh), dtype)
    P = random_orthogonal(jax.random.PRNGKey(0), (Kv,), dh)
    vk, ik = swan_prune_pallas(x, P, k, tile=tile)
    vr, ir = swan_prune_reference(x, P, k)
    assert bool(jnp.all(ik == ir)), "index selection must match lax.top_k"
    np.testing.assert_allclose(np.asarray(vk, np.float32),
                               np.asarray(vr, np.float32), atol=TOL[dtype])


def test_kernel_path_equals_core_path():
    """ops.py wrapper on a real hybrid cache == core swan attention."""
    from repro.configs import SwanConfig, get_smoke_config
    from repro.core import hybrid_cache as hc
    from repro.core import swan_attention as swa
    from repro.kernels.swan_decode.ops import swan_decode_attention_kernel

    cfg = get_smoke_config("llama3-8b").replace(dtype="float32")
    swan = SwanConfig(k_max=8, buffer=4, mode="topk")
    key = jax.random.PRNGKey(0)
    kh = jax.random.normal(key, (2, 20, cfg.n_kv_heads, cfg.d_head))
    vh = jax.random.normal(jax.random.PRNGKey(1),
                           (2, 20, cfg.n_kv_heads, cfg.d_head))
    cache = hc.init_swan_cache(cfg, swan, 2, 32)
    cache = hc.swan_cache_insert_prefill(cache, swan, cfg, kh, vh)
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (2, cfg.n_kv_heads, cfg.q_group, cfg.d_head))
    o_core = swa.swan_decode_attention(q, cache, swan, cfg, 19)
    o_kern = swan_decode_attention_kernel(q, cache, swan, cfg, 19,
                                          block_s=16)
    np.testing.assert_allclose(np.asarray(o_core), np.asarray(o_kern),
                               atol=1e-5)
