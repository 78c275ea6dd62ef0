"""The cost models against hand counts at the served shapes, with bf16
packed values and int8 indices."""
import json

import pytest

from bench.cell import BENCH
from bench.costs import Shapes
from bench.model import model_fields


SWAN = {"k_max": 64, "buffer": 128, "mode": "topk", "quantize": False}
# Yi-9B's published widths, cut to one 12-layer stage of 48
YI_9B = {"n_layers": 12, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
         "d_head": 128, "d_ff": 11008, "vocab_size": 64000,
         "dtype": "bfloat16"}


def shapes(name):
    if name == "yi-9b":
        return Shapes.from_fields(YI_9B, SWAN)
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return Shapes.from_fields(model_fields(conf), conf["swan"])


@pytest.mark.parametrize("name,per_token", [
    # 2 sides x KV heads x (64 x 2 B values + 64 x 1 B indices) x layers
    ("olmo-1b", 2 * 16 * (64 * 2 + 64) * 16),        # 98,304
    ("yi-9b", 2 * 4 * (64 * 2 + 64) * 12),           # 18,432
])
def test_packed_bytes_per_token(name, per_token):
    assert shapes(name).packed_bytes_per_token() == per_token


def test_decode_call_bytes_olmo():
    s = shapes("olmo-1b")
    # a lane at position 1000 with k=32: 873 sparse tokens at 32 columns,
    # a full 128-token ring, q and o
    pos, k = 1000, 32
    sparse = 2 * 16 * 873 * 32 * 3
    ring = 2 * 16 * 128 * 128 * 2
    qo = 2 * 16 * 128 * 2
    assert s.decode_call_bytes([(pos, k)]) == sparse + ring + qo
    # a young lane: no sparse prefix, a ring of pos + 1 tokens
    assert s.decode_call_bytes([(9, 64)]) == 2 * 16 * 10 * 128 * 2 + qo


def test_chunk_call_cost_yi():
    s = shapes("yi-9b")
    start, n, k = 1024, 256, 64
    sp = 1024 - 128
    rows = 4 * 256 * 8                     # KV heads x tokens x G
    want_bytes = 2 * 4 * sp * k * 3 + rows * 128 * 4 + rows * 130 * 4
    want_ops = 4 * 32 * 256 * sp * k
    assert s.chunk_call_cost([(start, n, k)]) == (want_bytes, want_ops)
    assert s.chunk_call_cost([(0, 256, 64)])[1] == 0


def test_model_flops():
    s = shapes("yi-9b")
    d, ff, h, kv, dh, L, V = 4096, 11008, 32, 4, 128, 12, 64000
    layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff
    assert s.token_matmul_flops() == 2 * L * layer
    # 12 layers x 173.0 M parameters (the issue's count)
    assert layer == pytest.approx(173.0e6, rel=1e-3)
    pos, k = 2000, 64
    attn = 4 * L * h * ((pos + 1 - 128) * k + 128 * dh)
    assert s.decode_token_flops(pos, k) == 2 * L * layer + attn + 2 * d * V
    # one lane's first chunk: everything dense and causal
    n = 256
    dense = n * 1 + n * (n - 1) // 2
    assert s.chunk_flops(0, n, k) == (n * 2 * L * layer
                                      + 4 * L * h * dense * dh + 2 * d * V)


def test_quantized_cache_is_refused():
    conf = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
    with pytest.raises(ValueError):
        Shapes.from_fields(model_fields(conf), {**conf["swan"],
                                                "quantize": True})
