"""Bytes and operations of the serve path, from shapes alone.

The paged SWAN cache stores, per layer, side (keys, values), KV head and
token, ``k_max`` packed values in the model dtype (bf16: 2 bytes) and
``k_max`` int8 indices; the dense ring holds the newest ``buffer`` tokens
at full ``d_head`` in the model dtype.  A request with retention ``k``
has only its first ``k`` packed columns non-zero, so the bytes its
sparse prefix *needs* are counted at ``k`` columns: the least a kernel
must read, not what the current kernel reads (it streams every column of
every page of the shipped bucket).

Positions follow the engine: a decode step at position ``pos`` attends a
sparse prefix of ``max(pos + 1 - buffer, 0)`` tokens and a ring of
``min(pos + 1, buffer)``; a prefill chunk starting at ``start`` attends a
sparse prefix of ``max(start - buffer, 0)`` tokens and everything after it
densely.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


@dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    buffer: int
    k_max: int
    value_bytes: int = 2       # packed values and activations (bf16)
    index_bytes: int = 1       # packed indices (int8)

    @classmethod
    def from_fields(cls, model: dict, swan: dict) -> "Shapes":
        """``model``: the program's model fields as run (``n_layers``,
        ``d_model``, ...); ``swan``: the configuration's ``swan`` group."""
        vb = DTYPE_BYTES[model.get("dtype", "bfloat16")]
        if swan.get("quantize"):
            raise ValueError("byte counts of the int8 cache are not modelled")
        return cls(n_layers=model["n_layers"], d_model=model["d_model"],
                   n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
                   d_head=model["d_head"], d_ff=model["d_ff"],
                   vocab=model["vocab_size"], buffer=swan["buffer"],
                   k_max=swan["k_max"], value_bytes=vb)

    # -- cache ----------------------------------------------------------
    def packed_bytes_per_token(self) -> int:
        """Bytes one cached (winnowed) token holds in the page pool, all
        layers and both sides, at ``k_max`` columns."""
        return (2 * self.n_layers * self.n_kv_heads * self.k_max
                * (self.value_bytes + self.index_bytes))

    def sparse_len_decode(self, pos: int) -> int:
        return max(pos + 1 - self.buffer, 0)

    def sparse_len_chunk(self, start: int) -> int:
        return max(start - self.buffer, 0)

    # -- kernels (one call = one layer) ---------------------------------
    def decode_call_bytes(self, lanes: Iterable[Tuple[int, int]]) -> int:
        """Least HBM bytes of one paged decode-kernel call (one layer) for
        live lanes ``(pos, k)``: each lane's packed sparse prefix at its
        own length and ``k``, its ring, q in and o out."""
        kv, dh, vb = self.n_kv_heads, self.d_head, self.value_bytes
        total = 0
        for pos, k in lanes:
            sp = self.sparse_len_decode(pos)
            ring = min(pos + 1, self.buffer)
            total += 2 * kv * sp * k * (vb + self.index_bytes)
            total += 2 * kv * ring * dh * vb
            total += 2 * self.n_heads * dh * vb                 # q, o
        return total

    def chunk_call_cost(self, lanes: Iterable[Tuple[int, int, int]]
                        ) -> Tuple[int, int]:
        """(least HBM bytes, least operations) of one paged chunk-stats
        kernel call (one layer) for lanes ``(start, n_tokens, k)``: the
        packed sparse prefix at ``k`` columns, the f32 queries in and the
        f32 partial softmax statistics out (m, l and o per query row);
        scores and weighted values at ``k`` multiply-adds per key."""
        kv, dh, vb = self.n_kv_heads, self.d_head, self.value_bytes
        g = self.n_heads // self.n_kv_heads
        nbytes = ops = 0
        for start, n, k in lanes:
            sp = self.sparse_len_chunk(start)
            rows = kv * n * g
            nbytes += 2 * kv * sp * k * (vb + self.index_bytes)
            nbytes += rows * dh * 4 + rows * (dh + 2) * 4
            ops += 4 * self.n_heads * n * sp * k
        return nbytes, ops

    # -- whole model ----------------------------------------------------
    def layer_matmul_params(self) -> int:
        d, h, kv, dh, ff = (self.d_model, self.n_heads, self.n_kv_heads,
                            self.d_head, self.d_ff)
        return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff

    def token_matmul_flops(self) -> int:
        """Operations of the layers' matrix products for one token."""
        return 2 * self.n_layers * self.layer_matmul_params()

    def head_flops(self) -> int:
        """Operations of one row of logits."""
        return 2 * self.d_model * self.vocab

    def decode_token_flops(self, pos: int, k: int) -> int:
        """Model operations of one decoded token at ``pos``: the layers'
        matrix products, attention at ``k`` over the sparse prefix and at
        ``d_head`` over the ring, and its row of logits."""
        sp = self.sparse_len_decode(pos)
        ring = min(pos + 1, self.buffer)
        attn = 4 * self.n_layers * self.n_heads * (sp * k + ring * self.d_head)
        return self.token_matmul_flops() + attn + self.head_flops()

    def chunk_flops(self, start: int, n: int, k: int) -> int:
        """Model operations of one lane's prefill chunk of ``n`` tokens at
        ``start``: matrix products for every token, attention at ``k``
        over the sparse prefix and at ``d_head`` (causally) over the ring
        and the chunk, and one row of logits (the chunk's last token)."""
        sp = self.sparse_len_chunk(start)
        dense = n * (start - sp + 1) + n * (n - 1) // 2
        attn = 4 * self.n_layers * self.n_heads * (n * sp * k
                                                   + dense * self.d_head)
        return n * self.token_matmul_flops() + attn + self.head_flops()
