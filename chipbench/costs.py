"""Operations and bytes that the served work needs, from shapes alone.

Counts follow the algorithm, not the program: prompt tokens as the client
sent them (padding to a bucket is waste, not work), attention over the
positions a token attends to (the causal triangle in prefill, the live
context in decode), and one read of each weight and of each live cached
key and value per step. Matrix products count 2 operations per
multiply-add; norms, softmax and rotary embeddings are left out (well
under 1% at these widths). Bytes are bfloat16.
"""
from __future__ import annotations

from typing import Sequence

from .model import Dims

BF16 = 2


def _layer_matmul_params(m: Dims) -> int:
    return (m.d * (m.heads + 2 * m.kv_heads) * m.hd + m.heads * m.hd * m.d
            + 3 * m.d * m.ff)


def weight_bytes(m: Dims) -> int:
    """Every weight once: layers, final norm and output head (the
    embedding table is read one row per token, counted per step)."""
    return BF16 * (m.layers * (_layer_matmul_params(m) + 2 * m.d)
                   + m.d + m.d * m.vocab)


def prefill_flops(m: Dims, n_tokens: int) -> float:
    """One prompt of `n_tokens`: every layer's projections and feed-forward
    per token, causal attention over the triangle, and the output head on
    the last position only (the one the first token needs)."""
    S = n_tokens
    dense = 2.0 * S * _layer_matmul_params(m)
    attn = 2.0 * 2.0 * m.heads * m.hd * S * (S + 1) / 2
    return m.layers * (dense + attn) + 2.0 * m.d * m.vocab


def attention_decode_flops(m: Dims, ctx: Sequence[int]) -> float:
    """`paged_decode` over all layers: QK and PV over each sequence's
    attended positions `ctx` (its cached tokens plus the new one)."""
    return m.layers * 4.0 * m.heads * m.hd * float(sum(ctx))


def attention_decode_bytes(m: Dims, ctx: Sequence[int]) -> float:
    """`paged_decode` over all layers: the live keys and values of each
    sequence, its query and its output."""
    kv = float(sum(ctx)) * 2 * m.kv_heads * m.hd * BF16
    qo = len(ctx) * 2 * m.heads * m.hd * BF16
    return m.layers * (kv + qo)


def decode_step_flops(m: Dims, ctx: Sequence[int]) -> float:
    """One decode step of len(ctx) sequences."""
    per_tok = 2.0 * (m.layers * _layer_matmul_params(m) + m.d * m.vocab)
    return len(ctx) * per_tok + attention_decode_flops(m, ctx)


def decode_step_bytes(m: Dims, ctx: Sequence[int]) -> float:
    """One decode step of len(ctx) sequences: the weights, one embedding
    row, the live cache read and the new token's keys and values written
    per sequence, and the logits written."""
    B = len(ctx)
    per_seq = BF16 * (m.d + m.vocab) + m.kv_bytes_per_token
    return (weight_bytes(m) + B * per_seq
            + float(sum(ctx)) * m.kv_bytes_per_token)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops"], nbytes / peak["bytes_per_s"])
