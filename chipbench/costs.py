"""Operations and bytes that the served work needs, from shapes alone.

Counts follow the algorithm, not the program: prompt tokens as the client
sent them (padding to a bucket is waste, not work), attention over the
positions a token attends to (the causal triangle in prefill, the live
context in decode), and one read of each weight and of each live cached
key and value per step. Matrix products count 2 operations per
multiply-add; norms, softmax and rotary embeddings are left out (well
under 1% at these widths). Bytes are bfloat16.

Each count is the architecture's own, from its module under `archs/`;
`roofline_s` is common to all.
"""
from __future__ import annotations

from typing import Sequence

from . import archs

BF16 = 2


def weight_bytes(m: archs.Dims) -> int:
    """Every weight once: layers, final norm and output head (the
    embedding table is read one row per token, counted per step)."""
    return archs.of(m).weight_bytes(m)


def prefill_flops(m: archs.Dims, n_tokens: int) -> float:
    """One prompt of `n_tokens`, with the output head on its last position
    only (the one the first token needs)."""
    return archs.of(m).prefill_flops(m, n_tokens)


def attention_decode_flops(m: archs.Dims, ctx: Sequence[int]) -> float:
    """`paged_decode` over all layers, for sequences that attend over
    `ctx` positions each (their cached tokens plus the new one)."""
    return archs.of(m).attention_decode_flops(m, ctx)


def attention_decode_bytes(m: archs.Dims, ctx: Sequence[int]) -> float:
    """`paged_decode` over all layers: the live cache of each sequence,
    its query and its output."""
    return archs.of(m).attention_decode_bytes(m, ctx)


def decode_step_flops(m: archs.Dims, ctx: Sequence[int]) -> float:
    """One decode step of len(ctx) sequences."""
    return archs.of(m).decode_step_flops(m, ctx)


def decode_step_bytes(m: archs.Dims, ctx: Sequence[int]) -> float:
    """One decode step of len(ctx) sequences: the weights, one embedding
    row, the live cache read and the new token's cache written per
    sequence, and the logits written."""
    return archs.of(m).decode_step_bytes(m, ctx)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops"], nbytes / peak["bytes_per_s"])
