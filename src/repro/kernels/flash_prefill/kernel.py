"""Pallas TPU flash-attention prefill kernel (causal, GQA, sliding window).

TPU mapping of the FlashAttention tiling: grid = (batch, q_heads, q_blocks,
kv_blocks) with the kv_blocks axis innermost/sequential ("arbitrary"), so
the online-softmax running state (m, l, acc) lives in VMEM scratch across
kv iterations. Block shapes are (block_q x head_dim) / (block_kv x
head_dim) — head_dim 64/128 aligns the MXU lane dimension; block_q/kv
default 128/256 to fill 128x128 MXU tiles while keeping
q+k+v+acc < 2 MB VMEM per step.

GQA is handled in the k/v BlockSpec index_map (kv head = q_head // group),
so no KV duplication is materialized in HBM or VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_kv: int, seq_q: int, seq_kv: int, softcap: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bkv, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kpos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = kpos < seq_kv
    if causal:
        mask &= kpos <= (seq_kv - seq_q) + qpos
    if window:
        mask &= kpos > (seq_kv - seq_q) + qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    v = v_ref[0, 0].astype(jnp.float32)                 # (bkv, hd)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _fin():
        den = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / den[:, None]).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal=True, window: int = 0,
                  block_q: int = 128, block_kv: int = 256,
                  scale=None, softcap: float = 0.0, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, Hkv, Skv, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    pq = (-Sq) % block_q
    pkv = (-Skv) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    nq = (Sq + pq) // block_q
    nk = (Skv + pkv) // block_kv

    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_q=block_q, block_kv=block_kv, seq_q=Sq,
                          seq_kv=Skv, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
    return out[:, :, :Sq]
