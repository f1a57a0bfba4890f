"""Pure-jnp oracle for paged decode attention."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def paged_decode_ref(q, k_pages, v_pages, block_table, lens, *,
                     scale=None, softcap: float = 0.0):
    B, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_pages.shape
    pages_per_seq = block_table.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    # gather each sequence's pages -> contiguous (B, S, Hkv, hd)
    k_seq = k_pages[block_table].reshape(B, pages_per_seq * page_size, Hkv, hd)
    v_seq = v_pages[block_table].reshape(B, pages_per_seq * page_size, Hkv, hd)
    kr = jnp.repeat(k_seq, G, axis=2)
    vr = jnp.repeat(v_seq, G, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(pages_per_seq * page_size)
    mask = pos[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", p, vr.astype(jnp.float32))
    # a slot of length 0 attends to nothing
    o = jnp.where((lens > 0)[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def paged_insert_ref(k_pages, v_pages, k_new, v_new, page_idx, offset):
    """Scatter one new token per sequence into its page: k/v_pages
    (num_pages, page, Hkv, hd); k/v_new (B, Hkv, hd); page_idx/offset (B,)
    i32. Returns the updated (k_pages, v_pages).

    This is byte-identical to the dense `.at[pidx, off].set(...)` splice
    the model used before the kernel existed — the decode-token equality
    tests pin that.
    """
    k_pages = k_pages.at[page_idx, offset].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page_idx, offset].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages
