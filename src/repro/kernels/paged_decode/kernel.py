"""Pallas TPU paged decode-attention kernel (PagedAttention, TPU-native).

One new token per sequence attends to a paged KV cache. The block table
rides in scalar-prefetch (SMEM) so the k/v BlockSpec index_map can chase
page indirections while the pipeline prefetches the next page HBM->VMEM —
the TPU analogue of vLLM's per-CTA page walk. Pages are the innermost
sequential grid axis; the flash-decoding running (m, l, acc) state lives in
VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38


def _kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size: int, groups: int,
            scale: float, softcap: float):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    base = p * page_size

    @pl.when(base < seq_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (H, hd)
        k = k_ref[0].astype(jnp.float32)                # (page, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        H, hd = q.shape
        Hkv = k.shape[1]
        valid = (base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
                 ) < seq_len                            # (1, page)

        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        s_rows = []
        for kv in range(Hkv):
            qg = q[kv * groups:(kv + 1) * groups]
            s_kv = jax.lax.dot_general(qg, k[:, kv],
                                       (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            s_rows.append(s_kv * scale)                 # (G, page)
        s = jnp.concatenate(s_rows, axis=0)             # (H, page)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)

        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        pexp = jnp.exp(s - m_new[:, None])              # (H, page)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pexp, axis=1)
        pv_rows = []
        for kv in range(Hkv):
            pg = pexp[kv * groups:(kv + 1) * groups]
            pv_kv = jax.lax.dot_general(pg, v[:, kv],
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            pv_rows.append(pv_kv)                       # (G, hd)
        pv = jnp.concatenate(pv_rows, axis=0)           # (H, hd)
        acc_ref[...] = acc_prev * corr[:, None] + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(p == np_ - 1)
    def _fin():
        den = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / den[:, None]).astype(o_ref.dtype)


def _dbuf_kernel(table_ref, lens_ref, q_ref, kp_ref, vp_ref, o_ref,
                 k_buf, v_buf, sem, *, page_size: int, groups: int,
                 scale: float, softcap: float):
    """Double-buffered page walk: the pools stay in compiler-chosen (HBM)
    memory and each page is DMA'd into one of two VMEM slots with
    `make_async_copy`, so page i+1's copy overlaps page i's flash step —
    the manual analogue of the BlockSpec pipeline in `_kernel`, without
    round-tripping the block table through an index_map."""
    b = pl.program_id(0)
    seq_len = lens_ref[b]
    n_used = (seq_len + page_size - 1) // page_size

    def dma(slot, i, buf, pool, ax):
        return pltpu.make_async_copy(pool.at[table_ref[b, i]],
                                     buf.at[slot], sem.at[slot, ax])

    @pl.when(n_used > 0)
    def _warm():
        dma(0, 0, k_buf, kp_ref, 0).start()
        dma(0, 0, v_buf, vp_ref, 1).start()

    q = q_ref[0].astype(jnp.float32)                    # (H, hd)
    H, hd = q.shape
    Hkv = k_buf.shape[2]

    def step(i, carry):
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_used)
        def _prefetch():
            dma(nxt, i + 1, k_buf, kp_ref, 0).start()
            dma(nxt, i + 1, v_buf, vp_ref, 1).start()

        dma(slot, i, k_buf, kp_ref, 0).wait()
        dma(slot, i, v_buf, vp_ref, 1).wait()
        k = k_buf[slot].astype(jnp.float32)             # (page, Hkv, hd)
        v = v_buf[slot].astype(jnp.float32)
        base = i * page_size
        valid = (base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
                 ) < seq_len
        s_rows = []
        for kv in range(Hkv):
            qg = q[kv * groups:(kv + 1) * groups]
            s_kv = jax.lax.dot_general(qg, k[:, kv],
                                       (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            s_rows.append(s_kv * scale)
        s = jnp.concatenate(s_rows, axis=0)             # (H, page)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        pexp = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pexp, axis=1)
        pv_rows = []
        for kv in range(Hkv):
            pg = pexp[kv * groups:(kv + 1) * groups]
            pv_rows.append(jax.lax.dot_general(
                pg, v[:, kv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        pv = jnp.concatenate(pv_rows, axis=0)
        return m_new, l_new, acc_prev * corr[:, None] + pv

    m0 = jnp.full((H,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H,), jnp.float32)
    acc0 = jnp.zeros((H, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_used, step, (m0, l0, acc0))
    den = jnp.maximum(l, 1e-37)
    o_ref[0] = (acc / den[:, None]).astype(o_ref.dtype)


def paged_decode(q, k_pages, v_pages, block_table, lens, *,
                 scale=None, softcap: float = 0.0, dbuf: bool = False,
                 interpret: bool = False):
    """q: (B, H, hd); k/v_pages: (num_pages, page, Hkv, hd);
    block_table: (B, pages_per_seq) i32; lens: (B,) i32 -> (B, H, hd).
    With `dbuf`, pages are prefetched via explicit async-copy double
    buffering instead of the BlockSpec pipeline."""
    B, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_pages.shape
    pages_per_seq = block_table.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    if dbuf:
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            functools.partial(_dbuf_kernel, page_size=page_size, groups=G,
                              scale=scale, softcap=softcap),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B,),
                in_specs=[
                    pl.BlockSpec((1, H, hd), lambda b, table, lens: (b, 0, 0)),
                    any_spec, any_spec,
                ],
                out_specs=pl.BlockSpec((1, H, hd),
                                       lambda b, table, lens: (b, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, page_size, Hkv, hd), k_pages.dtype),
                    pltpu.VMEM((2, page_size, Hkv, hd), v_pages.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
        )(block_table, lens, q, k_pages, v_pages)

    grid = (B, pages_per_seq)
    kv_spec = pl.BlockSpec(
        (1, page_size, Hkv, hd),
        lambda b, p, table, lens: (table[b, p], 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, groups=G,
                          scale=scale, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, p, table, lens: (b, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec((1, H, hd),
                                   lambda b, p, table, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H,), jnp.float32),
                pltpu.VMEM((H,), jnp.float32),
                pltpu.VMEM((H, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_table, lens, q, k_pages, v_pages)
    return out


def _insert_kernel(pidx_ref, off_ref, knew_ref, vnew_ref, kin_ref, vin_ref,
                   kout_ref, vout_ref, *, page_size: int):
    b = pl.program_id(0)
    off = off_ref[b]
    sel = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1, 1), 0) == off
    kout_ref[0] = jnp.where(sel, knew_ref[0][None].astype(kout_ref.dtype),
                            kin_ref[0])
    vout_ref[0] = jnp.where(sel, vnew_ref[0][None].astype(vout_ref.dtype),
                            vin_ref[0])


def paged_insert(k_pages, v_pages, k_new, v_new, page_idx, offset, *,
                 interpret: bool = False):
    """In-place page-pool splice of one new token per sequence.

    k/v_pages: (num_pages, page, Hkv, hd); k/v_new: (B, Hkv, hd);
    page_idx/offset: (B,) i32 -> updated (k_pages, v_pages).

    The grid visits only each sequence's target page (page_idx rides in
    scalar-prefetch so the BlockSpec walks the indirection) and the pools
    are donated via input_output_aliases — untouched pages are never read
    or written, so the splice costs O(B * page) HBM traffic instead of
    O(num_pages * page).
    """
    num_pages, page_size, Hkv, hd = k_pages.shape
    B = k_new.shape[0]
    grid = (B,)
    new_spec = pl.BlockSpec((1, Hkv, hd), lambda b, pidx, off: (b, 0, 0))
    pool_spec = pl.BlockSpec((1, page_size, Hkv, hd),
                             lambda b, pidx, off: (pidx[b], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_insert_kernel, page_size=page_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(page_idx, offset, k_new, v_new, k_pages, v_pages)
