"""Pallas TPU paged decode-attention kernel (PagedAttention, TPU-native).

One new token per sequence attends to a paged KV cache. The grid runs
over the batch slots in order; each slot walks only the pages it holds,
`ceil(lens[b] / page_size)` of them, a block of pages at a time. The
pools stay in HBM: every live page of a block is fetched into VMEM by its
own async copy, the table entry read from SMEM, and the blocks are
double-buffered, the next slot's first block in flight while the current
slot's last block computes, so short sequences pay no pipeline bubble per
slot. A slot with `lens` 0 fetches nothing and writes a zero row.

A page `(page_size, Hkv, hd)` is viewed as `page_size * Hkv` rows of
`hd` (the pool's bytes, no copy), so a block is a `(rows, hd)` matrix:
the scores of all `H` query heads against every row of a block are one
matmul, the entries pairing a query head with another kv head's row are
masked, and the flash-decoding running (m, l, acc) state takes one
update per block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38
# VMEM for the K and V block buffers, both double-buffered. A block costs
# a fixed update and one guarded copy per page whether the page is live
# or not; on a v5e, 1 MiB times within 6% of the best block size at both
# benchmark cells' shapes.
BLOCK_VMEM_BYTES = 2 ** 20


def pages_per_block(page_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, pages_per_seq: int) -> int:
    """Pages one block moves: as many as four buffers (K and V, two
    each) of `(pages, page_size * kv_heads, head_dim)` fit in
    `BLOCK_VMEM_BYTES`, and no more than a sequence's table holds."""
    page_bytes = page_size * kv_heads * head_dim * itemsize
    return max(1, min(pages_per_seq, BLOCK_VMEM_BYTES // (4 * page_bytes)))


def _kernel(table_ref, lens_ref, q_ref, kp_ref, vp_ref, o_ref,
            k_buf, v_buf, sem, buf_ref, *, page_size: int, block: int,
            kv_heads: int, groups: int, scale: float, softcap: float):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    pages_per_seq = table_ref.shape[1]
    page_rows = page_size * kv_heads
    block_tokens = block * page_size

    def n_pages(s):
        return (lens_ref[s] + page_size - 1) // page_size

    def copies(s, blk, buf):
        """(live, K copy, V copy) for each page of slot `s`'s block `blk`."""
        out = []
        for j in range(block):
            page = blk * block + j
            idx = table_ref[s, jnp.minimum(page, pages_per_seq - 1)]
            rows = pl.ds(j * page_rows, page_rows)
            out.append((
                page < n_pages(s),
                pltpu.make_async_copy(kp_ref.at[idx], k_buf.at[buf, rows],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(vp_ref.at[idx], v_buf.at[buf, rows],
                                      sem.at[1, buf])))
        return out

    def start(s, blk, buf):
        for live, ck, cv in copies(s, blk, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(s, blk, buf):
        for live, ck, cv in copies(s, blk, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    def next_live(s):
        """The first slot after `s` that holds a page, or the slot count."""
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < n_slots, lens_ref[jnp.minimum(t, n_slots - 1)] == 0),
            lambda t: t + 1, s + 1)

    @pl.when(b == 0)
    def _first_block():
        buf_ref[0] = 0
        first = next_live(-1)

        @pl.when(first < n_slots)
        def _():
            start(first, 0, 0)

    seq_len = lens_ref[b]
    n_blocks = (seq_len + block_tokens - 1) // block_tokens
    q = q_ref[0]                                        # (H, hd)
    H, hd = q.shape
    # bf16 products are exact in the f32 accumulator, whatever precision
    # the process asks of f32 matmuls
    if q.dtype == k_buf.dtype == jnp.bfloat16:
        qk, qk_precision = q, jax.lax.Precision.DEFAULT
    else:
        qk, qk_precision = q.astype(jnp.float32), None
    rows = block * page_rows

    def step(i, carry):
        m_prev, l_prev, acc_prev = carry
        buf = buf_ref[0]

        @pl.when(i + 1 < n_blocks)
        def _prefetch_own():
            start(b, i + 1, 1 - buf)

        @pl.when(i + 1 == n_blocks)
        def _prefetch_next_slot():
            nxt = next_live(b)

            @pl.when(nxt < n_slots)
            def _():
                start(nxt, 0, 1 - buf)

        wait(b, i, buf)
        buf_ref[0] = 1 - buf
        s = jax.lax.dot_general(
            qk, k_buf[buf].astype(qk.dtype), (((1,), (1,)), ((), ())),
            precision=qk_precision, preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        # row r of a block is token r // Hkv's kv head r % Hkv; rows past
        # the slot's length hold stale or unfetched data
        n_valid = (seq_len - i * block_tokens) * kv_heads
        col = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
        ok = ((jax.lax.rem(col, kv_heads) == jax.lax.div(head, groups))
              & (col < n_valid))
        s = jnp.where(ok, s, NEG_INF)
        v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                      < n_valid, v_buf[buf].astype(jnp.float32), 0.0)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])                 # (H, rows)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc_prev * corr[:, None] + pv

    m0 = jnp.full((H,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H,), jnp.float32)
    acc0 = jnp.zeros((H, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, step, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-37)[:, None]).astype(o_ref.dtype)


def paged_decode(q, k_pages, v_pages, block_table, lens, *,
                 scale=None, softcap: float = 0.0, interpret: bool = False):
    """q: (B, H, hd); k/v_pages: (num_pages, page, Hkv, hd);
    block_table: (B, pages_per_seq) i32; lens: (B,) i32 -> (B, H, hd).
    Slot b attends over its first `lens[b]` positions; a slot with
    `lens[b] == 0` returns zeros."""
    B, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_pages.shape
    pages_per_seq = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block = pages_per_block(page_size, Hkv, hd, k_pages.dtype.itemsize,
                            pages_per_seq)
    rows = block * page_size * Hkv
    flat = (num_pages, page_size * Hkv, hd)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    slot_spec = pl.BlockSpec((1, H, hd), lambda b, table, lens: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, block=block,
                          kv_heads=Hkv, groups=H // Hkv, scale=scale,
                          softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[slot_spec, any_spec, any_spec],
            out_specs=slot_spec,
            scratch_shapes=[
                pltpu.VMEM((2, rows, hd), k_pages.dtype),
                pltpu.VMEM((2, rows, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        # the prefetch crosses slots, so the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(block_table, lens, q, k_pages.reshape(flat), v_pages.reshape(flat))


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
