"""Pallas TPU fused suffix-prefill over paged prefix KV.

A flash-prefill variant for the prefix-cache hot path: suffix queries
attend over `n_prefix_pages` of shared prefix KV read *straight from the
paged pool* (block-table-indexed BlockSpecs, same scalar-prefetch page walk
as `paged_decode`) followed by their own fresh suffix KV with the offset
causal mask. The dense `(B, P, Hkv, hd)` prefix gather the engine used to
materialize never exists: the kv grid axis first walks the prefix pages,
then the suffix blocks, carrying one online-softmax state (m, l, acc) per
kv head in VMEM scratch across both phases.

Grid: (batch, q_blocks, n_prefix_pages + suffix_kv_blocks) with the
combined kv axis innermost/sequential ("arbitrary"). Each step fetches a
whole `(page, Hkv, hd)` page (trailing block dims equal the pool's, as the
TPU tiling rule requires) and serves every query head from it: kv head
`kv` is selected by a static index and its `G = H // Hkv` query heads are
stacked into one `(G * block_q, hd)` matmul operand, so each page is read
once per q block rather than once per query head. The block table and the
per-sequence prefix/suffix lengths ride in scalar-prefetch (SMEM) so the
page indirection is resolved during pipelining and ragged lengths
(including trash-padded table slots) are masked, not branched.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _body(plen_ref, slen_ref, q_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
          acc_ref, load_page, *, scale: float, softcap: float,
          page_size: int, block_q: int, block_kv: int, n_prefix_pages: int,
          groups: int):
    """Shared by both variants; `load_page(ik)` returns prefix page ik's
    (page, Hkv, hd) K and V as f32 values."""
    b = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    hkv = ks_ref.shape[1]
    rows = groups * block_q

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accum(kv, k, v, mask):
        """One online-softmax step of kv head `kv`'s query group over a
        (G * bq, n) score tile."""
        q = q_ref[0, kv * groups:(kv + 1) * groups].astype(jnp.float32)
        q = q.reshape(rows, q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[kv]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        m_ref[kv] = m_new
        l_ref[kv] = l_ref[kv] * corr + jnp.sum(p, axis=1)
        acc_ref[kv] = acc_ref[kv] * corr[:, None] + pv

    @pl.when(ik < n_prefix_pages)
    def _prefix():
        k, v = load_page(ik)
        # global prefix position vs ragged prefix length: masks both the
        # tail of a partially-filled page and trash-padded table slots
        kpos = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        mask = kpos < plen_ref[b]
        for kv in range(hkv):
            _accum(kv, k[:, kv], v[:, kv], mask)

    @pl.when(ik >= n_prefix_pages)
    def _suffix():
        j = ik - n_prefix_pages
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (groups, block_q, block_kv), 1).reshape(rows, block_kv)
        kpos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 1)
        # suffix-local causal: every suffix query already sees the whole
        # prefix, so the offset cancels and the mask is purely local
        mask = (kpos <= qpos) & (kpos < slen_ref[b])
        for kv in range(hkv):
            _accum(kv, ks_ref[0, kv].astype(jnp.float32),
                   vs_ref[0, kv].astype(jnp.float32), mask)

    @pl.when(ik == nk - 1)
    def _fin():
        for kv in range(hkv):
            den = jnp.maximum(l_ref[kv], 1e-37)
            out = acc_ref[kv] / den[:, None]
            o_ref[0, kv * groups:(kv + 1) * groups] = out.reshape(
                groups, block_q, out.shape[-1]).astype(o_ref.dtype)


def _kernel(tab_ref, plen_ref, slen_ref, q_ref, ks_ref, vs_ref,
            kp_ref, vp_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    def load_page(ik):
        return kp_ref[0].astype(jnp.float32), vp_ref[0].astype(jnp.float32)

    _body(plen_ref, slen_ref, q_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
          acc_ref, load_page, **kw)


def _dbuf_kernel(tab_ref, plen_ref, slen_ref, q_ref, ks_ref, vs_ref,
                 kp_ref, vp_ref, o_ref, m_ref, l_ref, acc_ref,
                 k_buf, v_buf, sem, **kw):
    """`_kernel` with the paged-prefix loads double-buffered by hand: the
    pools stay in compiler-chosen (HBM) memory and page ik+1's async copy
    is started before page ik's flash step runs, carried across the
    sequential kv grid axis in two VMEM slots. Suffix KV keeps the regular
    BlockSpec pipeline (it is dense and local to the batch row)."""
    b = pl.program_id(0)
    ik = pl.program_id(2)
    npp = kw["n_prefix_pages"]

    def dma(slot, i, buf, pool, ax):
        return pltpu.make_async_copy(pool.at[tab_ref[b, i]],
                                     buf.at[slot], sem.at[slot, ax])

    @pl.when(ik == 0)
    def _warm():
        dma(0, 0, k_buf, kp_ref, 0).start()
        dma(0, 0, v_buf, vp_ref, 1).start()

    def load_page(ik):
        slot = jax.lax.rem(ik, 2)
        nxt = jax.lax.rem(ik + 1, 2)

        @pl.when(ik + 1 < npp)
        def _prefetch():
            dma(nxt, ik + 1, k_buf, kp_ref, 0).start()
            dma(nxt, ik + 1, v_buf, vp_ref, 1).start()

        dma(slot, ik, k_buf, kp_ref, 0).wait()
        dma(slot, ik, v_buf, vp_ref, 1).wait()
        return (k_buf[slot].astype(jnp.float32),
                v_buf[slot].astype(jnp.float32))

    _body(plen_ref, slen_ref, q_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
          acc_ref, load_page, **kw)


def prefix_prefill(q, k_suf, v_suf, k_pages, v_pages, prefix_table,
                   prefix_lens, suffix_lens=None, *, scale=None,
                   softcap: float = 0.0, block_q: int = 128,
                   block_kv: int = 256, dbuf: bool = False,
                   interpret: bool = False):
    """q: (B, H, Sq, hd); k/v_suf: (B, Hkv, Sq, hd);
    k/v_pages: (num_pages, page, Hkv, hd); prefix_table: (B, npp) i32;
    prefix_lens: (B,) i32; suffix_lens: (B,) i32 or None -> (B, H, Sq, hd).
    """
    B, H, Sq, hd = q.shape
    _, Hkv, _, _ = k_suf.shape
    page_size = k_pages.shape[1]
    npp = prefix_table.shape[1]
    assert npp >= 1, "prefix_prefill needs >= 1 prefix page (else use flash)"
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if suffix_lens is None:
        suffix_lens = jnp.full((B,), Sq, jnp.int32)

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sq)
    pq = (-Sq) % block_q
    pkv = (-Sq) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k_suf = jnp.pad(k_suf, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v_suf = jnp.pad(v_suf, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    nq = (Sq + pq) // block_q
    nsk = (Sq + pkv) // block_kv

    grid = (B, nq, npp + nsk)
    # suffix blocks only advance once ik passes the prefix pages; the page
    # index is clamped symmetrically so the inactive branch stays in range
    suf_spec = pl.BlockSpec(
        (1, Hkv, block_kv, hd),
        lambda b, iq, ik, tab, pl_, sl: (
            b, 0, jnp.clip(ik - npp, 0, nsk - 1), 0))
    q_spec = pl.BlockSpec(
        (1, H, block_q, hd),
        lambda b, iq, ik, tab, pl_, sl: (b, 0, iq, 0))
    softmax_scratch = [
        pltpu.VMEM((Hkv, G * block_q), jnp.float32),
        pltpu.VMEM((Hkv, G * block_q), jnp.float32),
        pltpu.VMEM((Hkv, G * block_q, hd), jnp.float32),
    ]
    kw = dict(scale=scale, softcap=softcap, page_size=page_size,
              block_q=block_q, block_kv=block_kv, n_prefix_pages=npp,
              groups=G)
    if dbuf:
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        kern = functools.partial(_dbuf_kernel, **kw)
        page_specs = [any_spec, any_spec]
        extra_scratch = [
            pltpu.VMEM((2, page_size, Hkv, hd), k_pages.dtype),
            pltpu.VMEM((2, page_size, Hkv, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]
        # the manual DMA chain serializes the q-block walk too (slots are
        # reused across grid steps), so only the batch axis stays parallel
        semantics = ("parallel", "arbitrary", "arbitrary")
    else:
        kern = functools.partial(_kernel, **kw)
        page_spec = pl.BlockSpec(
            (1, page_size, Hkv, hd),
            lambda b, iq, ik, tab, pl_, sl: (
                tab[b, jnp.minimum(ik, npp - 1)], 0, 0, 0))
        page_specs = [page_spec, page_spec]
        extra_scratch = []
        semantics = ("parallel", "parallel", "arbitrary")
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec, suf_spec, suf_spec, *page_specs],
            out_specs=q_spec,
            scratch_shapes=softmax_scratch + extra_scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
    )(prefix_table, prefix_lens, suffix_lens, q, k_suf, v_suf,
      k_pages, v_pages)
    return out[:, :, :Sq]
