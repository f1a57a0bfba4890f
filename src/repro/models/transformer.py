"""Decoder-only transformer covering dense / MoE / VLM / local-global archs.

Layers are grouped into *segments* of homogeneous block kind (run-length
encoded from the per-layer pattern, e.g. gemma3's 5-local:1-global). Params
of each segment are stacked on a leading "layers" axis and executed with
lax.scan, keeping the lowered HLO O(1) in depth — essential for compiling
56–80-layer configs on the dry-run host.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import decode_attend, flash_reference
from .common import (Box, act_fn, apply_norm, apply_rope, embed_lookup,
                     keygen, norm_params, param, rmsnorm, shard, split_boxes)
from .moe import dense_ffn_apply, dense_ffn_params, moe_apply, moe_params

LOCAL_ROPE_THETA = 10000.0  # gemma3 local layers


# ---------------------------------------------------------------------------
# segment plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str      # "dense" | "moe" | "local" | "global"
    n: int


def layer_plan(cfg) -> List[Segment]:
    kinds = []
    for i in range(cfg.num_layers):
        if cfg.family == "moe":
            kind = "dense" if i < cfg.moe.first_k_dense else "moe"
        elif cfg.local_global_ratio:
            r = cfg.local_global_ratio
            kind = "global" if (i % (r + 1)) == r else "local"
        elif cfg.sliding_window:
            kind = "local"
        else:
            kind = "dense"
        kinds.append(kind)
    segs: List[Segment] = []
    for k in kinds:
        if segs and segs[-1].kind == k:
            segs[-1] = Segment(k, segs[-1].n + 1)
        else:
            segs.append(Segment(k, 1))
    return segs


def _is_windowed(kind: str, cfg) -> bool:
    return kind == "local" and cfg.sliding_window > 0


def _rope_theta(kind: str, cfg) -> float:
    if cfg.local_global_ratio and kind == "local":
        return LOCAL_ROPE_THETA
    return cfg.rope_theta


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def stack_init(fn, n: int, keys):
    """`n` layers of `fn(key_iter)` stacked on a leading "layers" axis.

    All layers come from one vmapped program over `n` per-layer keys: a
    jitted init then holds one RNG computation per parameter kind instead
    of one per layer, which keeps a full-depth compile short.
    """
    axes = {}

    def one(key):
        vals, axes["tree"] = split_boxes(fn(keygen(key)))
        return vals

    vals = jax.vmap(one)(jax.random.split(next(keys), n))
    return jax.tree.map(lambda ax, v: Box(v, ("layers",) + ax),
                        axes["tree"], vals,
                        is_leaf=lambda x: isinstance(x, tuple))


def attn_params(keys, cfg):
    d = cfg.d_model
    p = {
        "wq": param(next(keys), (d, cfg.num_heads, cfg.head_dim),
                    ("embed", "heads", None)),
        "wk": param(next(keys), (d, cfg.num_kv_heads, cfg.head_dim),
                    ("kv_embed", "kv_heads", None)),
        "wv": param(next(keys), (d, cfg.num_kv_heads, cfg.head_dim),
                    ("kv_embed", "kv_heads", None)),
        "wo": param(next(keys), (cfg.num_heads, cfg.head_dim, d),
                    ("heads", None, "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = param(next(keys), (cfg.head_dim,), (None,), init="zeros")
        p["k_norm"] = param(next(keys), (cfg.head_dim,), (None,), init="zeros")
    return p


def layer_params(keys, cfg, kind: str):
    p = {
        "ln1": norm_params(next(keys), cfg.d_model, cfg),
        "attn": attn_params(keys, cfg),
        "ln2": norm_params(next(keys), cfg.d_model, cfg),
    }
    if kind == "moe":
        p["moe"] = moe_params(keys, cfg)
    else:
        ff = cfg.moe.dense_d_ff or cfg.d_ff if cfg.family == "moe" else cfg.d_ff
        p["ffn"] = dense_ffn_params(keys, cfg.d_model, ff)
    return p


def init(key, cfg):
    keys = keygen(key)
    d = cfg.d_model
    p: Dict[str, Any] = {
        "embed": param(next(keys), (cfg.vocab_size, d), ("vocab", "embed"),
                       scale=cfg.d_model ** -0.5),
        "final_norm": norm_params(next(keys), d, cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = param(next(keys), (d, cfg.vocab_size), ("embed", "vocab"))
    for i, seg in enumerate(layer_plan(cfg)):
        p[f"seg{i}"] = stack_init(
            lambda ks: layer_params(ks, cfg, seg.kind), seg.n, keys)
    return p


# ---------------------------------------------------------------------------
# attention block application
# ---------------------------------------------------------------------------

def _qkv(p, x, cfg, positions, theta):
    B, S, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, theta, cfg.rope_fraction, cfg.rope_interleaved)
    k = apply_rope(k, positions, theta, cfg.rope_fraction, cfg.rope_interleaved)
    return q, k, v


def attn_full(p, x, cfg, kind, positions, attn_blocks=(512, 512),
              prefix=None, prefix_len=None, paged_prefix=None):
    """Full-sequence attention (train / prefill). Returns (out, (k, v)).

    `prefix` is an optional (k, v) pair of already-roped cached KV for
    positions before this chunk (shape (B, P, Hkv, hd)): queries attend
    over [prefix, self] with the causal offset handled by
    `flash_reference`'s Sq < Skv masking; `prefix_len` (scalar) marks how
    many of those P positions are live when the gather was padded to a
    bucket. `paged_prefix` = (k_pages, v_pages, block_table, prefix_lens)
    instead reads the prefix straight from the paged pool via the fused
    `prefix_prefill` kernel — no dense prefix is ever materialized. The
    returned cache carries only this chunk's KV — the prefix stays where
    it was cached."""
    window = cfg.sliding_window if _is_windowed(kind, cfg) else 0
    assert prefix is None or window == 0, "prefix reuse needs full attention"
    assert paged_prefix is None or window == 0
    q, k, v = _qkv(p, x, cfg, positions, _rope_theta(kind, cfg))
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    if paged_prefix is not None:
        from ..kernels.prefix_prefill.ops import prefix_prefill_op
        kp_l, vp_l, table, plens = paged_prefix
        o = prefix_prefill_op(q, k, v, kp_l, vp_l, table, plens,
                              block_q=attn_blocks[0],
                              block_kv=attn_blocks[1],
                              softcap=cfg.attn_logit_softcap)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype)), (k, v)
    ka, va = k, v
    if prefix is not None:
        pk, pv = prefix
        ka = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        va = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
    o = flash_reference(q, ka, va, causal=True, window=window,
                        block_q=attn_blocks[0], block_kv=attn_blocks[1],
                        logit_softcap=cfg.attn_logit_softcap,
                        prefix_len=prefix_len)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype)), (k, v)


def attn_decode(p, x, cfg, kind, k_cache, v_cache, pos):
    """Single-token attention. x: (B, d); pos: (B,) current write index.
    Returns (out, k_cache', v_cache')."""
    B, d = x.shape
    window = cfg.sliding_window if _is_windowed(kind, cfg) else 0
    q, k, v = _qkv(p, x[:, None], cfg, pos[:, None], _rope_theta(kind, cfg))
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    Smax = k_cache.shape[1]
    widx = (pos % Smax) if window else jnp.minimum(pos, Smax - 1)
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, widx].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, widx].set(v.astype(v_cache.dtype))
    kv_len = jnp.minimum(pos + 1, Smax)
    o = decode_attend(q, k_cache, v_cache, kv_len,
                      window=0,  # ring cache already bounds the window
                      logit_softcap=cfg.attn_logit_softcap,
                      ring_pos=pos if window else None)
    return jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(o.dtype)), k_cache, v_cache


def _ffn(pl, x, cfg, kind):
    if kind == "moe":
        out, aux = moe_apply(pl["moe"], x, cfg)
        return out, aux
    return dense_ffn_apply(pl["ffn"], x, cfg), 0.0


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg, frontend_embeds=None):
    x = params["embed"][tokens]  # vocab-sharded gather
    if cfg.embedding_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return x


def _layer_body(x, pl, cfg, kind, positions, attn_blocks, prefix=None,
                prefix_len=None, paged_prefix=None):
    h = apply_norm(x, pl["ln1"], cfg)
    a, kv = attn_full(pl["attn"], h, cfg, kind, positions, attn_blocks,
                      prefix=prefix, prefix_len=prefix_len,
                      paged_prefix=paged_prefix)
    x = x + a
    h = apply_norm(x, pl["ln2"], cfg)
    f, aux = _ffn(pl, h, cfg, kind)
    x = x + f
    x = shard(x, "batch", None, "embed_act")
    return x, kv, aux


def forward(params, tokens, cfg, *, frontend_embeds=None, remat=False,
            attn_blocks=(512, 512), return_cache=False, max_len=None,
            prefix_kv=None, prefix_pages=None, prefix_table=None,
            prefix_len=None, pos_offset=0, last_pos=None):
    """Full-sequence forward. tokens: (B, S_text). Returns (logits, cache, aux).

    Prefix reuse (serving prefix cache): `prefix_kv` maps segment names to
    {"k", "v"} arrays of shape (layers, B, P, Hkv, hd) holding the cached,
    already-roped KV of the first P prompt positions; `tokens` then covers
    only the uncached suffix and `pos_offset` (= P) shifts its rope
    positions. When the gather was padded to a bucket, `prefix_len`
    (scalar or (B,)) marks how many of the P positions are live.

    Fused paged path: `prefix_pages` maps segment names to {"k", "v"}
    *page pools* of shape (layers, num_pages, page_size, Hkv, hd) and
    `prefix_table` (B, npp) i32 addresses the prefix pages directly —
    attention runs the fused `prefix_prefill` kernel, never gathering the
    prefix densely. `prefix_len` then must be given ((B,) i32 live prefix
    tokens; trash-padded table slots are masked).

    `last_pos` picks which position's logits to return when
    `return_cache` (defaults to the final one — callers that right-pad
    pass the last *real* index)."""
    x = embed_tokens(params, tokens, cfg, frontend_embeds)
    x = shard(x, "batch", None, "embed_act")
    B, S, _ = x.shape
    positions = (jnp.asarray(pos_offset, jnp.int32)
                 + jnp.arange(S, dtype=jnp.int32))[None, :]
    if prefix_pages is not None:
        assert prefix_table is not None and prefix_len is not None
        plens = jnp.broadcast_to(jnp.asarray(prefix_len, jnp.int32), (B,))
    aux_total = 0.0
    cache: Dict[str, Any] = {}
    for i, seg in enumerate(layer_plan(cfg)):
        pkv = prefix_kv.get(f"seg{i}") if prefix_kv is not None else None
        ppg = prefix_pages.get(f"seg{i}") if prefix_pages is not None else None

        def body(x, layer, _kind=seg.kind, _pkv=pkv, _ppg=ppg):
            prefix = paged = None
            if _pkv is not None:
                pl, pk_l, pv_l = layer
                prefix = (pk_l, pv_l)
            elif _ppg is not None:
                pl, kp_l, vp_l = layer
                paged = (kp_l, vp_l, prefix_table, plens)
            else:
                pl = layer
            x, kv, aux = _layer_body(x, pl, cfg, _kind, positions, attn_blocks,
                                     prefix=prefix, prefix_len=prefix_len,
                                     paged_prefix=paged)
            if not return_cache:
                kv = (jnp.zeros((), x.dtype),) * 2  # don't carry KV in train
            return x, (kv, aux)
        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=())
        if pkv is not None:
            xs = (params[f"seg{i}"], pkv["k"], pkv["v"])
        elif ppg is not None:
            xs = (params[f"seg{i}"], ppg["k"], ppg["v"])
        else:
            xs = params[f"seg{i}"]
        x, (kvs, auxs) = jax.lax.scan(body, x, xs)
        aux_total = aux_total + jnp.sum(auxs)
        if return_cache:
            k_seg, v_seg = kvs
            target = S if max_len is None else max_len
            if _is_windowed(seg.kind, cfg):
                target = min(cfg.sliding_window, target)
            if S > target:
                # ring-pack the trailing `target` positions
                idx = (jnp.arange(S - target, S) % target)
                k_seg = jnp.zeros_like(k_seg[:, :, :target]).at[:, :, idx].set(k_seg[:, :, -target:])
                v_seg = jnp.zeros_like(v_seg[:, :, :target]).at[:, :, idx].set(v_seg[:, :, -target:])
            elif S < target:
                pad = [(0, 0), (0, 0), (0, target - S), (0, 0), (0, 0)]
                k_seg, v_seg = jnp.pad(k_seg, pad), jnp.pad(v_seg, pad)
            cache[f"seg{i}"] = {"k": k_seg, "v": v_seg}
    x = apply_norm(x, params["final_norm"], cfg)
    if return_cache:
        # prefill only needs the last position's logits — computing the
        # full (B,S,V) tensor would cost ~V/d extra memory (§Perf)
        if last_pos is None:
            x_last = x[:, -1]
        else:
            lp = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32), (B,))
            x_last = jnp.take_along_axis(x, lp[:, None, None], axis=1)[:, 0]
        logits = unembed(params, x_last, cfg)[:, None]
        cache["pos"] = jnp.full((B,), S, jnp.int32) + jnp.asarray(
            pos_offset, jnp.int32)
    else:
        logits = unembed(params, x, cfg)
    return logits, cache, aux_total


def unembed(params, x, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w.astype(x.dtype)
    # arity-aware constraint: decode logits are (B, V), train/prefill (B,S,V)
    ax = ("batch", None, "vocab") if logits.ndim == 3 else ("batch", "vocab")
    return shard(logits, *ax)


def prefill(params, tokens, cfg, *, frontend_embeds=None,
            attn_blocks=(512, 512), max_len=None):
    """Returns (last-token logits (B, V), cache sized for max_len)."""
    logits, cache, _ = forward(params, tokens, cfg,
                               frontend_embeds=frontend_embeds,
                               attn_blocks=attn_blocks, return_cache=True,
                               max_len=max_len)
    return logits[:, -1], cache


def decode_step(params, cache, tokens, cfg):
    """tokens: (B,) int32. Returns (logits (B, V), cache')."""
    x = embed_lookup(params["embed"], tokens)
    if cfg.embedding_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = shard(x, "batch", "embed_act")
    pos = cache["pos"]
    new_cache: Dict[str, Any] = {}
    for i, seg in enumerate(layer_plan(cfg)):
        kc, vc = cache[f"seg{i}"]["k"], cache[f"seg{i}"]["v"]

        def body(x, layer, _kind=seg.kind):
            pl, kc_l, vc_l = layer
            h = apply_norm(x[:, None], pl["ln1"], cfg)[:, 0]
            a, kc_l, vc_l = attn_decode(pl["attn"], h, cfg, _kind, kc_l, vc_l, pos)
            x = x + a
            h = apply_norm(x[:, None], pl["ln2"], cfg)[:, 0]
            f, _ = _ffn(pl, h[:, None], cfg, _kind)
            return x + f[:, 0], (kc_l, vc_l)

        x, (kc, vc) = jax.lax.scan(body, x, (params[f"seg{i}"], kc, vc))
        new_cache[f"seg{i}"] = {"k": kc, "v": vc}
    x = apply_norm(x[:, None], params["final_norm"], cfg)[:, 0]
    logits = unembed(params, x, cfg)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def decode_step_paged(params, cache, tokens, cfg):
    """Paged-KV decode step. tokens: (B,) int32. Returns (logits, cache').

    The cache holds per-segment page pools `(layers, num_pages, page_size,
    Hkv, hd)` shared across sequences, plus one block table `(B,
    pages_per_seq)` used by every layer: logical page j of sequence b lives
    in physical page `block_tables[b, j]` of *each* layer's pool. The new
    token's K/V is scattered into page `pos // page_size`, offset `pos %
    page_size`, then attention runs through `paged_decode_op` (Pallas on
    TPU, jnp oracle on CPU). Only non-windowed attention segments are
    supported — callers gate on `supports_paged`.
    """
    x = embed_lookup(params["embed"], tokens)
    if cfg.embedding_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = shard(x, "batch", "embed_act")
    from ..kernels.paged_decode.ops import paged_decode_op, paged_insert_op
    pos = cache["pos"]
    table = cache["block_tables"]
    B = tokens.shape[0]
    max_pps = table.shape[1]
    bidx = jnp.arange(B)
    new_cache: Dict[str, Any] = {}
    for i, seg in enumerate(layer_plan(cfg)):
        kc, vc = cache[f"seg{i}"]["k"], cache[f"seg{i}"]["v"]
        page_size = kc.shape[2]
        # freed/idle slots keep pos growing into the reserved trash page 0;
        # clamp so the page walk stays in-table and the write stays benign
        wpos = jnp.minimum(pos, max_pps * page_size - 1)
        pidx = table[bidx, wpos // page_size]
        off = wpos % page_size
        # a free slot's table starts at the trash page: it attends to
        # nothing, so the page walk skips it
        lens = jnp.where(table[:, 0] == 0, 0,
                         jnp.minimum(pos + 1, max_pps * page_size))

        def body(x, layer, _kind=seg.kind):
            pl, kc_l, vc_l = layer
            h = apply_norm(x[:, None], pl["ln1"], cfg)[:, 0]
            q, k, v = _qkv(pl["attn"], h[:, None], cfg, pos[:, None],
                           _rope_theta(_kind, cfg))
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            # splice through the paged_insert kernel: the fresh token's KV
            # feeds attention without a dense detour (ref path is the same
            # .at[pidx, off].set scatter, so tokens stay byte-identical)
            kc_l, vc_l = paged_insert_op(kc_l, vc_l, k, v, pidx, off)
            o = paged_decode_op(q, kc_l, vc_l, table, lens,
                                softcap=cfg.attn_logit_softcap)
            a = jnp.einsum("bhk,hkd->bd", o, pl["attn"]["wo"].astype(o.dtype))
            x = x + a
            h = apply_norm(x[:, None], pl["ln2"], cfg)[:, 0]
            f, _ = _ffn(pl, h[:, None], cfg, _kind)
            return x + f[:, 0], (kc_l, vc_l)

        x, (kc, vc) = jax.lax.scan(body, x, (params[f"seg{i}"], kc, vc))
        new_cache[f"seg{i}"] = {"k": kc, "v": vc}
    x = apply_norm(x[:, None], params["final_norm"], cfg)[:, 0]
    logits = unembed(params, x, cfg)
    new_cache["pos"] = pos + 1
    new_cache["block_tables"] = table
    return logits, new_cache


def paged_cache_specs(cfg, batch: int, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16, max_len: Optional[int] = None):
    """ShapeDtypeStructs for a paged KV cache.

    Per attention segment: k/v pools `(layers, num_pages, page_size, Hkv,
    hd)`. `block_tables` is `(batch, pages_per_seq)` where pages_per_seq =
    ceil(max_len / page_size); unassigned entries point at the reserved
    trash page 0. `pos` is the per-slot write cursor.
    """
    max_len = max_len if max_len is not None else num_pages * page_size
    pps = -(-max_len // page_size)
    out: Dict[str, Any] = {}
    for i, seg in enumerate(layer_plan(cfg)):
        shp = (seg.n, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
        out[f"seg{i}"] = {"k": jax.ShapeDtypeStruct(shp, dtype),
                          "v": jax.ShapeDtypeStruct(shp, dtype)}
    out["block_tables"] = jax.ShapeDtypeStruct((batch, pps), jnp.int32)
    out["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return out


def cache_specs(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """ShapeDtypeStructs for the KV cache (dry-run decode inputs)."""
    out: Dict[str, Any] = {}
    for i, seg in enumerate(layer_plan(cfg)):
        S = min(cfg.sliding_window, max_len) if _is_windowed(seg.kind, cfg) else max_len
        shp = (seg.n, batch, S, cfg.num_kv_heads, cfg.head_dim)
        out[f"seg{i}"] = {"k": jax.ShapeDtypeStruct(shp, dtype),
                          "v": jax.ShapeDtypeStruct(shp, dtype)}
    out["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return out


def cache_logical_axes(cfg, batch: int = 0, max_len: int = 0):
    """Logical axes matching cache_specs (same tree structure)."""
    out: Dict[str, Any] = {}
    for i, _seg in enumerate(layer_plan(cfg)):
        ax = ("layers", "kv_batch", "kv_seq", "kv_heads", None)
        out[f"seg{i}"] = {"k": ax, "v": ax}
    out["pos"] = ("kv_batch",)
    return out
