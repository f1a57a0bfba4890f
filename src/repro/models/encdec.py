"""Encoder–decoder model (seamless-m4t backbone). The audio frontend is a
stub per the assignment: `input_specs()` supplies precomputed frame
embeddings (B, T_src, d) directly to the encoder.

prefill = encoder pass + cross-KV projection + decoder prefill over the
target prefix; decode = decoder step (self-KV cache grows, cross-KV fixed).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .attention import decode_attend, flash_reference
from .common import apply_norm, embed_lookup, keygen, norm_params, param, shard
from .moe import dense_ffn_apply, dense_ffn_params
from .transformer import (attn_decode, attn_full, attn_params, stack_init,
                          _qkv)


def _xattn_params(keys, cfg):
    d = cfg.d_model
    return {
        "wq": param(next(keys), (d, cfg.num_heads, cfg.head_dim),
                    ("embed", "heads", None)),
        "wk": param(next(keys), (d, cfg.num_kv_heads, cfg.head_dim),
                    ("kv_embed", "kv_heads", None)),
        "wv": param(next(keys), (d, cfg.num_kv_heads, cfg.head_dim),
                    ("kv_embed", "kv_heads", None)),
        "wo": param(next(keys), (cfg.num_heads, cfg.head_dim, d),
                    ("heads", None, "embed")),
    }


def init(key, cfg):
    keys = keygen(key)
    d = cfg.d_model
    return {
        "embed": param(next(keys), (cfg.vocab_size, d), ("vocab", "embed"),
                       scale=cfg.d_model ** -0.5),
        "enc": stack_init(lambda ks: {
            "ln1": norm_params(next(ks), d, cfg),
            "attn": attn_params(ks, cfg),
            "ln2": norm_params(next(ks), d, cfg),
            "ffn": dense_ffn_params(ks, d, cfg.d_ff),
        }, cfg.encoder_layers, keys),
        "enc_norm": norm_params(next(keys), d, cfg),
        "dec": stack_init(lambda ks: {
            "ln1": norm_params(next(ks), d, cfg),
            "attn": attn_params(ks, cfg),
            "lnx": norm_params(next(ks), d, cfg),
            "xattn": _xattn_params(ks, cfg),
            "ln2": norm_params(next(ks), d, cfg),
            "ffn": dense_ffn_params(ks, d, cfg.d_ff),
        }, cfg.num_layers, keys),
        "final_norm": norm_params(next(keys), d, cfg),
        "lm_head": param(next(keys), (d, cfg.vocab_size), ("embed", "vocab")),
    }


def encode(params, src_embeds, cfg, attn_blocks=(512, 512)):
    """src_embeds: (B, T, d) frame embeddings -> encoder output (B, T, d)."""
    x = shard(src_embeds, "batch", None, "embed_act")
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :].astype(jnp.int32)

    def body(x, pl):
        h = apply_norm(x, pl["ln1"], cfg)
        q, k, v = _qkv(pl["attn"], h, cfg, positions, cfg.rope_theta)
        o = flash_reference(q, k, v, causal=False,
                            block_q=attn_blocks[0], block_kv=attn_blocks[1])
        x = x + jnp.einsum("bshk,hkd->bsd", o, pl["attn"]["wo"].astype(o.dtype))
        h = apply_norm(x, pl["ln2"], cfg)
        x = x + dense_ffn_apply(pl["ffn"], h, cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc"])
    return apply_norm(x, params["enc_norm"], cfg)


def _cross_kv(pl, enc_out, cfg):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, pl["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, pl["wv"].astype(enc_out.dtype))
    return k, v


def _decoder_forward(params, tgt_tokens, enc_out, cfg, attn_blocks,
                     return_cache=False, max_len=None):
    x = params["embed"][tgt_tokens]
    x = shard(x, "batch", None, "embed_act")
    B, S, _ = x.shape
    T = enc_out.shape[1]
    positions = jnp.arange(S)[None, :].astype(jnp.int32)

    def body(x, pl):
        h = apply_norm(x, pl["ln1"], cfg)
        a, kv = attn_full(pl["attn"], h, cfg, "dense", positions, attn_blocks)
        x = x + a
        h = apply_norm(x, pl["lnx"], cfg)
        q = jnp.einsum("bsd,dhk->bshk", h, pl["xattn"]["wq"].astype(h.dtype))
        xk, xv = _cross_kv(pl["xattn"], enc_out, cfg)
        o = flash_reference(q, xk, xv, causal=False,
                            block_q=attn_blocks[0], block_kv=attn_blocks[1])
        x = x + jnp.einsum("bshk,hkd->bsd", o, pl["xattn"]["wo"].astype(o.dtype))
        h = apply_norm(x, pl["ln2"], cfg)
        x = x + dense_ffn_apply(pl["ffn"], h, cfg)
        if return_cache:
            extras = (kv, (xk, xv))
        else:
            extras = ((jnp.zeros((), x.dtype),) * 2,) * 2
        return x, extras

    x, (kv, xkv) = jax.lax.scan(body, x, params["dec"])
    x = apply_norm(x, params["final_norm"], cfg)
    if return_cache:
        x = x[:, -1:]          # last-position logits only at prefill
    logits = x @ params["lm_head"].astype(x.dtype)
    logits = shard(logits, "batch", None, "vocab")
    cache = None
    if return_cache:
        k, v = kv
        target = max_len if max_len is not None else S
        if S < target:
            pad = [(0, 0), (0, 0), (0, target - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = {"k": k, "v": v, "xk": xkv[0], "xv": xkv[1],
                 "pos": jnp.full((B,), S, jnp.int32)}
    return logits, cache


def forward(params, batch, cfg, *, remat=False, attn_blocks=(512, 512),
            return_cache=False, max_len=None):
    """batch: {"src_embeds": (B,T,d), "tokens": (B,S)}"""
    enc_out = encode(params, batch["src_embeds"], cfg, attn_blocks)
    logits, cache = _decoder_forward(params, batch["tokens"], enc_out, cfg,
                                     attn_blocks, return_cache, max_len)
    return logits, cache, 0.0


def prefill(params, batch, cfg, *, attn_blocks=(512, 512), max_len=None):
    logits, cache, _ = forward(params, batch, cfg, attn_blocks=attn_blocks,
                               return_cache=True, max_len=max_len)
    return logits[:, -1], cache


def decode_step(params, cache, tokens, cfg):
    x = embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", "embed_act")
    B = x.shape[0]
    pos = cache["pos"]

    def body(x, xs):
        pl, kc, vc, xk, xv = xs
        h = apply_norm(x[:, None], pl["ln1"], cfg)[:, 0]
        a, kc, vc = attn_decode(pl["attn"], h, cfg, "dense", kc, vc, pos)
        x = x + a
        h = apply_norm(x[:, None], pl["lnx"], cfg)[:, 0]
        q = jnp.einsum("bd,dhk->bhk", h, pl["xattn"]["wq"].astype(h.dtype))
        o = decode_attend(q, xk, xv, jnp.full((B,), xk.shape[1], jnp.int32))
        x = x + jnp.einsum("bhk,hkd->bd", o, pl["xattn"]["wo"].astype(o.dtype))
        h = apply_norm(x[:, None], pl["ln2"], cfg)[:, 0]
        x = x + dense_ffn_apply(pl["ffn"], h[:, None], cfg)[:, 0]
        return x, (kc, vc)

    x, (kc, vc) = jax.lax.scan(
        body, x, (params["dec"], cache["k"], cache["v"], cache["xk"], cache["xv"]))
    x = apply_norm(x[:, None], params["final_norm"], cfg)[:, 0]
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits, dict(cache, k=kc, v=vc, pos=pos + 1)


def cache_specs(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                src_len: int = 4096):
    L = cfg.num_layers
    kv = jax.ShapeDtypeStruct((L, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
    xkv = jax.ShapeDtypeStruct((L, batch, src_len, cfg.num_kv_heads, cfg.head_dim), dtype)
    return {"k": kv, "v": kv, "xk": xkv, "xv": xkv,
            "pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}


def cache_logical_axes(cfg, batch: int = 0, max_len: int = 0):
    ax = ("layers", "kv_batch", "kv_seq", "kv_heads", None)
    return {"k": ax, "v": ax, "xk": ax, "xv": ax, "pos": ("kv_batch",)}
