"""Zamba2-style hybrid: Mamba2 backbone + ONE shared attention block applied
every `hybrid_attn_every` layers (weights shared across invocations, each
invocation keeps its own KV cache) [arXiv:2411.15242].

Layout: n_groups = num_layers // every groups of (every mamba blocks +
shared-attn invocation), plus a tail of leftover mamba blocks.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .common import (apply_norm, embed_lookup, keygen, norm_params, param,
                     shard, split_boxes)
from .moe import dense_ffn_apply, dense_ffn_params
from .ssd import (mamba_apply, mamba_params, mamba_state_specs, mamba_step)
from .transformer import attn_decode, attn_full, attn_params, stack_init, unembed


def _plan(cfg) -> Tuple[int, int, int]:
    every = cfg.hybrid_attn_every
    n_groups = cfg.num_layers // every
    tail = cfg.num_layers - n_groups * every
    return every, n_groups, tail


def init(key, cfg):
    keys = keygen(key)
    every, n_groups, tail = _plan(cfg)
    p: Dict[str, Any] = {
        "embed": param(next(keys), (cfg.vocab_size, cfg.d_model),
                       ("vocab", "embed"), scale=cfg.d_model ** -0.5),
        "final_norm": norm_params(next(keys), cfg.d_model, cfg),
        "lm_head": param(next(keys), (cfg.d_model, cfg.vocab_size),
                         ("embed", "vocab")),
        "groups": _reshape_groups(
            stack_init(lambda ks: {"m": mamba_params(ks, cfg),
                                   "ln": norm_params(next(ks), cfg.d_model, cfg)},
                       n_groups * every, keys), n_groups, every),
        "shared": {
            "ln1": norm_params(next(keys), cfg.d_model, cfg),
            "attn": attn_params(keys, cfg),
            "ln2": norm_params(next(keys), cfg.d_model, cfg),
            "ffn": dense_ffn_params(keys, cfg.d_model, cfg.d_ff),
        },
    }
    if tail:
        p["tail"] = stack_init(lambda ks: {"m": mamba_params(ks, cfg),
                                           "ln": norm_params(next(ks), cfg.d_model, cfg)},
                               tail, keys)
    return p


def _reshape_groups(tree, n_groups, every):
    from .common import Box

    def r(b):
        return Box(b.value.reshape(n_groups, every, *b.value.shape[1:]),
                   ("groups",) + b.axes)

    return jax.tree.map(r, tree, is_leaf=lambda x: isinstance(x, Box))


def _mamba_block(pl, x, cfg, state):
    h = apply_norm(x, pl["ln"], cfg)
    y, state = mamba_apply(pl["m"], h, cfg, h0=state[0], conv0=state[1])
    return x + y, state


def _mamba_block_step(pl, x, cfg, state):
    h = apply_norm(x[:, None], pl["ln"], cfg)[:, 0]
    y, state = mamba_step(pl["m"], h, cfg, state)
    return x + y, state


def _shared_block(ps, x, cfg, positions, attn_blocks):
    h = apply_norm(x, ps["ln1"], cfg)
    a, kv = attn_full(ps["attn"], h, cfg, "dense", positions, attn_blocks)
    x = x + a
    h = apply_norm(x, ps["ln2"], cfg)
    return x + dense_ffn_apply(ps["ffn"], h, cfg), kv


def forward(params, tokens, cfg, *, remat=False, attn_blocks=(512, 512),
            return_cache=False, max_len=None, frontend_embeds=None):
    every, n_groups, tail = _plan(cfg)
    x = params["embed"][tokens]
    x = shard(x, "batch", None, "embed_act")
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].astype(jnp.int32)

    def group_body(x, pg):
        def inner(x, pl):
            x, st = _mamba_block(pl, x, cfg, (None, None))
            return x, st
        if remat:
            inner = jax.checkpoint(inner, policy=jax.checkpoint_policies.nothing_saveable)
        x, states = jax.lax.scan(inner, x, pg)
        x, kv = _shared_block(params["shared"], x, cfg, positions, attn_blocks)
        if not return_cache:
            states = (jnp.zeros((), x.dtype), jnp.zeros((), x.dtype))
            kv = (jnp.zeros((), x.dtype),) * 2
        return x, (states, kv)

    if remat:
        group_body = jax.checkpoint(group_body, policy=jax.checkpoint_policies.nothing_saveable)
    x, (g_states, g_kv) = jax.lax.scan(group_body, x, params["groups"])

    t_states = None
    if tail:
        def inner(x, pl):
            x, st = _mamba_block(pl, x, cfg, (None, None))
            if not return_cache:
                st = (jnp.zeros((), x.dtype), jnp.zeros((), x.dtype))
            return x, st
        x, t_states = jax.lax.scan(inner, x, params["tail"])

    x = apply_norm(x, params["final_norm"], cfg)
    if return_cache:
        x = x[:, -1:]          # last-position logits only at prefill
    logits = x @ params["lm_head"].astype(x.dtype)
    logits = shard(logits, "batch", None, "vocab")

    cache = None
    if return_cache:
        target = max_len if max_len is not None else S
        k, v = g_kv
        if S < target:
            pad = [(0, 0), (0, 0), (0, target - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = {
            "ssm": g_states[0], "conv": g_states[1],           # (G, E, B, ...)
            "k": k, "v": v,                                    # (G, B, T, kv, hd)
            "pos": jnp.full((B,), S, jnp.int32),
        }
        if tail:
            cache["tail_ssm"], cache["tail_conv"] = t_states
    return logits, cache, 0.0


def prefill(params, tokens, cfg, *, attn_blocks=(512, 512), max_len=None,
            frontend_embeds=None):
    logits, cache, _ = forward(params, tokens, cfg, attn_blocks=attn_blocks,
                               return_cache=True, max_len=max_len)
    return logits[:, -1], cache


def decode_step(params, cache, tokens, cfg):
    every, n_groups, tail = _plan(cfg)
    x = embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", "embed_act")
    pos = cache["pos"]

    def group_body(x, xs):
        pg, ssm, conv, kc, vc = xs

        def inner(x_st, pl_states):
            x, = x_st
            pl, s0, c0 = pl_states
            x, st = _mamba_block_step(pl, x, cfg, (s0, c0))
            return (x,), st
        (x,), (ssm, conv) = jax.lax.scan(inner, (x,), (pg, ssm, conv))
        ps = params["shared"]
        h = apply_norm(x[:, None], ps["ln1"], cfg)[:, 0]
        a, kc, vc = attn_decode(ps["attn"], h, cfg, "dense", kc, vc, pos)
        x = x + a
        h = apply_norm(x[:, None], ps["ln2"], cfg)[:, 0]
        x = x + dense_ffn_apply(ps["ffn"], h[:, None], cfg)[:, 0]
        return x, (ssm, conv, kc, vc)

    x, (ssm, conv, kc, vc) = jax.lax.scan(
        group_body, x,
        (params["groups"], cache["ssm"], cache["conv"], cache["k"], cache["v"]))
    new_cache = dict(cache, ssm=ssm, conv=conv, k=kc, v=vc, pos=pos + 1)

    if tail:
        def inner(x_st, pl_states):
            x, = x_st
            pl, s0, c0 = pl_states
            x, st = _mamba_block_step(pl, x, cfg, (s0, c0))
            return (x,), st
        (x,), (tssm, tconv) = jax.lax.scan(
            inner, (x,), (params["tail"], cache["tail_ssm"], cache["tail_conv"]))
        new_cache["tail_ssm"], new_cache["tail_conv"] = tssm, tconv

    x = apply_norm(x[:, None], params["final_norm"], cfg)[:, 0]
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits, new_cache


def cache_specs(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    every, n_groups, tail = _plan(cfg)
    ssm, conv = mamba_state_specs(cfg, batch, dtype)
    kv = jax.ShapeDtypeStruct(
        (n_groups, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
    stk = lambda pre, s: jax.ShapeDtypeStruct(pre + s.shape, s.dtype)
    out = {
        "ssm": stk((n_groups, every), ssm),
        "conv": jax.tree.map(lambda s: stk((n_groups, every), s), conv),
        "k": kv, "v": kv,
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }
    if tail:
        out["tail_ssm"] = stk((tail,), ssm)
        out["tail_conv"] = jax.tree.map(lambda s: stk((tail,), s), conv)
    return out


def cache_logical_axes(cfg, batch: int = 0, max_len: int = 0):
    every, n_groups, tail = _plan(cfg)
    conv = {"x": ("groups", "layers", "kv_batch", None, "ssm_inner"),
            "B": ("groups", "layers", "kv_batch", None, "state"),
            "C": ("groups", "layers", "kv_batch", None, "state")}
    out = {
        "ssm": ("groups", "layers", "kv_batch", "heads", None, None),
        "conv": conv,
        "k": ("groups", "kv_batch", "kv_seq", "kv_heads", None),
        "v": ("groups", "kv_batch", "kv_seq", "kv_heads", None),
        "pos": ("kv_batch",),
    }
    if tail:
        tconv = {"x": ("layers", "kv_batch", None, "ssm_inner"),
                 "B": ("layers", "kv_batch", None, "state"),
                 "C": ("layers", "kv_batch", None, "state")}
        out["tail_ssm"] = ("layers", "kv_batch", "heads", None, None)
        out["tail_conv"] = tconv
    return out
