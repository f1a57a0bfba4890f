"""Pure Mamba2 language model (attention-free) [arXiv:2405.21060]."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .common import apply_norm, embed_lookup, keygen, norm_params, param, shard
from .ssd import mamba_apply, mamba_params, mamba_state_specs, mamba_step
from .transformer import stack_init


def init(key, cfg):
    keys = keygen(key)
    return {
        "embed": param(next(keys), (cfg.vocab_size, cfg.d_model),
                       ("vocab", "embed"), scale=cfg.d_model ** -0.5),
        "layers": stack_init(lambda ks: {
            "ln": norm_params(next(ks), cfg.d_model, cfg),
            "m": mamba_params(ks, cfg),
        }, cfg.num_layers, keys),
        "final_norm": norm_params(next(keys), cfg.d_model, cfg),
    }


def forward(params, tokens, cfg, *, remat=False, return_cache=False,
            max_len=None, attn_blocks=None, frontend_embeds=None):
    x = params["embed"][tokens]
    x = shard(x, "batch", None, "embed_act")

    def body(x, pl):
        h = apply_norm(x, pl["ln"], cfg)
        y, state = mamba_apply(pl["m"], h, cfg)
        if not return_cache:
            state = (jnp.zeros((), x.dtype), jnp.zeros((), x.dtype))
        return x + y, state

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, states = jax.lax.scan(body, x, params["layers"])
    x = apply_norm(x, params["final_norm"], cfg)
    if return_cache:
        x = x[:, -1:]          # last-position logits only at prefill
    logits = x @ params["embed"].T.astype(x.dtype)   # tied
    logits = shard(logits, "batch", None, "vocab")
    cache = None
    if return_cache:
        cache = {"ssm": states[0], "conv": states[1],
                 "pos": jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)}
    return logits, cache, 0.0


def prefill(params, tokens, cfg, *, max_len=None, attn_blocks=None,
            frontend_embeds=None):
    logits, cache, _ = forward(params, tokens, cfg, return_cache=True)
    return logits[:, -1], cache


def decode_step(params, cache, tokens, cfg):
    x = embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", "embed_act")

    def body(x_t, xs):
        x, = x_t
        pl, s, c = xs
        h = apply_norm(x[:, None], pl["ln"], cfg)[:, 0]
        y, (s, c) = mamba_step(pl["m"], h, cfg, (s, c))
        return (x + y,), (s, c)

    (x,), (s, c) = jax.lax.scan(body, (x,),
                                (params["layers"], cache["ssm"], cache["conv"]))
    x = apply_norm(x[:, None], params["final_norm"], cfg)[:, 0]
    logits = x @ params["embed"].T.astype(x.dtype)
    return logits, dict(cache, ssm=s, conv=c, pos=cache["pos"] + 1)


def cache_specs(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    ssm, conv = mamba_state_specs(cfg, batch, dtype)
    L = cfg.num_layers
    return {
        "ssm": jax.ShapeDtypeStruct((L,) + ssm.shape, ssm.dtype),
        "conv": jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), conv),
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def cache_logical_axes(cfg, batch: int = 0, max_len: int = 0):
    conv = {"x": ("layers", "kv_batch", None, "ssm_inner"),
            "B": ("layers", "kv_batch", None, "state"),
            "C": ("layers", "kv_batch", None, "state")}
    return {"ssm": ("layers", "kv_batch", "heads", None, None),
            "conv": conv, "pos": ("kv_batch",)}
