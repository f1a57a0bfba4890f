"""Unified model facade: one interface per family for engines/launchers.

batch dicts:
  LM families:  {"tokens": (B, S) i32 [, "frontend_embeds": (B, P, d)]}
  encdec:       {"src_embeds": (B, T, d), "tokens": (B, S) i32}
decode tokens: (B,) i32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import encdec, hybrid, mamba_lm, transformer
from .common import Box, split_boxes


def _mod(cfg):
    return {
        "dense": transformer, "moe": transformer, "vlm": transformer,
        "ssm": mamba_lm, "hybrid": hybrid, "encdec": encdec,
    }[cfg.family]


@dataclasses.dataclass
class Model:
    cfg: Any

    # ---- params ----------------------------------------------------
    def init(self, key, dtype=jnp.float32):
        """Random params in `dtype`, built and cast inside one jitted
        program: a bf16 model never holds an f32 copy on the device."""
        return _init(self.cfg, dtype, key)

    def init_with_axes(self, key):
        return split_boxes(_mod(self.cfg).init(key, self.cfg))

    def param_axes(self):
        """Logical-axes pytree without allocating (eval_shape the init)."""
        axes = {}

        def runner(key):
            nonlocal axes
            params, axes_ = split_boxes(_mod(self.cfg).init(key, self.cfg))
            axes = axes_
            return params

        shapes = jax.eval_shape(runner, jax.random.PRNGKey(0))
        return shapes, axes

    def cast(self, params, dtype):
        """Floating leaves to `dtype`; leaves already in it are returned
        as they are (no copy)."""
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype
            else x, params)

    # ---- compute ----------------------------------------------------
    def _fe(self, batch):
        return batch.get("frontend_embeds")

    def forward(self, params, batch, *, remat=False, attn_blocks=(512, 512)):
        """Full-sequence logits (training). Returns (logits, aux)."""
        m = _mod(self.cfg)
        if self.cfg.family == "encdec":
            logits, _, aux = m.forward(params, batch, self.cfg, remat=remat,
                                       attn_blocks=attn_blocks)
        else:
            logits, _, aux = m.forward(params, batch["tokens"], self.cfg,
                                       remat=remat, attn_blocks=attn_blocks,
                                       frontend_embeds=self._fe(batch))
        return logits, aux

    def prefill(self, params, batch, *, max_len: int, attn_blocks=(512, 512)):
        m = _mod(self.cfg)
        if self.cfg.family == "encdec":
            return m.prefill(params, batch, self.cfg, max_len=max_len,
                             attn_blocks=attn_blocks)
        return m.prefill(params, batch["tokens"], self.cfg, max_len=max_len,
                         attn_blocks=attn_blocks, frontend_embeds=self._fe(batch))

    def decode_step(self, params, cache, tokens):
        return _mod(self.cfg).decode_step(params, cache, tokens, self.cfg)

    def decode_step_paged(self, params, cache, tokens):
        if not supports_paged(self.cfg):
            raise NotImplementedError(
                f"paged KV decode unsupported for {self.cfg.name} "
                f"(family={self.cfg.family})")
        return _mod(self.cfg).decode_step_paged(params, cache, tokens,
                                                self.cfg)

    # ---- specs -------------------------------------------------------
    def cache_specs(self, batch: int, max_len: int, dtype=jnp.bfloat16, **kw):
        return _mod(self.cfg).cache_specs(self.cfg, batch, max_len, dtype, **kw)

    def paged_cache_specs(self, batch: int, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16, max_len=None):
        if not supports_paged(self.cfg):
            raise NotImplementedError(
                f"paged KV cache unsupported for {self.cfg.name}")
        return _mod(self.cfg).paged_cache_specs(
            self.cfg, batch, num_pages, page_size, dtype, max_len=max_len)

    def cache_logical_axes(self):
        return _mod(self.cfg).cache_logical_axes(self.cfg)


def supports_paged(cfg) -> bool:
    """Paged KV decode covers plain causal attention: dense/GQA (incl. MoE
    FFNs and VLM backbones) without sliding windows. SSM/hybrid state and
    ring-packed window caches stay on the dense slab path."""
    return (cfg.family in ("dense", "moe", "vlm")
            and cfg.sliding_window == 0
            and not cfg.local_global_ratio)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(cfg, dtype, key):
    params, _ = split_boxes(_mod(cfg).init(key, cfg))
    return Model(cfg).cast(params, dtype)


def build_model(cfg) -> Model:
    return Model(cfg)
