"""Dense grouped-query-attention decoders, shared by `llama.py` and
`chatglm.py`, which differ only in their configuration's key names and
rotary layout.

The serving program's tree: `embed`, `lm_head`, `final_norm` and one
stacked segment `seg0` of all layers. The reference follows the published
description of these families:

- pre-norm RMSNorm (`reference._rms`), eps from the configuration;
- grouped-query attention with scale 1/sqrt(head_dim); rotary embeddings
  on the whole head in two halves (Llama/Yi), or on the first half of the
  head in adjacent pairs (ChatGLM), with the configuration's base;
- a SwiGLU feed-forward (`wi` holds the gate and then the up projection);
- a final RMSNorm and an untied output head.

Layers run one at a time (`lax.scan` over the stacked weights, each cast to
float32 inside the step) and attention runs in blocks of queries, so a
whole 6B model and a 4096-token sequence fit beside the bfloat16 weights.

Costs follow the algorithm, as `costs.py` says: matrix products at 2
operations per multiply-add, causal attention over the triangle in
prefill and over the live context in decode, one read of each weight and
each live cached key and value per step, bfloat16 bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..costs import BF16
from ..reference import Q_BLOCK, _mm, _rms


@dataclasses.dataclass(frozen=True)
class Dims:
    model_type: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    vocab: int
    eps: float
    rope_theta: float
    rope_fraction: float      # share of each head's dims that rotates
    rope_interleaved: bool    # rotate adjacent pairs, not the two halves

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over all layers."""
        return self.layers * 2 * self.kv_heads * self.hd * 2


def check_program(m: Dims, pcfg) -> None:
    """The program's architecture entry must have the file's widths."""
    want = dict(num_layers=m.layers, d_model=m.d, num_heads=m.heads,
                num_kv_heads=m.kv_heads, head_dim=m.hd, d_ff=m.ff,
                vocab_size=m.vocab, rope_theta=m.rope_theta,
                rope_fraction=m.rope_fraction,
                rope_interleaved=m.rope_interleaved, norm_eps=m.eps)
    bad = {k: (getattr(pcfg, k), v) for k, v in want.items()
           if getattr(pcfg, k) != v}
    if bad or pcfg.family != "dense" or pcfg.norm_type != "rmsnorm":
        raise SystemExit(f"program config {pcfg.name} differs from the "
                         f"benchmark's configuration: {bad}")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_shapes(m: Dims):
    """The serving program's parameter tree, as shapes."""
    L, d, H, Hkv, hd, ff, V = (m.layers, m.d, m.heads, m.kv_heads, m.hd,
                               m.ff, m.vocab)
    return {
        "embed": (V, d),
        "final_norm": {"w": (d,)},
        "lm_head": (d, V),
        "seg0": {
            "ln1": {"w": (L, d)},
            "attn": {"wq": (L, d, H, hd), "wk": (L, d, Hkv, hd),
                     "wv": (L, d, Hkv, hd), "wo": (L, H, hd, d)},
            "ln2": {"w": (L, d)},
            "ffn": {"wi": (L, d, 2 * ff), "wo": (L, ff, d)},
        },
    }


_FAN_IN = {
    "lm_head": lambda s: s[0],
    "seg0/attn/wq": lambda s: s[1], "seg0/attn/wk": lambda s: s[1],
    "seg0/attn/wv": lambda s: s[1], "seg0/attn/wo": lambda s: s[1] * s[2],
    "seg0/ffn/wi": lambda s: s[1], "seg0/ffn/wo": lambda s: s[1],
}


def fan_in(path: str, shape) -> int:
    """Fan-in of each matrix, from its shape in `param_shapes`."""
    return _FAN_IN[path](shape)


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

def _rope(x, m: Dims):
    """x: (S, heads, hd); position of row i is i."""
    S, _, hd = x.shape
    rot = int(hd * m.rope_fraction)
    rot -= rot % 2
    inv = 1.0 / (m.rope_theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                  / rot))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr, xp = x[..., :rot], x[..., rot:]
    if m.rope_interleaved:
        x0, x1 = xr[..., 0::2], xr[..., 1::2]
        xr = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                       axis=-1).reshape(xr.shape)
    else:
        x0, x1 = xr[..., :rot // 2], xr[..., rot // 2:]
        xr = jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return jnp.concatenate([xr, xp], axis=-1)


def _attention(q, k, v, m: Dims, quant: bool):
    """Causal GQA over one sequence. q: (S, H, hd); k, v: (S, Hkv, hd)."""
    S = q.shape[0]
    G = m.heads // m.kv_heads
    qg = q.reshape(S, m.kv_heads, G, m.hd)
    scale = 1.0 / np.sqrt(m.hd)
    cols = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, 0)
        s = _mm("qkgd,skd->kgqs", qb, k, quant, -1, -1) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,skd->qkgd", p, v, quant, -1, 0)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return o.reshape(S, m.heads, m.hd)


def logits(m: Dims, params, tokens, quant: bool):
    """Float32 logits of every row of one padded sequence."""
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        a = f32["attn"]
        h = _rms(x, f32["ln1"]["w"], m.eps)
        q = _rope(_mm("sd,dhk->shk", h, a["wq"], quant, -1, 0), m)
        k = _rope(_mm("sd,dhk->shk", h, a["wk"], quant, -1, 0), m)
        v = _mm("sd,dhk->shk", h, a["wv"], quant, -1, 0)
        o = _attention(q, k, v, m, quant)
        x = x + _mm("shk,hkd->sd", o, a["wo"], quant, (-2, -1), (0, 1))
        h = _rms(x, f32["ln2"]["w"], m.eps)
        gu = _mm("sd,df->sf", h, f32["ffn"]["wi"], quant, -1, 0)
        g, u = jnp.split(gu, 2, axis=-1)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, f32["ffn"]["wo"],
                    quant, -1, 0)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["seg0"])
    x = _rms(x, params["final_norm"]["w"], m.eps)
    return _mm("sd,dv->sv", x, params["lm_head"].astype(jnp.float32),
               quant, -1, 0)


# ---------------------------------------------------------------------------
# costs
# ---------------------------------------------------------------------------

def _layer_matmul_params(m: Dims) -> int:
    return (m.d * (m.heads + 2 * m.kv_heads) * m.hd + m.heads * m.hd * m.d
            + 3 * m.d * m.ff)


def weight_bytes(m: Dims) -> int:
    """Every weight once: layers, final norm and output head (the
    embedding table is read one row per token, counted per step)."""
    return BF16 * (m.layers * (_layer_matmul_params(m) + 2 * m.d)
                   + m.d + m.d * m.vocab)


def prefill_flops(m: Dims, n_tokens: int) -> float:
    """One prompt of `n_tokens`: every layer's projections and feed-forward
    per token, causal attention over the triangle, and the output head on
    the last position only (the one the first token needs)."""
    S = n_tokens
    dense = 2.0 * S * _layer_matmul_params(m)
    attn = 2.0 * 2.0 * m.heads * m.hd * S * (S + 1) / 2
    return m.layers * (dense + attn) + 2.0 * m.d * m.vocab


def attention_decode_flops(m: Dims, ctx: Sequence[int]) -> float:
    """`paged_decode` over all layers: QK and PV over each sequence's
    attended positions `ctx` (its cached tokens plus the new one)."""
    return m.layers * 4.0 * m.heads * m.hd * float(sum(ctx))


def attention_decode_bytes(m: Dims, ctx: Sequence[int]) -> float:
    """`paged_decode` over all layers: the live keys and values of each
    sequence, its query and its output."""
    kv = float(sum(ctx)) * 2 * m.kv_heads * m.hd * BF16
    qo = len(ctx) * 2 * m.heads * m.hd * BF16
    return m.layers * (kv + qo)


def decode_step_flops(m: Dims, ctx: Sequence[int]) -> float:
    """One decode step of len(ctx) sequences."""
    per_tok = 2.0 * (m.layers * _layer_matmul_params(m) + m.d * m.vocab)
    return len(ctx) * per_tok + attention_decode_flops(m, ctx)


def decode_step_bytes(m: Dims, ctx: Sequence[int]) -> float:
    """One decode step of len(ctx) sequences: the weights, one embedding
    row, the live cache read and the new token's keys and values written
    per sequence, and the logits written."""
    B = len(ctx)
    per_seq = BF16 * (m.d + m.vocab) + m.kv_bytes_per_token
    return (weight_bytes(m) + B * per_seq
            + float(sum(ctx)) * m.kv_bytes_per_token)
