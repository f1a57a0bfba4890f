"""Plain reference forward pass of the served models, and its fp8 control.

A decoder-only transformer in straightforward `jax.numpy`, computed in
float32 with every matrix product at `Precision.HIGHEST`, over one whole
sequence at a time with a causal mask: no kernel, no cache, no batching.
It follows the published description of the two families served here:

- pre-norm RMSNorm (the program stores each gain as its offset from 1, so
  the gain is 1 + w), eps from the configuration;
- grouped-query attention with scale 1/sqrt(head_dim); rotary embeddings
  on the whole head in two halves (Llama/Yi), or on the first half of the
  head in adjacent pairs (ChatGLM), with the configuration's base;
- a SwiGLU feed-forward (`wi` holds the gate and then the up projection);
- a final RMSNorm and an untied output head.

Departures from the published models: ChatGLM3's query/key/value biases
are absent (the served program has none; see `departures` in the
configuration). The weights are random, made by `model.make_params`.

The control (`quant=True`) is the same pass with every matrix product's
operands rounded to float8 e4m3, per row of the activations and per
output column of the weights, and float32 accumulation: the precision
below the configuration's bfloat16.

Layers run one at a time (`lax.scan` over the stacked weights, each cast to
float32 inside the step) and attention runs in blocks of queries, so a
whole 6B model and a 4096-token sequence fit beside the bfloat16 weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model import Dims

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 512           # query rows per attention block
PAD = 1024              # sequences are padded to a multiple of this


def _q8(x, axis):
    """Round `x` to float8 e4m3 with one scale per slice along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant, a_axis, b_axis):
    """einsum at HIGHEST; with `quant`, operands rounded to fp8 along
    their contracted axes first."""
    if quant:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


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


@functools.partial(jax.jit, static_argnums=(0, 3))
def _score(m: Dims, params, tokens, quant: bool, probe_a, probe_b):
    """Per row of a padded sequence: the largest logit, the logits of
    `probe_a[i]` and `probe_b[i]`, and the top token."""
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
    logits = _mm("sd,dv->sv", x, params["lm_head"].astype(jnp.float32),
                 quant, -1, 0)

    def take(t):
        return jnp.take_along_axis(logits, t[:, None], 1)[:, 0]

    return (jnp.max(logits, -1), take(probe_a), take(probe_b),
            jnp.argmax(logits, -1).astype(jnp.int32))


def score(m: Dims, params, prompt, served, *, quant: bool = False,
          probe=None):
    """Run one served request through the reference (or, with `quant`,
    through the fp8 control).

    Rows `len(prompt) - 1 ...` of the sequence prompt + served[:-1]
    predict the served tokens. Returns a dict of per-served-token arrays:
    `gap`, the pass's largest logit minus its logit for the served token
    (0 where both put the same token first); `top`, the pass's own first
    token; and, where `probe` (one token id per served token) is given,
    `probe_gap`, the same gap for the probe tokens."""
    seq = list(prompt) + list(served)[:-1]
    S = len(seq)
    S_pad = -(-S // PAD) * PAD
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = seq
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    pa = np.zeros(S_pad, np.int32)
    pb = np.zeros(S_pad, np.int32)
    pa[rows] = served
    if probe is not None:
        pb[rows] = probe
    best, at_a, at_b, top = jax.device_get(_score(
        m, params, jnp.asarray(toks), quant, jnp.asarray(pa),
        jnp.asarray(pb)))
    out = {"gap": best[rows] - at_a[rows], "top": top[rows]}
    if probe is not None:
        out["probe_gap"] = best[rows] - at_b[rows]
    return out
