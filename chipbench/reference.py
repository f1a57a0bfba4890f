"""Plain reference forward pass of the served models, and its fp8 control.

A decoder in straightforward `jax.numpy`, computed in float32 with every
matrix product at `Precision.HIGHEST`, over one whole sequence at a time
with a causal mask: no kernel, no cache, no batching. The forward pass of
each architecture is its module's `logits` under `archs/`, which follows
the model's published description and builds on `_mm` and `_rms` here;
what this module adds is common to all: padding, the rows that predict the
served tokens, and the gaps. The weights are random, made by
`model.make_params`.

The control (`quant=True`) is the same pass with every matrix product's
operands rounded to float8 e4m3, per row of the activations and per
output column of the weights, and float32 accumulation: the precision
below the configuration's bfloat16.

Sequences are padded to a multiple of `PAD`, and an architecture computes
attention in blocks of `Q_BLOCK` queries, so that a whole 6B model and a
4096-token sequence fit beside the bfloat16 weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import archs

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
    """RMSNorm with the gain stored as its offset from 1, as the program
    stores it."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _score(m: archs.Dims, params, tokens, quant: bool, probe_a, probe_b):
    """Per row of a padded sequence: the largest logit, the logits of
    `probe_a[i]` and `probe_b[i]`, and the top token."""
    logits = archs.of(m).logits(m, params, tokens, quant)

    def take(t):
        return jnp.take_along_axis(logits, t[:, None], 1)[:, 0]

    return (jnp.max(logits, -1), take(probe_a), take(probe_b),
            jnp.argmax(logits, -1).astype(jnp.int32))


def score(m: archs.Dims, params, prompt, served, *, quant: bool = False,
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
