"""Model dimensions from a configuration file, and the weights made from a
seed.

A configuration file keeps the published `config.json` keys of its model
at the top level, under the names the model's own family uses. `dims`
maps them onto one set of names that the weights, the reference and the
cost functions share.

The weights are made here, by the benchmark, in the tree layout that the
serving program takes (`embed`, `lm_head`, `final_norm` and one stacked
segment `seg0` of all layers), on the device, in the type they are served
in, in one jitted call. The reference reads the same arrays.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# published config.json key names per model family
_KEYS = {
    "llama": dict(layers="num_hidden_layers", d="hidden_size",
                  heads="num_attention_heads", kv_heads="num_key_value_heads",
                  ff="intermediate_size", vocab="vocab_size",
                  eps="rms_norm_eps", theta="rope_theta"),
    "chatglm": dict(layers="num_layers", d="hidden_size",
                    heads="num_attention_heads",
                    kv_heads="multi_query_group_num", hd="kv_channels",
                    ff="ffn_hidden_size", vocab="padded_vocab_size",
                    eps="layernorm_epsilon"),
}


@dataclasses.dataclass(frozen=True)
class Dims:
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

    @property
    def layer_params(self) -> int:
        d, h, kv, hd, ff = self.d, self.heads, self.kv_heads, self.hd, self.ff
        return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff + 2 * d


def dims(cfg: dict) -> Dims:
    """The model's dimensions from a configuration file."""
    fam = cfg["model_type"]
    k = _KEYS[fam]
    assumed = cfg.get("assumed", {})
    heads = int(cfg[k["heads"]])
    d = int(cfg[k["d"]])
    if fam == "chatglm":
        # ChatGLM rotates the first half of each head (rotary_dim =
        # kv_channels // 2) in adjacent pairs, with base 10000
        hd = int(cfg[k["hd"]])
        theta = float(assumed["rope_theta"])
        fraction, interleaved = 0.5, True
    else:
        hd = int(cfg.get("head_dim", assumed.get("head_dim", d // heads)))
        theta = float(cfg[k["theta"]])
        fraction, interleaved = 1.0, False
    return Dims(layers=int(cfg[k["layers"]]), d=d, heads=heads,
                kv_heads=int(cfg[k["kv_heads"]]), hd=hd,
                ff=int(cfg[k["ff"]]), vocab=int(cfg[k["vocab"]]),
                eps=float(cfg[k["eps"]]), rope_theta=theta,
                rope_fraction=fraction, rope_interleaved=interleaved)


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


def seed_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# Scales keep the residual stream and the logits of order one: matrices
# N(0, 1/fan_in), embeddings N(0, 1), and norm gains 1 + N(0, 0.1^2) (the
# program stores the gain as its offset from 1).
_NORM_SD = 0.1


# fan-in of each matrix, from its shape in `param_shapes`
_FAN_IN = {
    "lm_head": lambda s: s[0],
    "seg0/attn/wq": lambda s: s[1], "seg0/attn/wk": lambda s: s[1],
    "seg0/attn/wv": lambda s: s[1], "seg0/attn/wo": lambda s: s[1] * s[2],
    "seg0/ffn/wi": lambda s: s[1], "seg0/ffn/wo": lambda s: s[1],
}


def _leaf(path: str, shape, key, dtype):
    if path.endswith("/w"):
        sd = _NORM_SD
    elif path == "embed":
        sd = 1.0
    else:
        sd = 1.0 / math.sqrt(_FAN_IN[path](shape))

    def one(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * sd).astype(dtype)

    if path.startswith("seg0/"):
        # one layer at a time, so no float32 copy of a stack is ever live
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: one(k, shape[1:]), keys)
    return one(key, shape)


def _flat(tree, prefix=""):
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _flat(v, p + "/")
        else:
            yield p, v


def _unflat(items):
    out: dict = {}
    for path, v in items:
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(m: Dims, key, dtype_name: str):
    dtype = jnp.dtype(dtype_name)
    flat = list(_flat(param_shapes(m)))
    keys = jax.random.split(key, len(flat))
    return _unflat((p, _leaf(p, shp, k, dtype))
                   for (p, shp), k in zip(flat, keys))


def make_params(m: Dims, seed: int, dtype: str = "bfloat16"):
    """Random weights from `seed`, on the default device, in `dtype`."""
    return _make(m, seed_key(seed), dtype)
