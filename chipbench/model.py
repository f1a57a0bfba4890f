"""Model dimensions from a configuration file, and the weights made from a
seed.

A configuration file keeps the published `config.json` keys of its model
at the top level, under the names the model's own family uses, and its
`model_type`. The module of that `model_type` under `archs/` maps them
onto its dimensions object and gives the parameter tree and each matrix's
fan-in; the weights, the reference and the cost functions share that
object.

The weights are made here, by the benchmark, in the tree layout that the
serving program takes (`embed`, `lm_head`, `final_norm` and the stacked
segments `seg<i>` of the layers), on the device, in the type they are
served in, in one jitted call. The reference reads the same arrays.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import archs


def dims(cfg: dict) -> archs.Dims:
    """The model's dimensions from a configuration file."""
    return archs.load(cfg["model_type"]).dims(cfg)


def param_shapes(m: archs.Dims):
    """The serving program's parameter tree, as shapes."""
    return archs.of(m).param_shapes(m)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# Scales keep the residual stream and the logits of order one: matrices
# N(0, 1/fan_in), embeddings N(0, 1), and norm gains 1 + N(0, 0.1^2) (the
# program stores the gain as its offset from 1).
_NORM_SD = 0.1


def _leaf(path: str, shape, key, dtype, fan_in):
    if path.endswith("/w"):
        sd = _NORM_SD
    elif path == "embed":
        sd = 1.0
    else:
        sd = 1.0 / math.sqrt(fan_in(path, shape))

    def one(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * sd).astype(dtype)

    if path.startswith("seg"):
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
def _make(m: archs.Dims, key, dtype_name: str):
    dtype = jnp.dtype(dtype_name)
    flat = list(_flat(param_shapes(m)))
    keys = jax.random.split(key, len(flat))
    fan_in = archs.of(m).fan_in
    return _unflat((p, _leaf(p, shp, k, dtype, fan_in))
                   for (p, shp), k in zip(flat, keys))


def make_params(m: archs.Dims, seed: int, dtype: str = "bfloat16"):
    """Random weights from `seed`, on the default device, in `dtype`."""
    return _make(m, seed_key(seed), dtype)
