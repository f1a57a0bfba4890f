"""Jitted wrapper for paged decode attention."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import paged_decode, paged_insert
from .ref import paged_decode_ref, paged_insert_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("scale", "softcap", "impl"))
def paged_decode_op(q, k_pages, v_pages, block_table, lens, *,
                    scale: float = None, softcap: float = 0.0,
                    impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return paged_decode_ref(q, k_pages, v_pages, block_table, lens,
                                scale=scale, softcap=softcap)
    return paged_decode(q, k_pages, v_pages, block_table, lens,
                        scale=scale, softcap=softcap,
                        interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("impl",))
def paged_insert_op(k_pages, v_pages, k_new, v_new, page_idx, offset, *,
                    impl: str = "auto"):
    """Splice one new token per sequence into the paged pools. The pallas
    path aliases the pools in place (input_output_aliases); the ref path
    relies on XLA's in-place scatter inside the enclosing jit."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return paged_insert_ref(k_pages, v_pages, k_new, v_new,
                                page_idx, offset)
    return paged_insert(k_pages, v_pages, k_new, v_new, page_idx, offset,
                        interpret=(impl == "interpret"))
