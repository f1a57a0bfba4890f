"""Compile the main-path kernels and one whole decode step for a TPU v5e.

Nothing runs: the chip's compiler is installed here and compiles for a
described `v5e:2x2` topology that is not attached. That catches what the
Pallas interpreter cannot (tiling rules, unsupported lowerings, VMEM and
HBM limits) at yi-6b widths in bf16, with the block sizes the serving
cluster uses. The topology is described inside a fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_decode import ops as decode_ops
from repro.kernels.paged_decode.kernel import paged_decode, paged_insert
from repro.kernels.prefix_prefill import ops as prefill_ops
from repro.kernels.prefix_prefill.kernel import prefix_prefill
from repro.models.api import build_model

HBM_BYTES = 16 * 2 ** 30            # one v5e
CFG = get_config("yi-6b")
H, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
PAGE, NUM_PAGES, B = 16, 1024, 8
BLOCKS = (64, 64)                   # ServingCluster's attn_blocks
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=BF):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("batch,num_pages,kv_heads", [
    (12, 1150, 4),                  # yi-6b, as the longdoc cell serves it
    (96, 3000, 2),                  # chatglm3-6b, as the chat cell does
], ids=["yi-6b", "chatglm3-6b"])
def test_paged_decode_compiles(spec, batch, num_pages, kv_heads):
    """The block walk at the benchmark cells' pools and slots, 256 table
    entries each; the pools reach the kernel as they are, no copy."""
    pool = spec((num_pages, PAGE, kv_heads, HD))
    c = _compile(paged_decode, spec((batch, H, HD)), pool, pool,
                 spec((batch, 256), jnp.int32), spec((batch,), jnp.int32))
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert " copy(" not in text and "transpose(" not in text


def test_paged_insert_compiles(spec):
    pool = spec((NUM_PAGES, PAGE, HKV, HD))
    new = spec((B, HKV, HD))
    idx = spec((B,), jnp.int32)
    c = _compile(paged_insert, pool, pool, new, new, idx, idx)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dbuf", [False, True])
def test_prefix_prefill_compiles(spec, dbuf):
    """A 256-token chunk over 32 prefix pages, as chunked prefill and a
    prefix-cache hit dispatch it."""
    Sq, npp = 256, 32
    pool = spec((NUM_PAGES, PAGE, HKV, HD))
    suf = spec((1, HKV, Sq, HD))
    c = _compile(
        lambda *a: prefix_prefill(*a, block_q=BLOCKS[0], block_kv=BLOCKS[1],
                                  dbuf=dbuf),
        spec((1, H, Sq, HD)), suf, suf, pool, pool,
        spec((1, npp), jnp.int32), spec((1,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_yi6b_paged_decode_step_compiles(spec, monkeypatch):
    """One whole yi-6b decode step in bf16 (batch 8, 1280-token slots) on
    the kernel path fits one v5e's HBM."""
    model = build_model(CFG)
    for mod in (decode_ops, prefill_ops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    ops = (decode_ops.paged_decode_op, decode_ops.paged_insert_op)
    for op in ops:
        op.clear_cache()
    try:
        params = jax.eval_shape(lambda k: model.init(k, BF),
                                jax.random.PRNGKey(0))
        max_len = 1280
        cache = model.paged_cache_specs(B, B * max_len // PAGE + 1, PAGE,
                                        BF, max_len=max_len)
        place = lambda t: jax.tree.map(lambda s: spec(s.shape, s.dtype), t)
        c = jax.jit(model.decode_step_paged, donate_argnums=(1,)).lower(
            place(params), place(cache), spec((B,), jnp.int32)).compile()
    finally:
        for op in ops:
            op.clear_cache()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < HBM_BYTES, total
