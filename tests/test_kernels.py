"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.flash_prefill.ref import flash_prefill_ref
from repro.kernels.paged_decode.kernel import (pages_per_block, paged_decode,
                                               paged_insert)
from repro.kernels.paged_decode.ref import paged_decode_ref, paged_insert_ref
from repro.kernels.prefix_prefill.kernel import prefix_prefill
from repro.kernels.prefix_prefill.ref import prefix_prefill_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref, ssd_scan_sequential

TOLS = {jnp.float32: dict(atol=5e-5, rtol=5e-5),
        jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, H, Hkv, Sq, Skv, hd, causal, window)
    (2, 4, 2, 128, 128, 64, True, 0),
    (1, 8, 1, 96, 256, 64, True, 0),
    (2, 4, 4, 128, 128, 128, False, 0),
    (1, 4, 2, 256, 256, 64, True, 64),
    (1, 2, 2, 64, 64, 32, True, 0),
])
def test_flash_prefill_sweep(shape, dtype):
    B, H, Hkv, Sq, Skv, hd, causal, window = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, hd), jnp.float32).astype(dtype)
    ref = flash_prefill_ref(q, k, v, causal=causal, window=window)
    out = flash_prefill(q, k, v, causal=causal, window=window,
                        block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, H, Hkv, hd, num_pages, page, pages_per_seq)
    (2, 8, 2, 64, 16, 16, 4),
    (3, 4, 4, 128, 32, 8, 8),
    (1, 16, 1, 64, 8, 32, 2),
])
def test_paged_decode_sweep(shape, dtype):
    B, H, Hkv, hd, pages, page, pps = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, page, Hkv, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, page, Hkv, hd), jnp.float32).astype(dtype)
    table = jax.random.permutation(ks[0], pages)[:B * pps].reshape(B, pps)
    table = table.astype(jnp.int32)
    lens = jnp.array([1 + (11 * i + 7) % (pps * page) for i in range(B)],
                     jnp.int32)
    ref = paged_decode_ref(q, kp, vp, table, lens)
    out = paged_decode(q, kp, vp, table, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


def test_paged_decode_full_page_boundary():
    """lens exactly on page boundaries."""
    B, H, Hkv, hd, pages, page, pps = 2, 4, 2, 64, 8, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (pages, page, Hkv, hd))
    vp = jax.random.normal(ks[2], (pages, page, Hkv, hd))
    table = jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
    lens = jnp.array([page, pps * page], jnp.int32)
    ref = paged_decode_ref(q, kp, vp, table, lens)
    out = paged_decode(q, kp, vp, table, lens, interpret=True)
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, H, Hkv, Sq, hd, num_pages, page, npp)
    (2, 4, 2, 64, 64, 16, 16, 3),      # GQA 2:1
    (1, 8, 1, 96, 64, 32, 8, 6),       # MQA, ragged q blocks
    (2, 4, 4, 128, 128, 16, 16, 2),    # MHA, hd 128
    (1, 2, 2, 32, 32, 8, 32, 1),       # single prefix page
])
def test_prefix_prefill_sweep(shape, dtype):
    B, H, Hkv, Sq, hd, pages, page, npp = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sq, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sq, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[3], (pages, page, Hkv, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[4], (pages, page, Hkv, hd), jnp.float32).astype(dtype)
    table = jax.random.permutation(ks[0], pages)[:B * npp].reshape(B, npp)
    table = table.astype(jnp.int32)
    # ragged prefix lengths (incl. a partially-filled last page)
    plens = jnp.array([1 + (7 * i + 5) % (npp * page) for i in range(B)],
                      jnp.int32)
    ref = prefix_prefill_ref(q, k, v, kp, vp, table, plens)
    out = prefix_prefill(q, k, v, kp, vp, table, plens,
                         block_q=32, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


def test_prefix_prefill_ragged_suffix_and_full_pages():
    """suffix_lens masking + prefix_lens exactly on page boundaries + a
    trash-padded table slot beyond the live prefix."""
    B, H, Hkv, Sq, hd, pages, page, npp = 2, 4, 2, 48, 64, 12, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (B, H, Sq, hd))
    k = jax.random.normal(ks[1], (B, Hkv, Sq, hd))
    v = jax.random.normal(ks[2], (B, Hkv, Sq, hd))
    kp = jax.random.normal(ks[3], (pages, page, Hkv, hd))
    vp = jax.random.normal(ks[4], (pages, page, Hkv, hd))
    table = jnp.arange(B * npp, dtype=jnp.int32).reshape(B, npp)
    # row 0: full pages; row 1: live prefix ends mid-table (pages beyond
    # plen are trash-padded and must be masked, not attended)
    table = table.at[1, 2:].set(0)
    plens = jnp.array([npp * page, 2 * page], jnp.int32)
    slens = jnp.array([Sq, Sq - 9], jnp.int32)
    ref = prefix_prefill_ref(q, k, v, kp, vp, table, plens, slens)
    out = prefix_prefill(q, k, v, kp, vp, table, plens, slens,
                         block_q=16, block_kv=16, interpret=True)
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-5)


def test_prefix_prefill_matches_flash_with_dense_prefix():
    """Cross-oracle: fused paged-prefix attention == flash attention over
    the dense concat [prefix ++ suffix] with the offset causal mask."""
    B, H, Hkv, Sq, hd, page, npp = 1, 4, 2, 32, 64, 8, 3
    P = npp * page
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, H, Sq, hd))
    k = jax.random.normal(ks[1], (B, Hkv, Sq, hd))
    v = jax.random.normal(ks[2], (B, Hkv, Sq, hd))
    kp = jax.random.normal(ks[3], (npp, page, Hkv, hd))
    vp = jax.random.normal(ks[4], (npp, page, Hkv, hd))
    table = jnp.arange(npp, dtype=jnp.int32)[None]
    plens = jnp.array([P], jnp.int32)
    out = prefix_prefill(q, k, v, kp, vp, table, plens,
                         block_q=16, block_kv=16, interpret=True)
    k_dense = jnp.concatenate(
        [kp.reshape(1, P, Hkv, hd).transpose(0, 2, 1, 3), k], axis=2)
    v_dense = jnp.concatenate(
        [vp.reshape(1, P, Hkv, hd).transpose(0, 2, 1, 3), v], axis=2)
    want = flash_prefill_ref(q, k_dense, v_dense, causal=True)
    np.testing.assert_allclose(out, want, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H,Hkv", [(32, 2), (32, 4)], ids=["G16", "G8"])
def test_paged_decode_walks_only_live_pages(H, Hkv, dtype):
    """The block walk == the oracle on clean pools, at chatglm3's (Hkv 2,
    G 16) and yi-6b's (Hkv 4, G 8) ratios, for ragged lens: 1, a page
    less one, exactly one block, one and a half blocks, the full table,
    and 0, whose row is zero. Every position past a slot's length, the
    rest of its reservation and the trash page 0 hold NaN: nothing there
    is read into the result."""
    hd, page = 128, 16
    P = pages_per_block(page, Hkv, hd, jnp.dtype(dtype).itemsize, 10 ** 6)
    pps = 2 * P + P // 2 + 1
    lens = np.array([1, page - 1, P * page, P * page + P * page // 2 + 3,
                     pps * page, 0], np.int32)
    B = len(lens)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dtype)
    pages = 1 + B * pps
    kp = jax.random.normal(ks[1], (pages, page, Hkv, hd),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, page, Hkv, hd),
                           jnp.float32).astype(dtype)
    table = 1 + np.arange(B * pps, dtype=np.int32).reshape(B, pps)
    table[1, 1:] = 0                    # a reservation ending in trash
    table[5] = 0                        # a free slot
    live = np.zeros((pages, page), bool)
    for b in range(B):
        for t in range(lens[b]):
            live[table[b, t // page], t % page] = True
    dirty = jnp.asarray(live)[:, :, None, None]
    kn = jnp.where(dirty, kp, jnp.nan)
    vn = jnp.where(dirty, vp, jnp.nan)
    table, lens = jnp.asarray(table), jnp.asarray(lens)
    ref = paged_decode_ref(q, kp, vp, table, lens)
    out = np.asarray(paged_decode(q, kn, vn, table, lens, interpret=True),
                     np.float32)
    assert np.isfinite(out).all()
    assert (out[5] == 0).all()
    np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                               **TOLS[dtype])


def test_paged_decode_with_no_live_slot():
    """A batch of free slots walks nothing and returns zeros."""
    q = jnp.ones((3, 8, 128))
    pool = jnp.full((4, 16, 2, 128), jnp.nan)
    out = paged_decode(q, pool, pool, jnp.zeros((3, 4), jnp.int32),
                       jnp.zeros((3,), jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_prefix_prefill_dbuf_parity():
    """Double-buffered paged-prefix loads == oracle, incl. ragged prefix,
    ragged suffix, and a trash-padded table slot."""
    B, H, Hkv, Sq, hd, pages, page, npp = 2, 4, 2, 48, 64, 12, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    q = jax.random.normal(ks[0], (B, H, Sq, hd))
    k = jax.random.normal(ks[1], (B, Hkv, Sq, hd))
    v = jax.random.normal(ks[2], (B, Hkv, Sq, hd))
    kp = jax.random.normal(ks[3], (pages, page, Hkv, hd))
    vp = jax.random.normal(ks[4], (pages, page, Hkv, hd))
    table = jnp.arange(B * npp, dtype=jnp.int32).reshape(B, npp)
    table = table.at[1, 2:].set(0)
    plens = jnp.array([npp * page, 2 * page], jnp.int32)
    slens = jnp.array([Sq, Sq - 9], jnp.int32)
    ref = prefix_prefill_ref(q, k, v, kp, vp, table, plens, slens)
    out = prefix_prefill(q, k, v, kp, vp, table, plens, slens,
                         block_q=16, block_kv=16, dbuf=True, interpret=True)
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_insert_parity(dtype):
    """Kernel splice == the dense .at[pidx, off].set oracle, including a
    duplicate trash-page target (garbage by design, shapes must hold)."""
    B, Hkv, hd, pages, page = 4, 2, 64, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    kp = jax.random.normal(ks[0], (pages, page, Hkv, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[1], (pages, page, Hkv, hd), jnp.float32).astype(dtype)
    kn = jax.random.normal(ks[2], (B, Hkv, hd))
    vn = jax.random.normal(ks[3], (B, Hkv, hd))
    pidx = jnp.array([3, 1, 7, 5], jnp.int32)
    off = jnp.array([0, 7, 15, 3], jnp.int32)
    rk, rv = paged_insert_ref(kp, vp, kn, vn, pidx, off)
    ok, ov = paged_insert(kp, vp, kn, vn, pidx, off, interpret=True)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
    # untouched pages bit-identical to the originals
    untouched = [p for p in range(pages) if p not in set(pidx.tolist())]
    np.testing.assert_array_equal(np.asarray(ok)[untouched],
                                  np.asarray(kp)[untouched])


@pytest.mark.parametrize("shape", [
    # (b, S, nh, hd, G, N, chunk)
    (2, 64, 4, 8, 2, 16, 16),
    (1, 128, 2, 64, 1, 128, 32),
    (2, 96, 4, 16, 4, 32, 32),
])
def test_ssd_scan_sweep(shape):
    b, S, nh, hd, G, N, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, S, G, N))
    C = jax.random.normal(ks[4], (b, S, G, N))
    D = jax.random.normal(ks[0], (nh,))
    y_seq, h_seq = ssd_scan_sequential(x, dt, A, B, C, D)
    y, h = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(y, y_seq, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(h, h_seq, atol=3e-4, rtol=3e-4)
    # kernel also matches the model-side chunked reference
    y_ref, h_ref = ssd_scan_ref(x, dt, A, B, C, D, chunk=chunk)
    np.testing.assert_allclose(y, y_ref, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(h, h_ref, atol=3e-4, rtol=3e-4)


def test_ops_wrappers_dispatch_ref_on_cpu():
    from repro.kernels.flash_prefill.ops import flash_prefill_op
    from repro.kernels.paged_decode.ops import paged_decode_op
    from repro.kernels.ssd_scan.ops import ssd_scan_op
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    out = flash_prefill_op(q, k, v)           # auto -> ref on CPU
    assert out.shape == q.shape
    qd = jax.random.normal(ks[0], (1, 4, 16))
    kp = jax.random.normal(ks[1], (4, 8, 2, 16))
    vp = jax.random.normal(ks[2], (4, 8, 2, 16))
    table = jnp.zeros((1, 2), jnp.int32)
    out = paged_decode_op(qd, kp, vp, table, jnp.array([5], jnp.int32))
    assert out.shape == (1, 4, 16)
    x = jax.random.normal(ks[0], (1, 32, 2, 8))
    dt = jnp.ones((1, 32, 2)) * 0.1
    y, h = ssd_scan_op(x, dt, -jnp.ones((2,)), jax.random.normal(ks[1], (1, 32, 1, 8)),
                       jax.random.normal(ks[2], (1, 32, 1, 8)), jnp.ones((2,)),
                       chunk=16)
    assert y.shape == x.shape
