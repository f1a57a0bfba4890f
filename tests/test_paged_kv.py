"""Paged KV-cache runtime: block-table splice semantics, paged-vs-dense
token equality, and pull-based page backpressure on both drivers."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.core.kv_transfer import kv_bytes
from repro.core.latency_model import LatencyModel, Parallelism
from repro.core.simulator import InstanceConfig, simulate_disaggregated
from repro.core.workload import Request
from repro.models.api import build_model, supports_paged
from repro.serving.cluster import DisaggCluster
from repro.serving.engine import Engine, Sequence
from repro.serving.kv_cache import KVCacheManager, TRASH_PAGE

CFG = get_config("yi-6b-smoke")


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


# ---------------- KVCacheManager ------------------------------------------

def test_kv_cache_manager_alloc_free():
    kv = KVCacheManager(9, 16, max_len=64)       # 8 usable pages
    assert kv.free_pages == 8
    assert kv.pages_for(1) == 1 and kv.pages_for(16) == 1
    assert kv.pages_for(17) == 2
    assert kv.pages_for(10 ** 6) == 4            # clamped to max_len
    a = kv.alloc(0, 33)                          # 3 pages
    assert a == [1, 2, 3] and kv.free_pages == 5
    assert TRASH_PAGE not in a
    b = kv.alloc(1, 64)                          # 4 pages
    assert kv.free_pages == 1
    assert not kv.can_admit(20)                  # needs 2, only 1 free
    assert kv.can_admit(10)
    assert kv.free(0) == 3
    assert kv.free_pages == 4
    assert kv.padded_table(1) == b + [TRASH_PAGE] * 0
    kv2 = KVCacheManager(9, 16, max_len=64)
    t = kv2.alloc(5, 20)
    assert kv2.padded_table(5) == t + [TRASH_PAGE] * 2


def test_engine_paged_gating():
    assert supports_paged(CFG)
    assert not supports_paged(get_config("mamba2-2.7b-smoke"))
    assert not supports_paged(get_config("gemma3-1b-smoke"))  # SWA/ring


# ---------------- block-table splice --------------------------------------

def _prefill_into(eng, seq):
    first, blob, _ = eng.prefill_request(seq)
    seq.tokens.append(first)
    seq.produced += 1
    eng.insert_kv(seq, blob)
    return blob


def test_insert_kv_is_block_table_splice(params):
    """Inserting a prefill writes exactly the sequence's pages: earlier
    residents' pages are untouched and unallocated pages stay zero — no
    full max_batch x max_len cache rewrite."""
    rng = np.random.default_rng(0)
    eng = Engine(CFG, params, max_batch=4, max_len=64, page_size=16)
    assert eng.paged
    ps = eng._kv.page_size
    segs = [k for k in eng._cache if k.startswith("seg")]

    sA = Sequence(0, rng.integers(1, CFG.vocab_size, 20).tolist(), 8)
    blobA = _prefill_into(eng, sA)
    tabA = list(eng._kv.block_table(0))
    n_spliceA = -(-20 // ps)
    # spliced pages hold the blob's chunks verbatim
    cacheA, n_tok = blobA
    assert n_tok == 20
    for seg in segs:
        src = np.asarray(cacheA[seg]["k"][:, 0])          # (n, bucket, Hkv, hd)
        for j in range(n_spliceA):
            page = np.asarray(eng._cache[seg]["k"][:, tabA[j]])
            want = src[:, j * ps:(j + 1) * ps]
            np.testing.assert_array_equal(page, want)
    snapA = {seg: np.asarray(eng._cache[seg]["k"][:, tabA]) for seg in segs}

    sB = Sequence(1, rng.integers(1, CFG.vocab_size, 25).tolist(), 8)
    _prefill_into(eng, sB)
    tabB = list(eng._kv.block_table(1))
    assert not set(tabA) & set(tabB)
    for seg in segs:
        # A's pages unchanged by B's insert (splice, not rewrite)
        np.testing.assert_array_equal(
            np.asarray(eng._cache[seg]["k"][:, tabA]), snapA[seg])
        # pages owned by nobody (incl. the trash page) still zero
        owned = set(tabA) | set(tabB)
        idle = [p for p in range(eng._kv.num_pages) if p not in owned]
        assert not np.asarray(eng._cache[seg]["k"][:, idle]).any()
    # block table rows point at the allocated pages, padded with trash
    rowB = np.asarray(eng._cache["block_tables"][sB.slot])
    assert list(rowB[:len(tabB)]) == tabB
    assert (rowB[len(tabB):] == TRASH_PAGE).all()


# ---------------- the decode kernel's page walk ---------------------------

def _decode_run(params, release_at=None, steps=6):
    """Three residents decoded together; with `release_at`, the middle one
    is released after that many steps. Returns the engine, the sequences,
    and for each step the `pages_walked` added and the lens the step hands
    `paged_decode_op`."""
    from repro.kernels.paged_decode import ops
    eng = Engine(CFG, params, max_batch=3, max_len=64, page_size=16)
    rng = np.random.default_rng(3)
    seqs = [Sequence(i, rng.integers(1, CFG.vocab_size, n).tolist(), 40)
            for i, n in enumerate((9, 16, 31))]
    for s in seqs:
        _prefill_into(eng, s)
    seen = []
    orig = ops.paged_decode_op

    def spy(q, k, v, table, lens, **kw):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), lens)
        return orig(q, k, v, table, lens, **kw)

    walked = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "paged_decode_op", spy)
        live = list(seqs)
        for step in range(steps):
            if step == release_at:
                eng.release(seqs[1])
                live.remove(seqs[1])
            before = eng.stats()["pages_walked"]
            seen.clear()
            eng.decode_step(live)
            walked.append((eng.stats()["pages_walked"] - before, seen[0]))
    return eng, seqs, walked


def test_pages_walked_is_the_kernels_walk(params):
    """`pages_walked` adds, per decode step, each live slot's
    `ceil((pos + 1) / page_size)`: the pages the kernel walks for the lens
    the step hands it. A released slot hands it 0, so it walks nothing;
    `pages_table` is the whole table per step."""
    eng, seqs, walked = _decode_run(params, release_at=3)
    ps = 16
    for step, (added, lens) in enumerate(walked):
        assert added == sum(-(-int(n) // ps) for n in lens), step
    assert walked[2][1][1] > 0 and walked[3][1][1] == 0   # the middle slot
    # the third slot crosses a page boundary: 32 -> 33 positions
    assert walked[0][0] == 1 + 2 + 2 and walked[1][0] == 1 + 2 + 3
    st = eng.stats()
    assert st["pages_table"] == len(walked) * 3 * 4


def test_release_mid_batch_leaves_batchmates_tokens(params):
    """Releasing the middle slot of a batch changes no other slot's
    decoded tokens."""
    _, kept, _ = _decode_run(params)
    _, cut, _ = _decode_run(params, release_at=2)
    for i in (0, 2):
        assert cut[i].tokens == kept[i].tokens


# ---------------- paged == dense tokens -----------------------------------

def _reqs(n=6):
    return [Request(i, i * 0.01, 10 + (i % 4) * 3, 5) for i in range(n)]


def test_paged_cluster_tokens_match_dense_path(params):
    """Token-for-token identical outputs: paged decode + block-table splice
    vs the pre-refactor dense slot-slab path."""
    dc_paged = DisaggCluster(CFG, params, n_prefill=1, n_decode=1,
                             max_batch=4, max_len=64, lm_tokens=48,
                             paged=True)
    dc_dense = DisaggCluster(CFG, params, n_prefill=1, n_decode=1,
                             max_batch=4, max_len=64, lm_tokens=48,
                             paged=False)
    assert dc_paged.decode[0].paged and not dc_dense.decode[0].paged
    r1 = dc_paged.run(_reqs())
    r2 = dc_dense.run(_reqs())
    assert set(r1) == set(r2)
    for rid in r1:
        assert r1[rid].tokens == r2[rid].tokens, rid


# ---------------- pull-based page backpressure ----------------------------

def test_live_cluster_page_backpressure(params):
    """Decode page pool smaller than the burst: finished prefills must park
    on the prefill side (parked_bytes > 0) and be admitted as pages free."""
    reqs = [Request(i, i * 0.001, 10, 5) for i in range(8)]
    one = kv_bytes(CFG, 10)
    # 16 usable pages / 4 pages per sequence -> 4 resident sequences
    dc = DisaggCluster(CFG, params, n_prefill=1, n_decode=1, max_batch=8,
                       max_len=64, lm_tokens=48, page_size=4,
                       decode_num_pages=17)
    res = dc.run(reqs)
    assert len(res) == 8 and all(r.finish >= 0 for r in res.values())
    assert dc.tx.peak_parked_bytes >= 2 * one      # real queueing occurred
    assert not dc.tx.parked
    # admission was page-bound: the pool really filled up (4 seqs x 4 pages)
    assert dc.decode[0]._kv.peak_used_pages == 16
    assert dc.decode[0]._kv.free_pages == 16       # and drained at the end


def test_simulator_page_backpressure():
    cfg = get_config("yi-6b")
    lm = LatencyModel(cfg, hw.V5E)
    reqs = [Request(i, i * 1e-4, 64, 16) for i in range(12)]
    one = lm.kv_transfer_time(64, 1.0)             # wire bytes of one prompt
    # 5 pages per request, 10-page pool -> 2 resident sequences
    reqs, extras = simulate_disaggregated(
        reqs, lm, InstanceConfig(Parallelism(1, 1), 1),
        InstanceConfig(Parallelism(1, 1), 1),
        page_tokens=16, num_decode_pages=10)
    assert all(r.finish >= 0 for r in reqs)
    assert extras["parked_bytes_peak"] >= 2 * one
    assert extras["breakdown"]["decode_pages"] == 10
