"""Live single-instance inference engine (CPU-runnable, TPU-shaped).

KV storage is *paged* for plain-attention archs (dense/GQA/MoE/VLM without
sliding windows): a pool of fixed-size pages plus per-sequence block
tables, managed by `KVCacheManager`. Prefill caches are spliced in at page
granularity (a block-table update + O(pages) scatter, never a full-cache
rewrite) and decode dispatches through the `kernels/paged_decode` op.
State-carrying archs (SSM, hybrid, encdec, sliding-window ring caches)
fall back to the dense `max_batch x max_len` slot slab.

Prefill runs per-request, right-padded to length buckets (bounded
recompiles) — padding sits *after* the causal horizon and beyond `pos`, so
it is never attended. Archs whose prefill carries running state through
the sequence use exact lengths instead.

Step times are measured and accumulated on a virtual clock so a 1-CPU host
can emulate N concurrent instances honestly (used by the Table-2
simulator-accuracy experiment). Beside that clock, `stats()` counts on the
host's clock the decode step's parts, the gaps between decode steps that
live sequences sit through, and the wait from a sequence's creation to
its first prefill; the host work itself is marked with
`telemetry.host_span`s for the profiler.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.telemetry import host_span
from ..models.api import build_model, supports_paged
from .api import FINISH_LENGTH, FINISH_STOP, SamplingParams
from .kv_cache import KVCacheManager, TRASH_PAGE
from .prefix_cache import RadixPrefixCache

_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


@dataclasses.dataclass
class KVBlob:
    """Migration payload handed to the transfer layer.

    On the fused prefix path `cache` carries only the *suffix* KV: the
    prefix tokens stay in the owning prefill engine's page pool, pinned
    via `prefix_pages` until `Engine.materialize_wire` stitches the wire
    payload (gathering only the pages the decode side actually needs) or
    `release_blob` drops the claim. Unpacks like the legacy
    `(cache, n_tok)` tuple for non-prefix consumers."""
    cache: Any
    n_tok: int
    prefix_tokens: int = 0
    prefix_pages: List[int] = dataclasses.field(default_factory=list)
    owner: Optional["Engine"] = None

    def __iter__(self):
        return iter((self.cache, self.n_tok))

    def __getitem__(self, i):
        return (self.cache, self.n_tok)[i]


def release_blob(blob):
    """Drop a blob's claim on its owner's prefix pages (no-op for legacy
    tuple blobs and for blobs already materialized)."""
    if isinstance(blob, KVBlob) and blob.prefix_pages:
        blob.owner.unpin(blob.prefix_pages)
        blob.prefix_pages = []


@dataclasses.dataclass
class PartialPrefill:
    """Resumable chunked-prefill state: the prompt's full block table is
    reserved at chunk 0; `done` tracks how many prompt tokens have KV
    resident in pool pages (cached prefix included)."""
    table: List[int]
    hit: int                    # cached-prefix tokens (page-aligned)
    done: int                   # resident prompt tokens (>= hit)
    chunks: int = 0             # chunks computed so far


@dataclasses.dataclass
class Sequence:
    rid: int
    tokens: List[int]
    out_len: int
    slot: int = -1
    produced: int = 0
    done: bool = False
    prefilled: int = 0          # resident prompt tokens (chunked prefill)
    prefix_hit: int = 0         # prefill-side cached-prefix tokens
    decode_hit: int = 0         # decode-side shared-prefix tokens
    kv_first: float = 0.0       # when the first layer's KV landed (stream)
    kv_full: float = 0.0        # when the last layer's KV lands (stream)
    sampling: Optional[SamplingParams] = None
    finish_reason: str = FINISH_LENGTH
    # host clock (`perf_counter`) at creation, which is the request's submit
    created: float = dataclasses.field(default_factory=time.perf_counter,
                                       compare=False)
    _rng: Any = None            # lazy, only for temperature > 0

    def append_token(self, tok: int):
        """Append one generated token and apply the stop conditions:
        a SamplingParams.stop id ends generation early (finish_reason
        "stop"); otherwise the out_len budget ends it ("length")."""
        self.tokens.append(tok)
        self.produced += 1
        sp = self.sampling
        if sp is not None and sp.stop and tok in sp.stop:
            self.done = True
            self.finish_reason = FINISH_STOP
        elif self.produced >= self.out_len:
            self.done = True

    def rng(self):
        if self._rng is None:
            sp = self.sampling or SamplingParams()
            self._rng = np.random.default_rng((sp.seed, self.rid))
        return self._rng


class Engine:
    def __init__(self, cfg, params=None, *, max_batch: int = 8,
                 max_len: int = 512, seed: int = 0, attn_blocks=(128, 128),
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 paged: Optional[bool] = None,
                 prefix_cache: bool = False,
                 fused_prefix: Optional[bool] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed))
        # serve at the dtype the params arrive in (the KV cache follows);
        # engines of one cluster share these arrays, never a copy
        self.params = params
        self.dtype = params["embed"].dtype
        self.max_batch = max_batch
        self.max_len = max_len
        self.attn_blocks = attn_blocks
        # exact-length prefill for state-carrying families
        self.exact_len = (cfg.family in ("ssm", "hybrid", "encdec")
                          or cfg.sliding_window > 0)
        self.paged = supports_paged(cfg) if paged is None \
            else (paged and supports_paged(cfg))
        self.clock = 0.0                      # virtual seconds
        self.steps = 0
        self.prefill_tokens = 0               # tokens actually computed
        self.prefix_hit_tokens = 0            # tokens served from the tree
        self.decode_tokens = 0
        self.fused_dispatches = 0             # prefix_prefill kernel calls
        self.chunk_dispatches = 0             # chunked-prefill kernel calls
        # host-clock seconds, cumulative (see `stats`)
        self.dispatch_s = self.wait_s = self.pull_s = 0.0
        self.held_gap_s = 0.0
        self.queue_wait_s = 0.0
        self.queue_waits = 0
        self._held_since: Optional[float] = None
        if self.paged:
            pps = -(-max_len // page_size)
            # default pool: dense-slab-equivalent capacity + trash page 0
            num_pages = num_pages or (max_batch * pps + 1)
            assert num_pages >= pps + 1, \
                "page pool must fit at least one max_len sequence"
            self._kv = KVCacheManager(num_pages, page_size, max_len)
        else:
            self._kv = None
        self.prefix_caching = bool(prefix_cache and self.paged)
        self.prefix_cache = (RadixPrefixCache(page_size, allocator=self._kv)
                             if self.prefix_caching else None)
        # fused paged-prefix prefill (prefix_prefill kernel) is the default
        # on paged archs; the dense-gather fallback stays behind the flag
        # for non-paged archs and for A/B token-equality tests
        self.fused_prefix = (self.prefix_caching if fused_prefix is None
                             else bool(fused_prefix and self.prefix_caching))
        self._cache = self._empty_cache()
        self._partial: Dict[int, PartialPrefill] = {}
        self._slot_free = list(range(max_batch))
        self._prefill_fn: Dict[int, Any] = {}
        self._suffix_fn: Dict[Tuple[int, int], Any] = {}
        self._fused_fn: Dict[Tuple[int, int], Any] = {}
        self._insert_fn: Dict[Tuple[int, int], Any] = {}
        self._gather_fn: Dict[int, Any] = {}
        self._write_fn: Dict[Tuple[int, int], Any] = {}

        if self.paged:
            def _decode(params, cache, tokens):
                return self.model.decode_step_paged(params, cache, tokens)
        else:
            def _decode(params, cache, tokens):
                return self.model.decode_step(params, cache, tokens)
        self._decode_fn = jax.jit(_decode, donate_argnums=(1,))

    def stats(self) -> Dict[str, float]:
        """Pull-collector snapshot for a `MetricsRegistry`: cumulative
        dispatch counters plus page-pool occupancy and prefix-tree state
        when paged/prefix-caching.

        Host-clock seconds, cumulative: `dispatch_s`, `wait_s` and
        `pull_s` split each decode step into enqueueing the step, waiting
        for it (together what the step adds to `clock_s`) and taking its
        tokens to the host; `held_gap_s` is the time from the end of one
        decode step to the start of the next while the engine holds a
        sequence that is not done; `queue_wait_s` over `queue_waits` is
        the mean time from a sequence's creation to its first prefill
        here."""
        out: Dict[str, float] = {
            "clock_s": self.clock, "steps": self.steps,
            "dispatch_s": self.dispatch_s, "wait_s": self.wait_s,
            "pull_s": self.pull_s, "held_gap_s": self.held_gap_s,
            "queue_wait_s": self.queue_wait_s,
            "queue_waits": self.queue_waits,
            "prefill_tokens": self.prefill_tokens,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "decode_tokens": self.decode_tokens,
            "fused_dispatches": self.fused_dispatches,
            "chunk_dispatches": self.chunk_dispatches,
            "slots_free": len(self._slot_free),
            "partial_prefills": len(self._partial),
        }
        if self._kv is not None:
            out.update(self._kv.stats())
        if self.prefix_caching:
            for k, v in self.prefix_cache.metrics().items():
                out[f"prefix.{k}"] = v
        return out

    # ---- cache plumbing ------------------------------------------------
    def _empty_cache(self):
        if self.paged:
            specs = self.model.paged_cache_specs(
                self.max_batch, self._kv.num_pages, self._kv.page_size,
                self.dtype, max_len=self.max_len)
        else:
            specs = self.model.cache_specs(self.max_batch, self.max_len,
                                           self.dtype)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)

    def _get_prefill_fn(self, bucket: int):
        if bucket not in self._prefill_fn:
            # paged engines emit a bucket-sized cache (the migration blob);
            # slab engines pad to max_len so the merge is a pure slot write
            target_len = None if self.paged else self.max_len
            # exact-length families take the final position's logits anyway
            # and their forward() signatures don't accept last_pos
            exact = self.exact_len

            def _pf(params, toks, last_pos):
                mod = self.model
                from ..models import api as _api
                m = _api._mod(mod.cfg)
                kw = {} if exact else {"last_pos": last_pos}
                logits, cache, _ = m.forward(
                    params, toks, mod.cfg, attn_blocks=self.attn_blocks,
                    return_cache=True, max_len=target_len, **kw)
                return logits, cache
            self._prefill_fn[bucket] = jax.jit(_pf)
        return self._prefill_fn[bucket]

    def _get_suffix_prefill_fn(self, bucket: int, n_prefix_pages: int):
        """Dense-gather fallback: prefill only the uncached suffix, with
        queries attending over the gathered prefix KV + themselves (exact
        attention, offset causal mask). `n_prefix_pages` is a power-of-two
        bucket — the gather is trash-padded to it and `plen` masks the
        padding — so the jit cache stays O(log pages), not O(pages)."""
        key = (bucket, n_prefix_pages)
        if key not in self._suffix_fn:
            def _sf(params, toks, prefix_kv, plen, offset, last_pos):
                mod = self.model
                from ..models import api as _api
                m = _api._mod(mod.cfg)
                logits, cache, _ = m.forward(
                    params, toks, mod.cfg, attn_blocks=self.attn_blocks,
                    return_cache=True, max_len=None, prefix_kv=prefix_kv,
                    prefix_len=plen, pos_offset=offset, last_pos=last_pos)
                return logits, cache
            self._suffix_fn[key] = jax.jit(_sf)
        return self._suffix_fn[key]

    def _get_fused_suffix_fn(self, bucket: int, n_prefix_pages: int):
        """Fused paged-prefix prefill: suffix queries attend over the
        prefix straight from the page pools through the `prefix_prefill`
        kernel — no dense prefix KV is ever materialized. `n_prefix_pages`
        is a power-of-two bucket; the block table is trash-padded to it
        and `plen` masks the padding."""
        key = (bucket, n_prefix_pages)
        if key not in self._fused_fn:
            seg_names = [k for k in self._cache if k.startswith("seg")]

            def _ff(params, toks, pools, table, plen, offset, last_pos):
                mod = self.model
                from ..models import api as _api
                m = _api._mod(mod.cfg)
                pages = {name: pools[name] for name in seg_names}
                logits, cache, _ = m.forward(
                    params, toks, mod.cfg, attn_blocks=self.attn_blocks,
                    return_cache=True, max_len=None, prefix_pages=pages,
                    prefix_table=table, prefix_len=plen,
                    pos_offset=offset, last_pos=last_pos)
                return logits, cache
            self._fused_fn[key] = jax.jit(_ff)
        return self._fused_fn[key]

    def _bucket_pages(self, n: int) -> int:
        """Power-of-two page-count bucket (capped at a full sequence) so
        long-running serving compiles O(log pages) suffix/gather variants
        instead of one per distinct prefix length."""
        pps = -(-self.max_len // self._kv.page_size)
        return min(1 << max(n - 1, 0).bit_length(), pps) if n else 0

    def _padded_page_ids(self, pages: List[int], n_bucket: int):
        return jnp.asarray(list(pages) + [TRASH_PAGE] * (n_bucket - len(pages)),
                           jnp.int32)

    def _get_gather_fn(self, n_pages: int):
        """Gather `n_pages` pool pages into a dense (layers, 1, n*ps, Hkv,
        hd) per-segment blob — used both as the suffix prefill's prefix KV
        and as the migration blob shipped to the decode side."""
        if n_pages not in self._gather_fn:
            ps = self._kv.page_size
            seg_names = [k for k in self._cache if k.startswith("seg")]

            def _g(cache, ids):
                out = {}
                for name in seg_names:
                    o = {}
                    for part in ("k", "v"):
                        pool = cache[name][part]   # (L, num_pages, ps, H, hd)
                        sel = pool[:, ids]
                        o[part] = sel.reshape(
                            pool.shape[0], n_pages * ps, *pool.shape[3:]
                        )[:, None]
                    out[name] = o
                return out
            self._gather_fn[n_pages] = jax.jit(_g)
        return self._gather_fn[n_pages]

    def _get_page_write_fn(self, n_splice: int, src_len: int):
        """Scatter a dense (layers, 1, src_len, Hkv, hd) blob into pool
        pages (the prefill-side twin of the insert splice — no block-table
        or pos rows, the prefill engine keeps those host-side)."""
        key = (n_splice, src_len)
        if key not in self._write_fn:
            ps = self._kv.page_size

            def _w(dst, src_segs, splice_ids):
                out = dict(dst)
                span = n_splice * ps
                for name, seg in src_segs.items():
                    k_src, v_src = seg["k"][:, 0], seg["v"][:, 0]
                    if src_len > span:
                        k_src, v_src = k_src[:, :span], v_src[:, :span]
                    elif src_len < span:
                        pad = [(0, 0), (0, span - src_len), (0, 0), (0, 0)]
                        k_src, v_src = jnp.pad(k_src, pad), jnp.pad(v_src, pad)
                    n = k_src.shape[0]
                    shp = (n, n_splice, ps) + k_src.shape[2:]
                    dk, dv = dst[name]["k"], dst[name]["v"]
                    out[name] = {
                        "k": dk.at[:, splice_ids].set(
                            k_src.reshape(shp).astype(dk.dtype)),
                        "v": dv.at[:, splice_ids].set(
                            v_src.reshape(shp).astype(dv.dtype)),
                    }
                return out
            self._write_fn[key] = jax.jit(_w, donate_argnums=(0,))
        return self._write_fn[key]

    # ---- public API -----------------------------------------------------
    def has_slot(self) -> bool:
        return bool(self._slot_free)

    @property
    def free_slots(self) -> int:
        return len(self._slot_free)

    @property
    def free_pages(self) -> int:
        return self._kv.free_pages if self.paged else self.free_slots

    @staticmethod
    def tokens_needed(seq: Sequence) -> int:
        """KV positions for the sequence's full residency: cached prompt +
        every remaining decode write. Invariant across prefill (prefill
        appends one token and bumps `produced` together)."""
        return len(seq.tokens) - 1 + seq.out_len - seq.produced

    def can_admit(self, seq: Sequence, n_shared_pages: int = 0) -> bool:
        """Pull-based admission signal: a free batch slot AND enough free
        KV pages for the whole residency (paper §4.3). Shared prefix pages
        don't need fresh pages, so admission gets easier with reuse. Under
        pressure, cached-but-unreferenced prefix subtrees are reclaimed
        (LRU) before rejecting — retained prefixes must never starve
        admission."""
        if not self._slot_free:
            return False
        if not self.paged:
            return True
        need = self._kv.pages_for(self.tokens_needed(seq)) - n_shared_pages
        if need > self._kv.free_pages and self.prefix_caching:
            self.prefix_cache.evict(need - self._kv.free_pages)
        return self._kv.can_admit(self.tokens_needed(seq), n_shared_pages)

    def reserve_for(self, seq: Sequence, n_shared: int = 0) -> int:
        """Hold the sequence's full residency ahead of its insert
        (streamed chunked admission: the grant lets the wire start while
        prefill is still computing). Returns the page count for
        `unreserve`; the later `insert_kv` allocates the same residency
        the reservation covered."""
        n = max(self._kv.pages_for(self.tokens_needed(seq)) - n_shared, 0)
        self._kv.reserve(n)
        return n

    def unreserve(self, n_pages: int):
        if n_pages:
            self._kv.unreserve(n_pages)

    # ---- prefix-cache surface ------------------------------------------
    def prefix_peek(self, tokens) -> int:
        """Routing probe: longest cached prefix (tokens), no LRU bump."""
        return self.prefix_cache.peek(tokens) if self.prefix_caching else 0

    def pin_prefix(self, tokens) -> Tuple[int, List[int]]:
        """Match + take a reference on the hit pages so they survive until
        `insert_kv` (eviction skips referenced pages). Returns
        (hit_tokens, page_ids); release with `unpin`."""
        if not self.prefix_caching:
            return 0, []
        hit, pages = self.prefix_cache.match(tokens)
        if pages:
            self._kv.acquire(pages)
        return hit, pages

    def unpin(self, pages: List[int]):
        if pages:
            self._kv.release(pages)

    def _bucket(self, n: int) -> int:
        b = next((b for b in _BUCKETS if n <= b), n)
        return min(max(b, n), self.max_len)

    def _forward_chunk(self, padded, ctx_pages: List[int], ctx_len: int,
                       last_pos: int, fused: bool):
        """One bounded prefill pass, shared by the whole-prompt prefix path
        and the chunked state machine: `padded` right-padded tokens attend
        over `ctx_len` tokens resident in `ctx_pages` (empty -> plain
        prefill) plus themselves under the offset causal mask. `last_pos`
        is the last *real* (unpadded) query position — `logits[0, 0]` is
        that row, the one first-token sampling must read. Returns
        (logits, cache, prefix_kv); `prefix_kv` is the dense gather, only
        on the non-fused fallback (callers stitch blobs from it)."""
        bucket = padded.shape[1]
        if not ctx_len:
            fn = self._get_prefill_fn(bucket)
            logits, cache = fn(self.params, jnp.asarray(padded),
                               jnp.asarray(last_pos, jnp.int32))
            return logits, cache, None
        npb = self._bucket_pages(len(ctx_pages))
        if fused:
            # fused hot path: queries attend over the context pages in
            # place (prefix_prefill kernel) — no dense gather at all
            self.fused_dispatches += 1
            table = self._padded_page_ids(ctx_pages, npb)[None]
            pools = {k: v for k, v in self._cache.items()
                     if k.startswith("seg")}
            fn = self._get_fused_suffix_fn(bucket, npb)
            logits, cache = fn(self.params, jnp.asarray(padded), pools,
                               table, jnp.asarray(ctx_len, jnp.int32),
                               jnp.asarray(ctx_len, jnp.int32),
                               jnp.asarray(last_pos, jnp.int32))
            return logits, cache, None
        # flagged fallback: dense gather padded to the page bucket, with
        # the padding masked out by plen
        prefix_kv = self._get_gather_fn(npb)(
            self._cache, self._padded_page_ids(ctx_pages, npb))
        fn = self._get_suffix_prefill_fn(bucket, npb)
        logits, cache = fn(self.params, jnp.asarray(padded), prefix_kv,
                           jnp.asarray(ctx_len, jnp.int32),
                           jnp.asarray(ctx_len, jnp.int32),
                           jnp.asarray(last_pos, jnp.int32))
        return logits, cache, prefix_kv

    def prefill_request(self, seq: Sequence) -> Tuple[int, Any, float]:
        """Run prefill; returns (first_token, kv_blob, step_time).

        With the prefix cache on, only the uncached suffix runs through
        the prefill kernel: the longest page-aligned cached prefix (capped
        so at least one suffix token remains to produce the first output
        logits) is gathered from the page pools and attended as context.
        The new full prompt pages are inserted into the radix tree for
        later requests, and the blob handed to the transfer layer is
        stitched from shared + fresh pages."""
        with host_span("serve.prefill"):
            self._note_queue_wait(seq)
            return self._prefill_request(seq)

    def _prefill_request(self, seq: Sequence) -> Tuple[int, Any, float]:
        toks = np.asarray(seq.tokens, np.int32)
        S = len(toks)
        assert S < self.max_len, (S, self.max_len)
        if self.prefix_caching:
            return self._prefill_with_prefix(seq, toks)
        if self.exact_len:
            bucket = S
        else:
            bucket = self._bucket(S)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :S] = toks                                  # right-pad
        fn = self._get_prefill_fn(bucket)
        t0 = time.perf_counter()
        logits, cache = fn(self.params, jnp.asarray(padded),
                           jnp.asarray(S - 1, jnp.int32))
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        self.clock += dt
        self.steps += 1
        self.prefill_tokens += S
        first = self._sample_first(seq, logits[0, 0])
        return first, (cache, S), dt

    def _prefill_with_prefix(self, seq: Sequence, toks) -> Tuple[int, Any, float]:
        ps = self._kv.page_size
        S = len(toks)
        token_list = [int(t) for t in toks]
        hit, hit_pages = self.prefix_cache.match(token_list)
        # keep >= 1 suffix token: the first output comes from its logits
        hit = min(hit, ((S - 1) // ps) * ps)
        hit_pages = hit_pages[:hit // ps]
        if hit_pages:
            self._kv.acquire(hit_pages)     # pin across compute + eviction
        suffix = toks[hit:]
        Ssuf = len(suffix)
        bucket = self._bucket(Ssuf)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :Ssuf] = suffix
        t0 = time.perf_counter()
        fused = bool(hit and self.fused_prefix)
        logits, cache, prefix_kv = self._forward_chunk(
            padded, hit_pages, hit, Ssuf - 1, fused)
        first = self._sample_first(seq, logits[0, 0])

        # the migration blob: on the fused path it carries only the suffix
        # KV (the prefix stays pinned in the page pool until the transfer
        # layer materializes the wire payload); on the fallback path it is
        # stitched from the already-gathered prefix KV + fresh suffix
        # (never a second gather of the hit pages)
        blob_cache = {}
        for name, seg in cache.items():
            if not name.startswith("seg"):
                continue
            if prefix_kv is not None:
                pk = prefix_kv[name]
                blob_cache[name] = {
                    p: jnp.concatenate([pk[p][:, :, :hit], seg[p]], axis=2)
                    for p in ("k", "v")}
            else:
                blob_cache[name] = {p: seg[p] for p in ("k", "v")}

        # write the fresh suffix pages back into the pools and publish the
        # new full prompt pages in the radix tree for later requests (on
        # pool exhaustion the request simply isn't retained — eviction
        # already ran — and the blob above is still complete)
        total_pages = -(-S // ps)
        fresh_needed = total_pages - len(hit_pages)
        if fresh_needed > self._kv.free_pages:
            self.prefix_cache.evict(fresh_needed - self._kv.free_pages)
        if fresh_needed <= self._kv.free_pages:
            table = self._kv.alloc(seq.rid, S, shared=hit_pages)
            src_len = next(iter(
                c for k, c in cache.items() if k.startswith("seg")
            ))["k"].shape[2]
            self._cache = self._get_page_write_fn(fresh_needed, src_len)(
                self._cache, {k: v for k, v in cache.items()
                              if k.startswith("seg")},
                jnp.asarray(table[len(hit_pages):], jnp.int32))
            self.prefix_cache.insert(token_list[:(S // ps) * ps],
                                     table[:S // ps])
            self._kv.free(seq.rid)          # tree refs keep shared pages
        jax.block_until_ready(blob_cache)
        dt = time.perf_counter() - t0
        self.clock += dt
        self.steps += 1
        self.prefill_tokens += Ssuf
        self.prefix_hit_tokens += hit
        seq.prefix_hit = hit
        if fused:
            # the blob keeps the pin: pages must survive tree eviction
            # until materialize_wire/release_blob
            return first, KVBlob(blob_cache, S, prefix_tokens=hit,
                                 prefix_pages=hit_pages, owner=self), dt
        if hit_pages:
            self._kv.release(hit_pages)     # unpin
        return first, (blob_cache, S), dt

    # ---- chunked prefill (incremental state machine) --------------------
    def has_partial(self, seq: Sequence) -> bool:
        """True for a sequence mid-chunked-prefill on this engine: its
        whole residency is already reserved, so it can always resume (the
        scheduler may drain it past a page-blocked queue head)."""
        return seq.rid in self._partial

    def can_start_chunked(self, seq: Sequence) -> bool:
        """Admission gate for starting a chunked prefill: the *whole*
        prompt's pages are reserved at chunk 0 (minus the cached prefix),
        so later chunks never deadlock on pool space. Already-started
        sequences always resume."""
        if seq.rid in self._partial:
            return True
        S = len(seq.tokens)
        ps = self._kv.page_size
        hit = min(self.prefix_peek(seq.tokens), ((S - 1) // ps) * ps)
        need = -(-S // ps) - hit // ps
        if need > self._kv.free_pages and self.prefix_caching:
            self.prefix_cache.evict(need - self._kv.free_pages)
        return need <= self._kv.free_pages

    def prefill_chunk(self, seq: Sequence,
                      chunk_tokens: int) -> Tuple[bool, Optional[int],
                                                  Any, float, int]:
        """Run (at most) one more chunk of the sequence's prefill.

        Chunk k's queries attend over chunks 0..k-1's KV resident in pool
        pages through the fused `prefix_prefill` kernel (same offset
        causal mask as the prefix-cache path), and the chunk's fresh KV is
        written *directly into pool pages* — no dense per-request blob is
        ever materialized on this path. Non-final chunks are rounded down
        to whole pages (>= 1 page) so the next chunk's page writes never
        clobber a partially-filled page; the final chunk takes the ragged
        tail. Returns ``(done, first_token, blob, dt, new_tokens)`` —
        `first_token`/`blob` are None until the final chunk, where the
        blob is fully page-backed (`prefix_tokens == n_tok`, pages pinned
        until `materialize_wire`/`release_blob`)."""
        with host_span("serve.prefill"):
            self._note_queue_wait(seq)
            return self._prefill_chunk(seq, chunk_tokens)

    def _prefill_chunk(self, seq: Sequence, chunk_tokens: int):
        assert self.paged, "chunked prefill needs the paged runtime"
        toks = np.asarray(seq.tokens, np.int32)
        S = len(toks)
        assert S < self.max_len, (S, self.max_len)
        ps = self._kv.page_size
        t0 = time.perf_counter()
        st = self._partial.get(seq.rid)
        if st is None:
            token_list = [int(t) for t in toks]
            hit, hit_pages = (self.prefix_cache.match(token_list)
                              if self.prefix_caching else (0, []))
            # keep >= 1 suffix token: the first output needs its logits
            hit = min(hit, ((S - 1) // ps) * ps)
            hit_pages = hit_pages[:hit // ps]
            if hit_pages:
                self._kv.acquire(hit_pages)  # pin across eviction
            need = -(-S // ps) - len(hit_pages)
            if need > self._kv.free_pages and self.prefix_caching:
                self.prefix_cache.evict(need - self._kv.free_pages)
            table = self._kv.alloc(seq.rid, S, shared=hit_pages)
            if hit_pages:
                self._kv.release(hit_pages)  # table refs hold them now
            st = PartialPrefill(list(table), hit, hit)
            self._partial[seq.rid] = st
        ctx = st.done
        c = min(chunk_tokens, S - ctx)
        if ctx + c < S:
            # non-final chunks end on a page boundary
            c = min(max((c // ps) * ps, ps), S - ctx)
        final = ctx + c == S
        padded = np.zeros((1, self._bucket(c)), np.int32)
        padded[0, :c] = toks[ctx:ctx + c]
        fused = self.fused_prefix if self.prefix_caching else True
        logits, cache, _ = self._forward_chunk(
            padded, st.table[:ctx // ps], ctx, c - 1, fused)
        first = self._sample_first(seq, logits[0, 0]) if final else None
        # in-place paged write of the chunk's fresh KV
        first_page = ctx // ps
        n_chunk_pages = -(-(ctx + c) // ps) - first_page
        segs = {k: v for k, v in cache.items() if k.startswith("seg")}
        src_len = next(iter(segs.values()))["k"].shape[2]
        self._cache = self._get_page_write_fn(n_chunk_pages, src_len)(
            self._cache, segs,
            jnp.asarray(st.table[first_page:first_page + n_chunk_pages],
                        jnp.int32))
        jax.block_until_ready(self._cache)
        dt = time.perf_counter() - t0
        self.clock += dt
        self.steps += 1
        self.prefill_tokens += c
        self.chunk_dispatches += 1
        st.done = ctx + c
        st.chunks += 1
        seq.prefilled = st.done
        if not final:
            return False, None, None, dt, c
        # close out: the blob is the page set itself — pin every page,
        # publish the full-page prefix in the radix tree, drop the table
        self._kv.acquire(st.table)
        if self.prefix_caching:
            self.prefix_cache.insert([int(t) for t in toks[:(S // ps) * ps]],
                                     st.table[:S // ps])
        self._kv.free(seq.rid)              # blob pins + tree refs remain
        blob = KVBlob({}, S, prefix_tokens=S,
                      prefix_pages=list(st.table), owner=self)
        del self._partial[seq.rid]
        self.prefix_hit_tokens += st.hit
        seq.prefix_hit = st.hit
        return True, first, blob, dt, c

    def abort_partial(self, seq: Sequence):
        """Cancel a mid-chunk prefill without leaking: drop the resumable
        state and release the whole reserved residency (shared head pages
        survive through their tree references)."""
        st = self._partial.pop(seq.rid, None)
        if st is not None:
            self._kv.free(seq.rid)
            seq.prefilled = 0

    def materialize_wire(self, blob, skip_tokens: int = 0):
        """Stitch the wire payload actually shipped to the decode side.

        For a fused-path `KVBlob`, gathers only the prefix pages beyond
        `skip_tokens` (positions the decode side already holds) and
        concatenates the suffix KV — the decode-side cached prefix is
        never gathered or shipped. Drops the blob's page pins. For legacy
        tuple blobs, slices the dense cache at `skip_tokens`. Returns the
        (cache, n_tok) tuple `insert_kv` consumes, whose seg token axis
        starts at position `skip_tokens`."""
        if not isinstance(blob, KVBlob):
            cache, n_tok = blob
            if skip_tokens:
                cache = {k: ({p: v[p][:, :, skip_tokens:] for p in ("k", "v")}
                             if k.startswith("seg") else v)
                         for k, v in cache.items()}
            return cache, n_tok
        ps = self._kv.page_size
        hit = blob.prefix_tokens
        Ssuf = blob.n_tok - hit
        out = {}
        if skip_tokens < hit:
            assert skip_tokens % ps == 0
            ship_pages = blob.prefix_pages[skip_tokens // ps:]
            npb = self._bucket_pages(len(ship_pages))
            pk = self._get_gather_fn(npb)(
                self._cache, self._padded_page_ids(ship_pages, npb))
            # the paged span may end ragged (chunked blobs carry the whole
            # prompt in pages, incl. an un-page-aligned tail)
            span = hit - skip_tokens
            for name in pk:
                pieces = {p: [pk[name][p][:, :, :span]] for p in ("k", "v")}
                if name in blob.cache:      # fused path: fresh suffix KV
                    for p in ("k", "v"):
                        pieces[p].append(blob.cache[name][p][:, :, :Ssuf])
                out[name] = {p: (pieces[p][0] if len(pieces[p]) == 1 else
                                 jnp.concatenate(pieces[p], axis=2))
                             for p in ("k", "v")}
        else:
            cut = skip_tokens - hit
            for name, seg in blob.cache.items():
                out[name] = {p: seg[p][:, :, cut:Ssuf] for p in ("k", "v")}
        release_blob(blob)
        return out, blob.n_tok

    def kv_blob_bytes(self, kv_blob) -> int:
        cache, _ = kv_blob
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))

    # ---- paged insert (block-table splice) ------------------------------
    def _get_insert_fn(self, n_splice: int, src_len: int):
        key = (n_splice, src_len)
        if key not in self._insert_fn:
            ps = self._kv.page_size

            def _ins(dst, src_segs, splice_ids, row, slot, n_tok):
                out = dict(dst)
                span = n_splice * ps
                for name, seg in src_segs.items():
                    k_src, v_src = seg["k"][:, 0], seg["v"][:, 0]
                    if src_len > span:
                        k_src, v_src = k_src[:, :span], v_src[:, :span]
                    elif src_len < span:
                        pad = [(0, 0), (0, span - src_len), (0, 0), (0, 0)]
                        k_src, v_src = jnp.pad(k_src, pad), jnp.pad(v_src, pad)
                    n = k_src.shape[0]
                    shp = (n, n_splice, ps) + k_src.shape[2:]
                    dk, dv = dst[name]["k"], dst[name]["v"]
                    out[name] = {
                        "k": dk.at[:, splice_ids].set(
                            k_src.reshape(shp).astype(dk.dtype)),
                        "v": dv.at[:, splice_ids].set(
                            v_src.reshape(shp).astype(dv.dtype)),
                    }
                out["block_tables"] = dst["block_tables"].at[slot].set(row)
                out["pos"] = dst["pos"].at[slot].set(n_tok)
                return out

            self._insert_fn[key] = jax.jit(_ins, donate_argnums=(0,))
        return self._insert_fn[key]

    def insert_kv(self, seq: Sequence, kv_blob, shared: List[int] = (),
                  skip_tokens: int = 0) -> int:
        """Install a transferred prefill cache.

        Paged: allocate the block table for the sequence's residency —
        `shared` pages (pinned via `pin_prefix`) head the table, covering
        the first `skip_tokens` positions, and the blob (which carries only
        the suffix KV beyond `skip_tokens`) is spliced into the fresh
        pages — touches O(suffix pages) of device memory, not the whole
        cache. Dense fallback: slot write into the slab."""
        cache, n_tok = kv_blob
        if self.paged:
            return self._insert_kv_paged(seq, cache, n_tok, shared,
                                         skip_tokens)
        assert not shared and not skip_tokens
        slot = self._slot_free.pop(0)
        seq.slot = slot

        def merge(dst, src):
            if dst.ndim == src.ndim:
                for ax in range(dst.ndim):
                    if (dst.shape[ax] == self.max_batch
                            and src.shape[ax] == 1
                            and dst.shape[:ax] == src.shape[:ax]):
                        idx = [slice(None)] * dst.ndim
                        idx[ax] = slot
                        # sequence axes may be shorter in src (bucket < max)
                        sl = tuple(slice(0, s) for s in src.shape)
                        src_sq = jnp.squeeze(src[sl], axis=ax)
                        full_idx = list(idx)
                        j = 0
                        for i2 in range(dst.ndim):
                            if i2 == ax:
                                continue
                            full_idx[i2] = slice(0, src_sq.shape[j])
                            j += 1
                        return dst.at[tuple(full_idx)].set(src_sq.astype(dst.dtype))
            return dst
        self._cache = jax.tree.map(merge, self._cache, cache)
        self._cache["pos"] = self._cache["pos"].at[slot].set(
            jnp.asarray(n_tok, jnp.int32))
        return slot

    def _insert_kv_paged(self, seq: Sequence, cache, n_tok: int,
                         shared: List[int] = (), skip_tokens: int = 0) -> int:
        ps = self._kv.page_size
        assert skip_tokens % ps == 0 and skip_tokens // ps == len(shared)
        need = self._kv.pages_for(max(self.tokens_needed(seq), n_tok))
        if need - len(shared) > self._kv.free_pages and self.prefix_caching:
            self.prefix_cache.evict(need - len(shared) - self._kv.free_pages)
        slot = self._slot_free.pop(0)
        seq.slot = slot
        # same residency formula the admission check approved
        page_ids = self._kv.alloc(seq.rid, max(self.tokens_needed(seq), n_tok),
                                  shared=shared)
        n_prompt = min(-(-n_tok // ps), len(page_ids))
        n_splice = n_prompt - len(shared)
        splice_ids = page_ids[len(shared):n_prompt]
        row = jnp.asarray(self._kv.padded_table(seq.rid), jnp.int32)
        if n_splice > 0:
            src_segs = {k: v for k, v in cache.items() if k.startswith("seg")}
            src_len = next(iter(src_segs.values()))["k"].shape[2]
            fn = self._get_insert_fn(n_splice, src_len)
            self._cache = fn(
                self._cache, src_segs,
                jnp.asarray(splice_ids, jnp.int32),
                row, jnp.asarray(slot, jnp.int32),
                jnp.asarray(n_tok, jnp.int32))
        else:   # fully shared prompt: just point the slot at the table
            self._cache["block_tables"] = \
                self._cache["block_tables"].at[slot].set(row)
            self._cache["pos"] = self._cache["pos"].at[slot].set(
                jnp.asarray(n_tok, jnp.int32))
        if self.prefix_caching:
            # publish the full prompt pages for future shared-prefix hits
            n_full = n_tok // ps
            self.prefix_cache.insert(seq.tokens[:n_full * ps],
                                     page_ids[:n_full])
            seq.decode_hit = skip_tokens
        return slot

    def release(self, seq: Sequence):
        if seq.slot >= 0:
            if self.paged:
                self._kv.free(seq.rid)
                # repoint the slot at the trash page; later writes are inert
                self._cache["block_tables"] = (
                    self._cache["block_tables"].at[seq.slot].set(TRASH_PAGE))
                self._cache["pos"] = self._cache["pos"].at[seq.slot].set(0)
            self._slot_free.append(seq.slot)
            seq.slot = -1
            if len(self._slot_free) == self.max_batch:
                self._held_since = None     # nothing left to stall

    def cancel(self, seq: Sequence, pinned: List[int] = ()):
        """Abort a sequence at any lifecycle stage without leaking: drop
        any prefix pins taken on its behalf (`pin_prefix` references held
        while it was parked in transfer) and free its pages/slot if it was
        resident. Safe to call for sequences that never reached this
        engine (both paths no-op on nothing-held)."""
        if pinned:
            self.unpin(list(pinned))
        if self.paged:
            self.abort_partial(seq)
        self.release(seq)

    def _note_queue_wait(self, seq: Sequence):
        """Count the wait of a sequence whose first prefill starts now."""
        if not seq.produced and not seq.prefilled:
            self.queue_wait_s += time.perf_counter() - seq.created
            self.queue_waits += 1

    def _sample_first(self, seq: Sequence, logits_row) -> int:
        with host_span("serve.prefill.sample"):
            return self._sample_token(seq, logits_row)

    def _sample_token(self, seq: Sequence, logits_row) -> int:
        """Greedy argmax (default) or temperature softmax sampling with
        the sequence's per-request rng."""
        sp = seq.sampling
        if sp is None or sp.temperature <= 0.0:
            return int(jnp.argmax(logits_row))
        x = np.asarray(logits_row, np.float64) / sp.temperature
        x -= x.max()
        p = np.exp(x)
        p /= p.sum()
        return int(seq.rng().choice(p.shape[0], p=p))

    def decode_step(self, seqs: List[Sequence]) -> float:
        """One decode iteration for all active sequences."""
        if not seqs:
            return 0.0
        with host_span("serve.decode.dispatch"):
            tokens = np.zeros((self.max_batch,), np.int32)
            for s in seqs:
                tokens[s.slot] = s.tokens[-1]
            t0 = time.perf_counter()
            logits, self._cache = self._decode_fn(self.params, self._cache,
                                                  jnp.asarray(tokens))
            t1 = time.perf_counter()
        with host_span("serve.decode.wait"):
            logits.block_until_ready()
            t2 = time.perf_counter()
        with host_span("serve.decode.pull"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            sampled = any(s.sampling is not None
                          and s.sampling.temperature > 0 for s in seqs)
            rows = np.asarray(logits) if sampled else None
            for s in seqs:
                if s.sampling is not None and s.sampling.temperature > 0:
                    tok = self._sample_token(s, rows[s.slot])
                else:
                    tok = int(nxt[s.slot])
                s.append_token(tok)
            t3 = time.perf_counter()
        if self._held_since is not None:
            self.held_gap_s += t0 - self._held_since
        self._held_since = t3 if any(not s.done for s in seqs) else None
        dt = t2 - t0
        self.clock += dt
        self.steps += 1
        self.decode_tokens += len(seqs)
        self.dispatch_s += t1 - t0
        self.wait_s += t2 - t1
        self.pull_s += t3 - t2
        return dt
