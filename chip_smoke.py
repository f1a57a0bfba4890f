#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip.

Serves yi-6b at its published widths in bf16, with random weights drawn
from `--seed`, through the public serving API, and checks what comes out:

    python chip_smoke.py [--seed 0]

Phases, all in this one process (a chip belongs to one process):

1. device check: exits non-zero unless JAX's first device is a TPU;
2. `paged_decode_op`, `prefix_prefill_op` and `paged_insert_op` on the
   chip's dispatch (`impl="auto"`) against their jnp oracles
   (`impl="ref"`), at yi-6b widths in bf16;
3. disaggregated serving, `ServingCluster(cfg, params, ["prefill",
   "decode"])`: 8 requests of 128-1024 prompt tokens and 32 output tokens
   through `submit` -> `handle.tokens()` -> `drain`;
4. the prefix cache and chunked prefill: a 512-token shared system prompt,
   `prefix_cache=True`, `chunk_tokens=256`;
5. colocated `["mixed"]` on phase 3's trace: greedy tokens must equal
   phase 3's.

Each serving phase runs its trace twice: the first pass compiles every
shape, the second is the one whose TTFT/TPOT medians are printed. Those
are a smoke, not a benchmark. Any failed check raises and exits non-zero.
The last line of stdout is the JSON contract line
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.workload import Request  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.serving.api import FINISH_LENGTH  # noqa: E402
from repro.serving.cluster import ServingCluster  # noqa: E402

ARCH = "yi-6b"
LABEL = "smoke, not a benchmark"
GIB = 2 ** 30
HBM_BYTES = 16 * GIB            # one v5e
# the block sizes ServingCluster hands its engines (attn_blocks default)
BLOCKS = (64, 64)
# Full-size serving settings. max_len covers the longest prompt plus its
# outputs; 1280 = 80 pages of 16 tokens per sequence.
PROMPT_LENS = (128, 256, 384, 512, 640, 768, 896, 1024)
SYSTEM_LEN = 512
SUFFIX_LENS = (129, 160, 192, 224, 256, 140, 200, 250)
OUT_LEN = 32
MAX_LEN = 1280
CHUNK = 256


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeError(what)


def mem_stats():
    return jax.devices()[0].memory_stats() or {}


def peak_bytes():
    return mem_stats().get("peak_bytes_in_use")


def fmt_bytes(n):
    return "n/a" if n is None else f"{n / 1e9:.2f} GB"


# -- phase 2: kernels against their oracles ---------------------------------

# Outputs are convex combinations of V rows, so every output element lies
# within max|V|. Each side rounds its result to bf16 (<= 2^-8 max|V|), and
# either side may feed the softmax weights to the MXU in bf16 for the PV
# product (<= 2^-9 max|V|): at most 1.5 * 2^-7 max|V| in all, inside the
# 2^-6 max|V| allowed here. The splice is a copy and must be exact.
ATTN_TOL = 2.0 ** -6


def phase_kernels(seed: int):
    from repro.kernels.paged_decode.ops import paged_decode_op, paged_insert_op
    from repro.kernels.prefix_prefill.ops import prefix_prefill_op
    cfg = get_config(ARCH)
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, page, npages, pps = 8, 16, 1024, 64
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(bf)

    kp, vp = normal(ks[0], (npages, page, Hkv, hd)), normal(
        ks[1], (npages, page, Hkv, hd))
    vmax = float(jnp.max(jnp.abs(vp.astype(jnp.float32))))
    perm = jax.random.permutation(ks[2], npages).astype(jnp.int32)
    rows = []

    def compare(name, fn, args, kw, tol):
        t0 = time.perf_counter()
        got = fn(*args, impl="auto", **kw)
        want = fn(*args, impl="ref", **kw)
        jax.block_until_ready((got, want))
        dt = time.perf_counter() - t0
        err = max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                        - w.astype(jnp.float32))))
                  for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        kernel = "tpu_custom_call" in fn.lower(*args, impl="auto",
                                               **kw).as_text()
        rows.append(dict(name=name, err=err, tol=tol, kernel=kernel,
                         seconds=dt))

    q = normal(ks[3], (B, H, hd))
    table = perm[:B * pps].reshape(B, pps)
    # ragged lengths, the full table, and a free slot (length 0)
    lens = jnp.asarray([1 + (131 * i + 17) % (pps * page)
                        for i in range(B - 2)] + [pps * page, 0], jnp.int32)
    compare("paged_decode", paged_decode_op, (q, kp, vp, table, lens), {},
            ATTN_TOL * vmax)

    Sq, npp, Bp = 256, 32, 2
    qs = normal(ks[4], (Bp, Sq, H, hd))
    k_suf, v_suf = normal(ks[5], (Bp, Sq, Hkv, hd)), normal(
        ks[6], (Bp, Sq, Hkv, hd))
    vmax_p = max(vmax, float(jnp.max(jnp.abs(v_suf.astype(jnp.float32)))))
    ptable = perm[:Bp * npp].reshape(Bp, npp)
    plens = jnp.asarray([npp * page, npp * page - 23], jnp.int32)
    for dbuf in (False, True):
        compare(f"prefix_prefill dbuf={dbuf}", prefix_prefill_op,
                (qs, k_suf, v_suf, kp, vp, ptable, plens),
                dict(block_q=BLOCKS[0], block_kv=BLOCKS[1], dbuf=dbuf),
                ATTN_TOL * vmax_p)

    k_new, v_new = normal(ks[7], (B, Hkv, hd)), normal(ks[0], (B, Hkv, hd))
    off = jnp.asarray([(5 * i) % page for i in range(B)], jnp.int32)
    compare("paged_insert", paged_insert_op,
            (kp, vp, k_new, v_new, perm[:B], off), {}, 0.0)
    return rows


# -- phases 3-5: serving through the public API ------------------------------

def make_prompts(cfg, rng, lens, system=()):
    """Token ids drawn from `rng`, each prompt opening with `system`."""
    return [list(system) + rng.integers(1, cfg.vocab_size, n).tolist()
            for n in lens]


def serve_twice(cluster, warm, prompts, out_len: int):
    """Warm-up pass on `warm` (compiles every shape), then the measured pass
    on `prompts` (same lengths). Returns both passes' results (in prompt
    order) and wall seconds."""
    passes = []
    for rep, batch in enumerate((warm, prompts)):
        t0 = time.perf_counter()
        start = cluster.now
        reqs = [Request(rep * len(warm) + i, start + 0.01 * i, len(p),
                        out_len, tokens=tuple(p))
                for i, p in enumerate(batch)]
        handles = [cluster.submit(r) for r in reqs]
        for h in handles:
            for _ in h.tokens():
                pass
        done = cluster.drain()
        res = [done[h.rid] for h in handles]
        passes.append((res, time.perf_counter() - t0))
    for res, _ in passes:
        finished = sum(r.finish_reason == FINISH_LENGTH
                       and r.n_generated == out_len for r in res)
        check(finished == len(prompts),
              f"{finished}/{len(prompts)} requests finished by length")
    return passes


def _summary(passes, n_prompts: int):
    (_, warm_s), (res, run_s) = passes
    return dict(
        finished=n_prompts,
        tokens=[r.tokens[-len(r.token_times):] for r in res],
        warmup_s=warm_s, measured_s=run_s,
        ttft_median_s=statistics.median(r.ttft for r in res),
        tpot_median_s=statistics.median(r.tpot for r in res),
    )


def _decode_has_kernel(engine) -> bool:
    """The engine's compiled decode program calls a Pallas kernel (and not
    its jnp oracle): lower it on the live arguments and look."""
    tok = jnp.zeros((engine.max_batch,), jnp.int32)
    txt = engine._decode_fn.lower(engine.params, engine._cache, tok).as_text()
    return "tpu_custom_call" in txt


def phase_disagg(cfg, params, prompts, *, out_len: int, max_len: int):
    cluster = ServingCluster(cfg, params, ["prefill", "decode"],
                             max_batch=len(prompts), max_len=max_len)
    out = _summary(serve_twice(cluster, prompts, prompts, out_len),
                   len(prompts))
    out["decode_kernel"] = _decode_has_kernel(cluster.decode[0])
    out["peak_bytes"] = peak_bytes()
    del cluster
    gc.collect()
    return out


def phase_prefix_chunked(cfg, params, warm, prompts, *, out_len: int,
                         max_len: int, chunk_tokens: int):
    """`warm` and `prompts` share one system prompt; their own suffixes
    differ, so the measured pass hits the cached system prompt only."""
    cluster = ServingCluster(cfg, params, ["prefill", "decode"],
                             max_batch=len(prompts), max_len=max_len,
                             prefix_cache=True, chunk_tokens=chunk_tokens)
    out = _summary(serve_twice(cluster, warm, prompts, out_len),
                   len(prompts))
    p = cluster.prefill[0]
    out.update(fused_dispatches=p.fused_dispatches,
               chunk_dispatches=p.chunk_dispatches,
               prefix_hit_tokens=p.prefix_hit_tokens,
               peak_bytes=peak_bytes())
    check(p.fused_dispatches > 0, "no fused prefix_prefill dispatch")
    check(p.chunk_dispatches > 0, "no chunked-prefill dispatch")
    del cluster, p
    gc.collect()
    return out


def phase_colocated(cfg, params, prompts, *, out_len: int, max_len: int):
    cluster = ServingCluster(cfg, params, ["mixed"],
                             max_batch=len(prompts), max_len=max_len)
    out = _summary(serve_twice(cluster, prompts, prompts, out_len),
                   len(prompts))
    out["peak_bytes"] = peak_bytes()
    del cluster
    gc.collect()
    return out


def serving_phases(cfg, params, *, seed: int, prompt_lens, system_len: int,
                   suffix_lens, out_len: int, max_len: int,
                   chunk_tokens: int, log=print):
    """Phases 3-5 on `params`; raises on any failed check."""
    rng = np.random.default_rng(seed)
    prompts = make_prompts(cfg, rng, prompt_lens)
    disagg = phase_disagg(cfg, params, prompts, out_len=out_len,
                          max_len=max_len)
    log(_line("phase 3 disagg [prefill, decode]", disagg)
        + f"; decode program has tpu_custom_call: {disagg['decode_kernel']}")
    system = rng.integers(1, cfg.vocab_size, system_len).tolist()
    warm = make_prompts(cfg, rng, suffix_lens, system)
    shared = make_prompts(cfg, rng, suffix_lens, system)
    prefix = phase_prefix_chunked(cfg, params, warm, shared, out_len=out_len,
                                  max_len=max_len, chunk_tokens=chunk_tokens)
    log(_line("phase 4 prefix cache + chunked prefill", prefix)
        + f"; fused_dispatches {prefix['fused_dispatches']}, "
          f"chunk_dispatches {prefix['chunk_dispatches']}, "
          f"prefix_hit_tokens {prefix['prefix_hit_tokens']}")
    colo = phase_colocated(cfg, params, prompts, out_len=out_len,
                           max_len=max_len)
    same = colo["tokens"] == disagg["tokens"]
    log(_line("phase 5 colocated [mixed]", colo)
        + f"; tokens equal to phase 3: {same}")
    check(same, "colocated greedy tokens differ from disaggregated")
    return dict(disagg=disagg, prefix=prefix, colocated=colo)


def _line(name, r):
    return (f"{name}: {r['finished']}/{r['finished']} finished (length); "
            f"warm-up pass {r['warmup_s']:.1f} s (compiles), measured pass "
            f"{r['measured_s']:.1f} s; TTFT median "
            f"{r['ttft_median_s'] * 1e3:.1f} ms, TPOT median "
            f"{r['tpot_median_s'] * 1e3:.2f} ms ({LABEL}); "
            f"peak {fmt_bytes(r['peak_bytes'])}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # phase 1: device check
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform!r})",
              file=sys.stderr)
        return 1
    n_dev = len(jax.devices())
    print(f"phase 1 device: {dev.device_kind}, {n_dev} device(s); "
          f"compile cache {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    for r in phase_kernels(args.seed):
        print(f"phase 2 {r['name']}: max abs err {r['err']:.3g} "
              f"(tol {r['tol']:.3g}); kernel {r['kernel']}; "
              f"{r['seconds']:.1f} s incl. compile", flush=True)
        check(r["kernel"], f"{r['name']}: no tpu_custom_call on the chip")
        check(r["err"] <= r["tol"], f"{r['name']}: error above tolerance")
    print(f"phase 2 done in {time.perf_counter() - t0:.1f} s", flush=True)

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    init = jax.jit(build_model(cfg).init, static_argnums=1).lower(
        key, jnp.bfloat16).compile()
    t1 = time.perf_counter()
    params = jax.block_until_ready(init(key))
    w_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"weights: {ARCH} bf16 {fmt_bytes(w_bytes)}; init compile "
          f"{t1 - t0:.1f} s, run {time.perf_counter() - t1:.1f} s; in use "
          f"{fmt_bytes(mem_stats().get('bytes_in_use'))}", flush=True)

    out = serving_phases(
        cfg, params, seed=args.seed, prompt_lens=PROMPT_LENS,
        system_len=SYSTEM_LEN, suffix_lens=SUFFIX_LENS, out_len=OUT_LEN,
        max_len=MAX_LEN, chunk_tokens=CHUNK,
        log=lambda s: print(s, flush=True))
    check(out["disagg"]["decode_kernel"],
          "decode program has no tpu_custom_call")
    peak = peak_bytes()
    print(f"peak bytes in use {fmt_bytes(peak)} of {fmt_bytes(HBM_BYTES)}; "
          f"weights {fmt_bytes(w_bytes)} (one copy: peak - weights = "
          f"{fmt_bytes(peak - w_bytes)} < weights)", flush=True)
    check(peak < HBM_BYTES, "peak bytes in use above 16 GiB")
    check(peak - w_bytes < w_bytes, "a second copy of the weights")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
