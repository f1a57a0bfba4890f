"""One run of one cell: set-up, the timed window, and the check.

The serving program is driven only through its public surface:
`ServingCluster(cfg, params, roles, ...)`, `submit(..., on_token=...)`,
`step()`, `next_time()`, and, for warming up every shape, the engines'
`prefill_request`, `insert_kv` and `release`.

Timing is the client's. Each request is due at a fixed offset from the
start of the traffic, is submitted when the host clock reaches it, and
every token is stamped on the host clock as the program hands it over.
TTFT and TPOT run from the due time, so a stall that delays later
submissions counts against them. The cluster's own clock is virtual (it
advances each instance by the step times its engine measured) and only
orders its events here; it is read for a cross-check and never for a
metric.
"""
from __future__ import annotations

import dataclasses
import gc
import statistics
import time
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from . import archs, reference, spec, trace as tracing, traffic
from .model import dims, make_params


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 1]."""
    xs = sorted(xs)
    if not xs:
        return float("nan")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class CompileCounter:
    """Counts programs lowered (compiled, or loaded from the persistent
    cache) while `armed`."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.lowered = 0
        self.backend = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, _secs, fun_name="", **_kw):
        if not self.armed:
            return
        if event == self.LOWER:
            self.lowered += 1
            self.names.append(fun_name)
        elif event == self.BACKEND:
            self.backend += 1


class CompiledInWindow(RuntimeError):
    """A program was lowered between the window's open and the end of the
    follow-up: the warm-up missed a shape, and the window would time a
    compile."""


@dataclasses.dataclass
class StepRecord:
    """One `cluster.step()` as the host saw it."""
    t0: float
    t1: float
    prefill_tokens: int           # prompt tokens the prefill engine computed
    decode_ctx: List[int]         # attended positions per sequence decoded
    admitted: int                 # sequences whose first decode token came
    kv_used_frac: Optional[float]  # decode pool occupancy after the step


class Client:
    """Submits requests when they fall due and stamps every token."""

    def __init__(self, cluster, reqs: List[traffic.Req], clock, annotate,
                 rid_base: int = 0):
        self.cluster = cluster
        self.rid_base = rid_base
        self.reqs = reqs
        self.clock = clock
        self.annotate = annotate
        self.origin = 0.0
        self.next = 0
        self.submitted: Dict[int, float] = {}
        self.stamps: Dict[int, List[float]] = defaultdict(list)
        self.tokens: Dict[int, List[int]] = defaultdict(list)
        self.steps: List[StepRecord] = []
        self.record_steps = False
        self.on_step = None
        self.handles = {}
        self._step_ctx: List[int] = []
        self._step_admitted = 0
        self._pe = cluster.prefill[0]
        self._de = cluster.decode[0]

    def _on_token(self, state, ev):
        rid = state.rid - self.rid_base
        self.stamps[rid].append(self.clock())
        self.tokens[rid].append(ev.token)
        if ev.index >= 1:
            # decode of token j attends over the prompt and j generated
            # positions
            self._step_ctx.append(len(self.reqs[rid].prompt) + ev.index)
            self._step_admitted += ev.index == 1

    def _submit_due(self, now: float):
        from repro.core.workload import Request
        while self.next < len(self.reqs) and \
                self.reqs[self.next].due <= now:
            r = self.reqs[self.next]
            req = Request(self.rid_base + r.idx, 0.0, len(r.prompt),
                          r.out_len, tokens=r.prompt)
            self.handles[r.idx] = self.cluster.submit(
                req, t=self.cluster.now, on_token=self._on_token)
            self.submitted[r.idx] = now
            self.next += 1

    def _step(self):
        pf0 = self._pe.prefill_tokens
        self._step_ctx = []
        self._step_admitted = 0
        t0 = self.clock()
        with self.annotate("bench.step"):
            self.cluster.step()
        t1 = self.clock()
        if self.record_steps:
            kv = None
            if self._step_ctx:
                s = self._de.stats()
                kv = s["kv.used_pages"] / s["kv.num_pages"]
            self.steps.append(StepRecord(
                t0, t1, self._pe.prefill_tokens - pf0,
                list(self._step_ctx), self._step_admitted, kv))
            if self.on_step is not None:
                self.on_step(self.steps[-1])

    def status(self, idx: int) -> Optional[str]:
        h = self.handles.get(idx)
        return None if h is None else h.status.name

    def backlog(self, t: float) -> int:
        """Requests due by host time `t` that had not reached decode by
        then: no second token (the first comes from prefill), so a queue
        before prefill and one before decode both count."""
        def decoding(r):
            st = self.stamps.get(r.idx, ())
            k = min(2, r.out_len)
            return len(st) >= k and st[k - 1] <= t
        return sum(1 for r in self.reqs if self.origin + r.due <= t
                   and not decoding(r))

    def cancel_rest(self):
        """Cancel what is still in flight, so that the cluster can serve
        another pass."""
        for h in self.handles.values():
            if not h.done:
                h.cancel()
        self.cluster.drain()

    def drive(self, stop, hooks=()):
        """Serve until `stop(now)` is true; `hooks` are (offset, fn) pairs
        run once when the traffic clock passes the offset."""
        hooks = sorted(hooks, key=lambda h: h[0])
        while True:
            now = self.clock() - self.origin
            while hooks and hooks[0][0] <= now:
                hooks.pop(0)[1]()
            self._submit_due(now)
            if stop(now):
                return
            if self.cluster.next_time() is not None:
                self._step()
                continue
            nxt = [self.reqs[self.next].due] if self.next < len(
                self.reqs) else []
            nxt += [hooks[0][0]] if hooks else []
            wait = (min(nxt) - now) if nxt else 0.001
            with self.annotate("bench.wait_for_arrival"):
                time.sleep(min(max(wait, 0.0), 0.05))


def _annotate():
    import jax
    return jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def check_program(m: archs.Dims, pcfg) -> None:
    """The program's architecture entry must have the file's widths and
    family; `SystemExit` where it has not."""
    archs.of(m).check_program(m, pcfg)


def warm_shapes(cluster, page: int, mix: dict, vocab: int, rng
                ) -> Dict[str, int]:
    """Run every program the traffic can reach once before the window.

    Prefill: the prefill engine's buckets are found by prefilling prompts
    of whole pages and bisecting wherever two probes produce caches of
    different lengths, so every bucket between the shortest and the
    longest prompt is met. Insert: the decode engine splices one cache of
    each prompt page count in the range (the program compiles one splice
    per page count and bucket), then releases it. Decode: two short
    requests run through the whole cluster."""
    import jax
    from repro.core.workload import Request
    from repro.serving.engine import Sequence
    pe, de = cluster.prefill[0], cluster.decode[0]
    lo, hi = traffic.length_range(mix)
    probes: Dict[int, int] = {}         # page count -> cache length
    caches: Dict[int, dict] = {}        # one prefill cache per length
    rid = [-1]

    def length(pages):                  # a prompt of `pages` pages in range
        return min(max(pages * page, lo), hi)

    def probe(pages):
        if pages not in probes:
            rid[0] -= 1
            S = length(pages)
            seq = Sequence(rid[0], rng.integers(1, vocab, S).tolist(), 2)
            _first, blob, _dt = pe.prefill_request(seq)
            src = next(v for k, v in blob[0].items() if k.startswith("seg"))
            probes[pages] = jax.tree.leaves(src)[0].shape[2]
            caches.setdefault(probes[pages], blob[0])
        return probes[pages]

    def bisect(a, b):
        # both ends are probed before the range is judged, so each pair of
        # neighbouring page counts whose cache lengths differ is probed on
        # both sides
        if probe(a) == probe(b) or b - a <= 1:
            return
        bisect(a, (a + b) // 2)
        bisect((a + b) // 2, b)

    p_lo, p_hi = -(-lo // page), -(-hi // page)
    bisect(p_lo, p_hi)
    probe(p_lo)
    probe(p_hi)
    known = sorted(probes)
    for pages in range(p_lo, p_hi + 1):
        # the nearest probe at or above shares this page count's cache
        # length: bisect found no change of length between them
        cache = caches[probes[next(k for k in known if k >= pages)]]
        S = length(pages)
        seq = Sequence(-10_000 - pages, [1] * (S + 1), 2)
        seq.produced = 1
        de.insert_kv(seq, (cache, S))
        de.release(seq)
    # decode, sampling and release through the public path
    for i, S in enumerate((lo, hi)):
        rid[0] -= 1
        cluster.submit(Request(rid[0], 0.0, S, 3, tokens=tuple(
            rng.integers(1, vocab, S).tolist())), t=cluster.now)
    cluster.drain()
    return {"prefill_probes": len(probes), "inserts": p_hi - p_lo + 1}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader gets: the model's dimensions, the chip's peaks,
    the window's counters and steps, and the trace summary (None without
    `--trace 1`)."""
    dims: archs.Dims
    peak: dict
    counters: Dict[str, float]
    steps: List[StepRecord]
    trace: Optional[object] = None


@dataclasses.dataclass
class Served:
    """One serving pass: the end-to-end values, what a reader gets, the
    finished requests to check, and the backlog at the window's edges."""
    values: Dict[str, float]
    run: Run
    served: Dict[int, tuple]
    attempted: int
    failed: int
    backlog: Dict[str, int]


def _counters(cluster) -> Dict[str, float]:
    pe, de = cluster.prefill[0], cluster.decode[0]
    out = {f"prefill.{k}": v for k, v in pe.stats().items()}
    out.update({f"decode.{k}": v for k, v in de.stats().items()})
    out.update({f"tx.{k}": v for k, v in cluster.tx.stats().items()})
    return out


def _latency(client, reqs, stop_t, limits):
    """Per window request: TTFT and TPOT from the due time, and whether
    both limits were met. TPOT is the mean gap over the tokens it had by
    the stop; a request still generating counts the gap it is waiting in.
    A request with no first token by the stop misses both limits."""
    rows = []
    for r in reqs:
        st = client.stamps.get(r.idx, [])
        due = client.origin + r.due
        h = client.handles.get(r.idx)
        failed = h is not None and h.status.name in ("FAILED", "CANCELLED")
        if not st:
            rows.append(dict(ttft=stop_t - due, tpot=None, ok=False,
                             failed=failed))
            continue
        if len(st) >= r.out_len:
            tpot = (st[-1] - st[0]) / max(len(st) - 1, 1)
        else:
            tpot = (stop_t - st[0]) / len(st)
        ttft = st[0] - due
        rows.append(dict(ttft=ttft, tpot=tpot, failed=failed,
                         ok=not failed and ttft <= limits["ttft_s"]
                         and tpot <= limits["tpot_s"]))
    return rows


class Session:
    """The weights from a seed, the serving cluster at the configuration's
    sizes, and every program the mix can reach run once."""

    def __init__(self, cell: spec.Cell, seed: int, t_start: float,
                 log=print):
        import jax
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.serving.cluster import ServingCluster

        self.cell, self.log = cell, log
        serving = cell.config["serving"]
        self.dims = m = dims(cell.config)
        pcfg = get_config(serving["arch"])
        check_program(m, pcfg)
        cache_dir = enable_compile_cache()
        # every program, however quick to compile, goes to the persistent
        # cache, so a cell's second run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self.device = dev = jax.devices()[0]
        self.n_devices = len(jax.devices())
        log(f"device {dev.platform} {dev.device_kind} "
            f"x{self.n_devices}; compile cache {cache_dir}")
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(
            make_params(m, seed, serving["dtype"]))
        t1 = time.perf_counter()
        self.cluster = ServingCluster(
            pcfg, self.params, serving["roles"],
            max_batch=serving["max_batch"], max_len=serving["max_len"],
            page_size=serving["page_size"],
            decode_num_pages=serving["decode_num_pages"],
            prefill_num_pages=serving.get("prefill_num_pages"),
            attn_blocks=tuple(serving["attn_blocks"]), seed=0)
        warm = warm_shapes(self.cluster, serving["page_size"], cell.traffic,
                           m.vocab, traffic.seed_rng(seed, 3))
        log(f"set-up: process start to weights {t1 - t_start:.3f} s "
            f"(of which weights {t1 - t0:.3f} s), shapes "
            f"{time.perf_counter() - t1:.3f} s {warm}")
        self.rid_base = 0

    def serve(self, mix: dict, seed: int, seconds: float, t_start: float,
              trace: bool = False) -> Served:
        """Warm-up traffic, the window of `seconds`, and the follow-up, on
        the host clock. `setup_s` runs from `t_start` to the window."""
        clock = time.perf_counter
        cluster, log = self.cluster, self.log
        reqs = traffic.build_requests(mix, seed, seconds, self.dims.vocab)
        win = [r for r in reqs if r.phase == "window"]
        client = Client(cluster, reqs, clock, _annotate(), self.rid_base)
        self.rid_base += len(reqs)
        warm_s, grace_s = float(mix["warm_s"]), float(mix["grace_s"])
        marks: Dict[str, float] = {}
        base: Dict[str, Dict[str, float]] = {}
        tracer = tracing.Tracer(mix) if trace else None
        compiles = CompileCounter()

        def open_window():
            marks["open"] = clock()
            compiles.armed = True
            client.record_steps = True
            base["open"] = _counters(cluster)

        def close_window():
            marks["close"] = clock()
            base["close"] = _counters(cluster)

        hooks = [(warm_s, open_window), (warm_s + seconds, close_window)]
        if tracer:
            hooks.append((warm_s + tracer.at, tracer.start))
            client.on_step = tracer.observe

        def done(now):
            if now < warm_s + seconds:
                return False
            if now >= warm_s + seconds + grace_s:
                return True
            # follow every window request to its second token (or its end)
            return all(len(client.stamps.get(r.idx, ())) >= min(2, r.out_len)
                       or client.handles[r.idx].done for r in win)

        client.origin = clock()
        try:
            client.drive(done, hooks)
        finally:
            compiles.close()
        stop_t = clock()
        if tracer and tracer.running:
            tracer.stop()
        t_open, t_close = marks["open"], marks["close"]
        self.peak_bytes = (self.device.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)

        rows = _latency(client, win, stop_t, mix["limits"])
        out_tokens = sum(1 for st in client.stamps.values() for t in st
                         if t_open <= t < t_close)
        window_len = t_close - t_open
        ttfts = [r["ttft"] for r in rows]
        tpots = [r["tpot"] for r in rows if r["tpot"] is not None]
        values = {
            "ttft_p90_ms": percentile(ttfts, 0.9) * 1e3,
            "tpot_p90_ms": percentile(tpots, 0.9) * 1e3,
            "slo_attainment": sum(r["ok"] for r in rows) / max(len(rows), 1),
            "output_tok_per_s": out_tokens / window_len,
            "setup_s": t_open - t_start,
        }
        late = [client.submitted[r.idx] - r.due for r in win
                if r.idx in client.submitted]
        virt = [cluster.results[client.rid_base + r.idx] for r in win
                if client.rid_base + r.idx in cluster.results]
        d_open, d_close = base["open"], base["close"]
        backlog = {k: client.backlog(t) for k, t in
                   (("open", t_open), ("close", t_close))}
        log(f"window {window_len:.3f} s, {len(win)} requests due, "
            f"{len(tpots)} with a first token, {sum(r['ok'] for r in rows)} "
            f"within both limits; {out_tokens} tokens in the window; "
            f"followed {stop_t - t_close:.3f} s after it; backlog (due, not "
            f"decoding) {backlog['open']} at open, {backlog['close']} at "
            f"close")
        log(f"generator lateness p50 {percentile(late, 0.5) * 1e3:.3f} ms, "
            f"p99 {percentile(late, 0.99) * 1e3:.3f} ms")
        log(f"programs lowered in the window {compiles.lowered} "
            f"(backend compiles {compiles.backend}) {compiles.names}")
        if compiles.lowered:
            raise CompiledInWindow(
                f"{compiles.lowered} program(s) lowered in the window: "
                f"{compiles.names}")
        log(f"peak bytes in use {self.peak_bytes}")
        dec = [s for s in client.steps if s.decode_ctx
               and t_open <= s.t0 and s.t1 <= t_close]
        pf = [s for s in client.steps if s.prefill_tokens
              and t_open <= s.t0 and s.t1 <= t_close]
        batch = statistics.fmean([len(s.decode_ctx) for s in dec] or [0])
        log(f"window steps: {len(dec)} with decode (mean batch {batch:.3f}, "
            f"median {_med([s.t1 - s.t0 for s in dec]) * 1e3:.3f} ms), "
            f"{len(pf)} with prefill ({sum(s.prefill_tokens for s in pf)} "
            f"prompt tokens, {sum((s.t1 - s.t0) for s in pf):.3f} s)")
        log(f"virtual-clock cross-check (not a metric): TTFT median "
            f"{_med([r.ttft for r in virt]) * 1e3:.3f} ms, TPOT median "
            f"{_med([r.tpot for r in virt]) * 1e3:.3f} ms over {len(virt)} "
            f"finished; host-clock TTFT median {_med(ttfts) * 1e3:.3f} ms, "
            f"TPOT median {_med(tpots) * 1e3:.3f} ms")
        log(f"modeled link time charged in the window "
            f"{d_close['tx.total_time_s'] - d_open['tx.total_time_s']:.6f} s"
            f" for {d_close['tx.total_bytes'] - d_open['tx.total_bytes']} "
            f"bytes")
        run = Run(self.dims, {},
                  {k: d_close[k] - d_open[k] for k in d_close
                   if isinstance(d_close[k], (int, float))},
                  [s for s in client.steps
                   if t_open <= s.t0 and s.t1 <= t_close])
        if tracer:
            run.peak = spec.peaks(self.device.device_kind)
            run.trace = tracer.summary()
        served = {r.idx: (r.prompt, client.tokens[r.idx]) for r in reqs
                  if r.phase != "warm" and client.status(r.idx) == "FINISHED"}
        client.cancel_rest()
        return Served(values, run, served, len(win),
                      sum(r["failed"] for r in rows), backlog)

    def close(self):
        """Drop the program's state; the weights stay for the check."""
        del self.cluster
        gc.collect()


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, log=print, control: bool = False) -> dict:
    """Set up, serve, measure and check one cell; returns the result line's
    object. `log` receives the earlier lines (cross-checks, settings).

    For reading the check's limits (`limits.py`), `control=True` also
    judges the fp8 control on the checked sample, in the served tokens'
    place (under the key `control`, which the benchmark's own runs never
    print)."""
    s = Session(cell, seed, t_start, log=log)
    out = s.serve(cell.traffic, seed, seconds, t_start, trace=trace)
    s.close()
    # the check runs on what the window served, once the program's state
    # is gone
    check = check_served(s.dims, s.params, out.served, cell.config["check"],
                         seed, log=log, control=control)
    device = {"platform": s.device.platform, "kind": s.device.device_kind,
              "count": s.n_devices, "memory_peak_bytes": int(s.peak_bytes)}
    result = {"correct": check["correct"], "attempted": out.attempted,
              "failed": out.failed}
    if trace:
        result["metrics"] = layer_metrics(cell, out.run)
        if out.run.trace is not None:
            device["busy_s"] = out.run.trace.busy_s
            device["window_s"] = out.run.trace.window_s
            result["breakdown"] = out.run.trace.breakdown
    else:
        result["metrics"] = {m["name"]: {"value": out.values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    if control:
        result["control"] = check["control"]
    result["checks"] = check["checks"]
    return result


def _med(xs):
    return statistics.median(xs) if xs else float("nan")


def layer_metrics(cell: spec.Cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(cell.metrics_dir, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_served(served: Dict[int, tuple], check_cfg: dict, seed: int
                  ) -> List[tuple]:
    """A sample, drawn from the seed, of finished requests: the one with
    the most served tokens first, then others in random order until the
    sample holds `tokens` served tokens or `max_requests` requests."""
    if not served:
        return []
    ids = sorted(served)
    longest = max(ids, key=lambda i: (len(served[i][1]), len(served[i][0])))
    rest = [i for i in traffic.seed_rng(seed, 4).permutation(ids)
            if i != longest]
    out, n = [], 0
    for i in [longest] + rest:
        if n >= check_cfg["tokens"] or len(out) >= check_cfg["max_requests"]:
            break
        out.append(served[i])
        n += len(served[i][1])
    return out


def verdict(gap: float, n: int, check_cfg: dict) -> dict:
    """The comparison that decides `correct`: the widest gap of a scored
    token below the reference's best logit against the configuration's
    limit, over at least its least number of tokens."""
    limit = float(check_cfg["logit_gap_limit"])
    need = int(check_cfg["min_tokens"])
    return {"correct": bool(n >= need and gap <= limit),
            "checks": {"logit_gap_max": {"value": gap, "limit": limit},
                       "tokens_compared_min": {"value": n, "limit": need}}}


def check_served(m: archs.Dims, params, served: Dict[int, tuple],
                 check_cfg: dict, seed: int, log=print,
                 control: bool = False) -> dict:
    """Compare the served tokens of a sample with the reference: the
    widest gap by which a served token's reference logit lies below the
    reference's best, against the configuration's limit. With `control`,
    the tokens the fp8 control puts first at each of those positions are
    scored in the served tokens' place and judged by the same `verdict`,
    under the key `control`."""
    t0 = time.perf_counter()
    sample = sample_served(served, check_cfg, seed)
    gap, n, ctrl = 0.0, 0, 0.0
    for prompt, toks in sample:
        probe = reference.score(m, params, prompt, toks, quant=True)["top"] \
            if control else None
        out = reference.score(m, params, prompt, toks, probe=probe)
        gap = max(gap, float(np.max(out["gap"])))
        if control:
            ctrl = max(ctrl, float(np.max(out["probe_gap"])))
        n += len(toks)
    log(f"reference: {len(sample)} requests, {n} served tokens, "
        f"{time.perf_counter() - t0:.3f} s")
    out = verdict(gap, n, check_cfg)
    if control:
        out["control"] = verdict(ctrl, n, check_cfg)
    return out
