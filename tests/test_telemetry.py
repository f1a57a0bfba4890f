"""Unified request-lifecycle tracing: tracer semantics, sim == live span
parity on the virtual clock, span conservation under cancellation fuzz,
tracer-off identity, Chrome-trace export + schema validation, metrics
registry, and TTFT/TPOT attribution feeding the SLO tracker.

The parity pin is the load-bearing one: with a deterministic
`EngineCharge` replacing measured kernel times, the live `DisaggCluster`
and `SimDisaggBackend` must emit the SAME span schema at the SAME
virtual-clock floats for a pinned multi-turn trace with chunked prefill
and streamed migration on. The one structural divergence is the decode
step span's start: the live cluster forms the batch at pull time while
the simulator joins at transfer_first — step spans therefore compare by
(count, end-time) only; phase/compute/wire spans and token instants
compare exactly.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.core.goodput import SLOTracker
from repro.core.latency_model import EngineCharge, LatencyModel, Parallelism
from repro.core.simulator import InstanceConfig, SimDisaggBackend
from repro.core.telemetry import (MetricsRegistry, NULL_TRACER, Tracer,
                                  attribute_request, to_chrome_trace,
                                  validate_chrome_trace)
from repro.core.workload import Request, WorkloadSpec, with_cancellations
from repro.models.api import build_model
from repro.serving.cluster import DisaggCluster

CFG = get_config("yi-6b-smoke")
LM = LatencyModel(CFG, hw.V5E)
PAR = Parallelism(1, 1)
SLOW_BW = 1e3       # B/s: wire time dwarfs compute, exercising streaming


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


# ---------------- tracer unit semantics ------------------------------------

def test_span_lifecycle_and_double_close():
    tr = Tracer()
    sp = tr.begin("compute", "chunk", 1.0, "prefill0", rid=7)
    assert sp.open and tr.open_spans() == [sp]
    tr.end(sp, 2.0, tokens=32)
    assert not sp.open and sp.dur == 1.0 and sp.args["tokens"] == 32
    with pytest.raises(ValueError):
        tr.end(sp, 3.0)                 # every span closes exactly once
    with pytest.raises(ValueError):
        tr.end(tr.begin("x", "y", 5.0, "l"), 4.0)   # time travel


def test_phase_machine_reentry_and_terminal():
    tr = Tracer()
    tr.phase(1, "queued", 0.0, "prefill0")
    tr.phase(1, "prefilling", 1.0, "prefill0")
    tr.phase(1, "prefilling", 2.0, "prefill0")  # chunked re-queue: no-op
    tr.phase(1, "decoding", 3.0, "decode0")
    tr.finish_phase(1, 4.0, "FINISHED")
    names = [(s.name, s.t0, s.t1) for s in tr.for_rid(1)]
    assert names == [("queued", 0.0, 1.0), ("prefilling", 1.0, 3.0),
                     ("decoding", 3.0, 4.0)]
    assert tr.spans[-1].events[-1].name == "FINISHED"
    assert not tr.open_spans()
    assert tr.terminals[1] == ("FINISHED", 4.0)


def test_null_tracer_is_falsy_noop():
    assert not NULL_TRACER and NULL_TRACER.enabled is False
    NULL_TRACER.phase(1, "queued", 0.0, "x")    # all no-ops, no state
    NULL_TRACER.complete("a", "b", 0.0, 1.0, "l")
    NULL_TRACER.finish_phase(1, 1.0, "FINISHED")


# ---------------- chrome-trace export + schema checker ---------------------

def test_chrome_trace_roundtrip_validates():
    tr = Tracer()
    tr.phase(1, "queued", 0.0, "prefill0")
    tr.phase(1, "prefilling", 1.0, "prefill0")
    tr.complete("compute", "chunk", 1.0, 2.0, "prefill0", rid=1, tokens=32)
    tr.phase(1, "migrating", 2.0, "decode0")
    tr.complete("wire", "kv_stream", 2.0, 3.0, "wire:0->0", rid=1,
                bytes=4096)
    tr.phase(1, "decoding", 3.0, "decode0")
    tr.event("token", 3.5, rid=1, i=0)
    tr.finish_phase(1, 4.0, "FINISHED")
    doc = to_chrome_trace(tr)
    doc = json.loads(json.dumps(doc))       # survives JSON round-trip
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    # one process lane per instance/wire, flow arrows follow the request
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    assert any(e["ph"] == "s" for e in evs) and any(
        e["ph"] == "f" for e in evs)
    # globally sorted timestamps
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_validator_rejects_corrupt_traces():
    tr = Tracer()
    tr.complete("compute", "chunk", 0.0, 1.0, "prefill0", rid=1)
    good = to_chrome_trace(tr)
    assert validate_chrome_trace(good) == []
    xi = next(i for i, e in enumerate(good["traceEvents"])
              if e["ph"] == "X")
    bad = json.loads(json.dumps(good))
    bad["traceEvents"][xi]["ts"] = -5.0
    assert validate_chrome_trace(bad)
    bad2 = json.loads(json.dumps(good))
    bad2["traceEvents"][xi]["ph"] = "Z"
    assert validate_chrome_trace(bad2)
    bad3 = json.loads(json.dumps(good))
    bad3["traceEvents"].append({"ph": "B", "name": "orphan", "ts": 9.0,
                                "pid": 1, "tid": 1})
    assert any("unclosed" in e or "orphan" in e
               for e in validate_chrome_trace(bad3))
    assert validate_chrome_trace({"not": "a trace"})


# ---------------- metrics registry -----------------------------------------

def test_metrics_registry_snapshot_and_prometheus():
    m = MetricsRegistry()
    m.counter("requests_finished")
    m.counter("requests_finished", 2)
    m.gauge("queue.depth", 7)
    for v in (0.1, 0.2, 0.3):
        m.observe("ttft_s", v)
    m.register(lambda: {"kv.used_pages": 5.0})
    snap = m.snapshot()
    assert snap["requests_finished"] == 3.0
    assert snap["queue.depth"] == 7.0
    assert snap["kv.used_pages"] == 5.0
    assert snap["ttft_s_count"] == 3.0
    assert snap["ttft_s_sum"] == pytest.approx(0.6)
    assert snap["ttft_s_max"] == pytest.approx(0.3)
    text = m.prometheus()
    assert "repro_requests_finished 3" in text
    assert "repro_queue_depth 7" in text
    assert "repro_kv_used_pages 5" in text


# ---------------- the parity pin: live == sim spans ------------------------

def _multiturn_trace():
    """Pinned 3-turn conversation: each turn's prompt extends the last
    (shared radix prefixes), long enough that chunk_tokens=32 splits every
    prefill, arrivals spaced so turns run serially (decode batch stays 1
    and the step-span divergence below stays confined to start times)."""
    rng = np.random.default_rng(42)
    sys_p = tuple(int(x) for x in rng.integers(1, CFG.vocab_size, 32))
    gap = 120.0         # >> any wire/compute time at SLOW_BW smoke scale
    reqs, prompt = [], sys_p
    for turn in range(3):
        user = tuple(int(x) for x in rng.integers(1, CFG.vocab_size, 16))
        prompt = prompt + user
        reqs.append(Request(turn, turn * gap, len(prompt), 4,
                            tokens=prompt))
        prompt = prompt + (7, 7, 7, 7)      # stand-in for the reply
    return reqs


def _span_sig(tr, cats=("phase", "compute", "wire")):
    return sorted((s.cat, s.name, s.lane, s.rid, s.t0, s.t1)
                  for s in tr.spans if s.cat in cats)


def test_live_and_sim_emit_identical_spans(params):
    """Same schema, same lanes, same virtual-clock floats: phase, compute
    and wire spans (plus token instants and route decisions) from the
    live cluster under an `EngineCharge` match the simulator's exactly on
    a pinned multi-turn chunked+streamed trace."""
    tr_live, tr_sim = Tracer(), Tracer()
    live = DisaggCluster(CFG, params, n_prefill=1, n_decode=1, max_batch=2,
                         max_len=256, lm_tokens=128, chunk_tokens=32,
                         transfer_bandwidth=SLOW_BW, prefix_cache=True,
                         tracer=tr_live, charge=EngineCharge(LM, PAR))
    live.run(_multiturn_trace())
    sim = SimDisaggBackend(LM, InstanceConfig(PAR, 1),
                           InstanceConfig(PAR, 1), transfer_bw=SLOW_BW,
                           lm_tokens=128, chunk_tokens=32,
                           prefix_cache=True, tracer=tr_sim)
    for r in _multiturn_trace():
        sim.submit(r)
    sim.drain()

    a, b = _span_sig(tr_live), _span_sig(tr_sim)
    assert len(a) == len(b), (len(a), len(b))
    for sa, sb in zip(a, b):
        assert sa[:4] == sb[:4], (sa, sb)           # cat/name/lane/rid
        assert sa[4] == pytest.approx(sb[4], rel=1e-9, abs=1e-12), (sa, sb)
        assert sa[5] == pytest.approx(sb[5], rel=1e-9, abs=1e-12), (sa, sb)
    # chunked prefill and streamed migration actually happened
    assert any(s[1] == "chunk" for s in a)
    assert any(s[0] == "wire" and s[1] == "kv_stream" for s in a)
    # prefix reuse surfaced: later turns report non-zero hits both sides
    assert live.dispatcher.decisions == sim.disp.decisions
    # decode step spans: same count and end-times (start times differ by
    # construction — live batches at pull, sim at transfer_first)
    st_a = sorted((s.lane, s.t1) for s in tr_live.spans if s.cat == "step")
    st_b = sorted((s.lane, s.t1) for s in tr_sim.spans if s.cat == "step")
    assert len(st_a) == len(st_b)
    for (la, ta), (lb, tb) in zip(st_a, st_b):
        assert la == lb and ta == pytest.approx(tb, rel=1e-9)
    # token instants: same count and virtual times per request
    for rid in range(3):
        tok_a = [i.t for i in tr_live.tokens_for(rid)]
        tok_b = [i.t for i in tr_sim.tokens_for(rid)]
        assert len(tok_a) == len(tok_b) == 4
        assert tok_a == pytest.approx(tok_b, rel=1e-9)
        assert tr_live.terminals[rid][0] == "FINISHED"
        assert tr_sim.terminals[rid][0] == "FINISHED"
    # both traces export to valid Chrome JSON
    assert validate_chrome_trace(to_chrome_trace(tr_live)) == []
    assert validate_chrome_trace(to_chrome_trace(tr_sim)) == []


# ---------------- span conservation under cancellation fuzz ----------------

def test_span_conservation_cancel_fuzz(params):
    """Every opened span closes exactly once; cancelled requests end in a
    CANCELLED terminal regardless of which lifecycle stage the cancel
    lands in (queued / mid-chunk / parked / pending-admit / decoding)."""
    rng = np.random.default_rng(0)
    sys_p = tuple(rng.integers(1, CFG.vocab_size, 16).tolist())
    for trial in range(2):
        rr = np.random.default_rng(300 + trial)
        reqs = []
        for i in range(10):
            u = tuple(rr.integers(1, CFG.vocab_size,
                                  int(rr.integers(4, 20))).tolist())
            reqs.append(Request(i, i * 0.02, 16 + len(u), 4,
                                tokens=sys_p + u))
        reqs = with_cancellations(reqs, frac=0.5, seed=trial,
                                  mean_wait_s=0.3)
        tr = Tracer()
        dc = DisaggCluster(CFG, params, n_prefill=2, n_decode=1,
                           max_batch=4, max_len=64, lm_tokens=48,
                           chunk_tokens=16, prefix_cache=True,
                           decode_num_pages=3 * (64 // 16) + 1,
                           tracer=tr)
        res = dc.run(reqs)
        assert not tr.open_spans(), \
            [(s.cat, s.name, s.rid) for s in tr.open_spans()]
        for rid, r in res.items():
            term, _ = tr.terminals[rid]
            if r.finish_reason == "cancelled":
                assert term == "CANCELLED", (rid, term)
            else:
                assert term == "FINISHED", (rid, term)
        doc = to_chrome_trace(tr)
        assert validate_chrome_trace(doc) == []


def test_sim_span_conservation_cancel_fuzz():
    rng = np.random.default_rng(1)
    reqs = [Request(i, float(i) * 0.05, int(rng.integers(16, 400)),
                    int(rng.integers(4, 40))) for i in range(40)]
    reqs = with_cancellations(reqs, frac=0.4, seed=2, mean_wait_s=0.01)
    tr = Tracer()
    sim = SimDisaggBackend(LM, InstanceConfig(PAR, 1),
                           InstanceConfig(PAR, 1), tracer=tr)
    for r in reqs:
        sim.submit(r)
    sim.drain()
    assert not tr.open_spans()
    n_cancelled = sum(r.finish_reason == "cancelled" for r in reqs)
    assert n_cancelled > 0
    for r in reqs:
        term, _ = tr.terminals[r.rid]
        assert term == ("CANCELLED" if r.finish_reason == "cancelled"
                        else "FINISHED")
    assert validate_chrome_trace(to_chrome_trace(tr)) == []


# ---------------- tracer-off identity --------------------------------------

def test_tracer_off_is_default_and_identical(params):
    """Tracing must be observation only: with a deterministic charge, a
    traced run and an untraced run produce byte-identical tokens, float-
    identical virtual times, and the same routing decisions. Tracer off
    is the default (NULL_TRACER)."""
    def run(tracer):
        dc = DisaggCluster(CFG, params, n_prefill=1, n_decode=1,
                           max_batch=2, max_len=256, lm_tokens=128,
                           chunk_tokens=32, transfer_bandwidth=SLOW_BW,
                           prefix_cache=True, tracer=tracer,
                           charge=EngineCharge(LM, PAR))
        res = dc.run(_multiturn_trace())
        return dc, res
    dc0, res0 = run(None)
    assert dc0.tracer is NULL_TRACER
    dc1, res1 = run(Tracer())
    assert sorted(res0) == sorted(res1)
    for rid in res0:
        assert res0[rid].tokens == res1[rid].tokens
        assert res0[rid].token_times == res1[rid].token_times
        assert res0[rid].finish_reason == res1[rid].finish_reason
    assert dc0.dispatcher.decisions == dc1.dispatcher.decisions


def test_colocated_backends_emit_spans(params):
    """Both colocated backends (live + sim) speak the same span schema on
    `engine{i}` lanes: queued -> prefilling -> decoding phases, per-batch
    prefill_batch compute spans, decode_step step spans, FINISHED
    terminals, and a valid Chrome-trace export."""
    from repro.serving.cluster import ColocatedCluster
    from repro.core.simulator import SimColocatedBackend

    def check(tr, n):
        assert not tr.open_spans()
        for rid in range(n):
            names = {s.name for s in tr.for_rid(rid) if s.cat == "phase"}
            assert {"queued", "prefilling", "decoding"} <= names
            assert tr.terminals[rid][0] == "FINISHED"
            assert len(tr.tokens_for(rid)) == 4
        assert all(s.lane.startswith("engine") for s in tr.spans)
        assert any(s.name == "prefill_batch" for s in tr.spans)
        assert any(s.cat == "step" for s in tr.spans)
        assert validate_chrome_trace(to_chrome_trace(tr)) == []

    reqs = [Request(i, i * 0.01, 12 + 4 * i, 4) for i in range(3)]
    tr_live = Tracer()
    cc = ColocatedCluster(CFG, params, n_engines=1, max_batch=4,
                          max_len=64, tracer=tr_live)
    cc.run([Request(r.rid, r.arrive, r.in_len, r.out_len) for r in reqs])
    check(tr_live, 3)

    tr_sim = Tracer()
    sim = SimColocatedBackend(LM, InstanceConfig(PAR, 1), tracer=tr_sim)
    for r in reqs:
        sim.submit(r)
    sim.drain()
    check(tr_sim, 3)


def test_sim_tracer_off_identity():
    def run(tracer):
        reqs = [Request(i, i * 0.1, 64 + 16 * i, 6) for i in range(6)]
        sim = SimDisaggBackend(LM, InstanceConfig(PAR, 1),
                               InstanceConfig(PAR, 1), tracer=tracer)
        for r in reqs:
            sim.submit(r)
        sim.drain()
        return reqs
    r0, r1 = run(None), run(Tracer())
    for a, b in zip(r0, r1):
        assert (a.first_token, a.finish) == (b.first_token, b.finish)
        assert a.finish_reason == b.finish_reason


# ---------------- attribution + SLO annotation -----------------------------

def test_attribution_decomposes_ttft_and_tpot(params):
    tr = Tracer()
    dc = DisaggCluster(CFG, params, n_prefill=1, n_decode=1, max_batch=2,
                       max_len=256, lm_tokens=128, chunk_tokens=32,
                       transfer_bandwidth=SLOW_BW, prefix_cache=True,
                       tracer=tr, charge=EngineCharge(LM, PAR))
    reqs = _multiturn_trace()
    dc.run(reqs)
    for r in reqs:
        att = attribute_request(tr, r.rid)
        assert att is not None
        # TTFT parts cover arrive -> first token (within float slop)
        ttft = r.first_token - r.arrive
        assert sum(att.ttft_parts().values()) == pytest.approx(
            ttft, rel=1e-6, abs=1e-9)
        assert att.dominant_ttft in att.ttft_parts()
        assert att.n_tokens == 4
        if att.n_tokens > 1:
            assert att.tpot_parts()["step_compute"] >= 0
            assert att.tpot_parts()["batch_wait"] >= 0
        assert "ttft" in att.format()


def test_slo_tracker_annotates_violations(params):
    """A tight SLO turns every request into a violation; with a tracer
    attached each violation carries its attribution and the dominant
    TTFT term (the slow wire makes migration dominate here)."""
    spec = WorkloadSpec("w", 5.0, 1.0, (4, 512), 4.0, 0.5, (4, 64),
                        slo_ttft=1e-6, slo_tpot=1e-9)
    tr = Tracer()
    tracker = SLOTracker(spec, tracer=tr)
    dc = DisaggCluster(CFG, params, n_prefill=1, n_decode=1, max_batch=2,
                       max_len=256, lm_tokens=128, chunk_tokens=32,
                       transfer_bandwidth=SLOW_BW, prefix_cache=True,
                       tracer=tr, charge=EngineCharge(LM, PAR),
                       tracker=tracker)
    dc.run(_multiturn_trace())
    assert len(tracker.violations) == 3
    top = tracker.top_violations(2)
    assert len(top) == 2
    assert top[0].severity >= top[1].severity
    for v in top:
        assert v.attribution is not None
        assert v.attribution.dominant_ttft in v.attribution.ttft_parts()
        assert "ttft" in v.format()


# ---------------- per-request trace sampling -------------------------------

def test_sampling_decision_is_deterministic_and_partial():
    tr = Tracer(sample_rate=0.5, sample_seed=3)
    picks = [tr.sampled(rid) for rid in range(400)]
    assert picks == [tr.sampled(rid) for rid in range(400)]
    frac = sum(picks) / len(picks)
    assert 0.35 < frac < 0.65           # roughly the requested rate
    # a different seed samples a different subset at the same rate
    other = [Tracer(sample_rate=0.5, sample_seed=4).sampled(r)
             for r in range(400)]
    assert other != picks
    assert all(Tracer(sample_rate=1.0).sampled(r) for r in range(32))
    assert not any(Tracer(sample_rate=0.0).sampled(r) for r in range(32))
    assert Tracer(sample_rate=0.0).sampled(None)    # rid-less: always kept


def test_sampling_keeps_instants_and_terminals_drops_spans():
    tr = Tracer(sample_rate=0.4, sample_seed=1)
    sim = SimDisaggBackend(LM, InstanceConfig(PAR, 1),
                           InstanceConfig(PAR, 1), tracer=tr)
    reqs = [Request(i, i * 0.05, 32 + 8 * i, 5) for i in range(20)]
    for r in reqs:
        sim.submit(r)
    sim.drain()
    kept = {r.rid for r in reqs if tr.sampled(r.rid)}
    assert 0 < len(kept) < len(reqs)    # both kinds present at this seed
    for r in reqs:
        if r.rid in kept:
            assert tr.for_rid(r.rid), r.rid
        else:
            assert not tr.for_rid(r.rid), r.rid
        # instants-only data survives for everyone: tokens + terminal
        assert len(tr.tokens_for(r.rid)) == r.out_len
        assert tr.terminals[r.rid][0] == "FINISHED"
    assert tr.open_spans() == []
    # the thinned trace still exports as a valid chrome trace
    doc = to_chrome_trace(tr)
    assert validate_chrome_trace(doc) == []


def test_sampling_never_changes_tokens_or_routing():
    """The satellite pin: sampling only filters what is recorded — a
    fleet run at sample_rate 1.0 and 0.1 must produce identical tokens,
    timings, and routing decisions."""
    from repro.core.workload import sample_multi_turn
    from repro.serving.router import FleetRouter, OverloadDetector

    def fleet_run(rate):
        spec = WorkloadSpec("s", 3.0, 0.4, (8, 64), 2.0, 0.3, (4, 16),
                            slo_ttft=1.0, slo_tpot=1.0,
                            sys_len=16, turns=2, share=0.8)
        reqs = sample_multi_turn(spec, rate=50.0, n=40, seed=9,
                                 vocab=1000, think_s=0.5)
        tr = Tracer(sample_rate=rate)
        router = FleetRouter(
            [SimDisaggBackend(LM, InstanceConfig(PAR, 1),
                              InstanceConfig(PAR, 1), prefix_cache=True,
                              tracer=tr) for _ in range(2)],
            policy="prefix_affinity", tracer=tr,
            detector=OverloadDetector(max_inflight=4, max_queue=8,
                                      shed_after_s=0.2))
        for r in reqs:
            router.submit(r)
        res = router.drain()
        return tr, router.decisions, res

    tr_all, dec_all, res_all = fleet_run(1.0)
    tr_thin, dec_thin, res_thin = fleet_run(0.1)
    assert dec_all == dec_thin
    assert set(res_all) == set(res_thin)
    for rid in res_all:
        assert res_all[rid].tokens == res_thin[rid].tokens
        assert res_all[rid].finish == res_thin[rid].finish
        assert res_all[rid].finish_reason == res_thin[rid].finish_reason
    assert len(tr_thin.spans) < len(tr_all.spans)   # it did thin the trace
    assert tr_thin.terminals == tr_all.terminals    # but lost no terminals
