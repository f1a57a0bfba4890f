"""The serving program's host spans in a profiler trace read back through
`trace.read`, the program names that `trace.PROGRAMS` matches, and the
readers of the spans and host-clock counters on hand-built runs."""
import gc
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import spec, trace
from chipbench.harness import Run
from repro.configs import get_config
from repro.core.telemetry import HOST_SPANS
from repro.core.workload import Request
from repro.models.api import build_model
from repro.serving.cluster import ServingCluster
from repro.serving.engine import Engine

CFG = get_config("yi-6b-smoke")
METRICS = spec.HERE / "metrics"


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


def _submit(cluster, rids):
    for rid in rids:
        cluster.submit(Request(rid, 0.0, 20, 5,
                               tokens=tuple(range(1, 21))), t=cluster.now)


@pytest.fixture(scope="module")
def traced(params):
    """Two requests through a prefill and a decode engine, every
    `cluster.step()` inside a `bench.step` annotation as the harness
    makes it, after the same two requests compiled every program."""
    cluster = ServingCluster(CFG, params, ["prefill", "decode"],
                             max_batch=2, max_len=64)
    _submit(cluster, (0, 1))
    cluster.drain()
    tr = trace.Tracer({"trace": {"at_s": 0.0, "min_prefills": 1,
                                 "min_decode_steps": 1, "max_span_s": 9.0}})
    tr.start()
    _submit(cluster, (2, 3))
    while cluster.next_time() is not None:
        with jax.profiler.TraceAnnotation(trace.STEP):
            cluster.step()
    tr.stop()
    return tr.summary()


# a collection opens `serve.gc` wherever it falls, inside a step or not
PROGRAM_SPANS = set(HOST_SPANS) - {"serve.gc"}


def _spans(s):
    return [e for e in s.host if e.name in PROGRAM_SPANS]


def test_every_span_lies_inside_a_cluster_step(traced):
    spans = _spans(traced)
    assert {e.name for e in spans} == PROGRAM_SPANS
    for e in spans:
        assert any(st.start <= e.start and e.end <= st.end
                   for st in traced.steps), e.name


def test_spans_nest_strictly(traced):
    spans = sorted(_spans(traced), key=lambda e: (e.start, -e.end))
    for i, a in enumerate(spans):
        for b in spans[i + 1:]:
            if b.start >= a.end:
                break
            assert b.end <= a.end, (a.name, b.name)
    inner = {"serve.kv_handoff": "serve.admit",
             "serve.prefill.sample": "serve.prefill"}
    for e in spans:
        if e.name in inner:
            assert any(o.name == inner[e.name] and o.start <= e.start
                       and e.end <= o.end for o in spans), e.name


def test_a_collection_is_named_on_the_trace(params):
    ServingCluster(CFG, params, ["prefill", "decode"], max_batch=2,
                   max_len=64)                  # installs `mark_gc`
    tr = trace.Tracer({"trace": {"at_s": 0.0, "min_prefills": 1,
                                 "min_decode_steps": 1, "max_span_s": 9.0}})
    tr.start()
    with jax.profiler.TraceAnnotation(trace.STEP):
        gc.collect()
    tr.stop()
    s = tr.summary()
    step, = s.steps
    assert any(e.name == "serve.gc" and step.start <= e.start
               and e.end <= step.end for e in s.host)


def test_splice_reader_on_a_recorded_trace(traced):
    run = Run(None, {}, {}, [], traced)
    ms = spec.metric_reader(METRICS, "transfer.splice_ms_per_req")(run)
    assert ms is not None and ms > 0


def _module_name(lowered) -> str:
    return lowered.compiler_ir().operation.attributes["sym_name"].value


def test_program_names_are_the_ones_the_trace_matches(params):
    """A rename of the prefill, decode or splice program would silently
    leave `mfu.*` and `transfer.splice_ms_per_req` with nothing to read."""
    eng = Engine(CFG, params, max_batch=2, max_len=64, page_size=16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    segs = {k: v for k, v in eng._cache.items() if k.startswith("seg")}
    src = {k: {p: jax.ShapeDtypeStruct(
        (v[p].shape[0], 1, 16) + v[p].shape[3:], v[p].dtype)
        for p in ("k", "v")} for k, v in segs.items()}
    names = {
        "prefill": _module_name(eng._get_prefill_fn(16).lower(
            eng.params, i32(1, 16), i32())),
        "decode": _module_name(eng._decode_fn.lower(
            eng.params, eng._cache, i32(2))),
        "insert": _module_name(eng._get_insert_fn(1, 16).lower(
            eng._cache, src, i32(1),
            i32(eng._cache["block_tables"].shape[1]), i32(), i32())),
    }
    assert names == {"prefill": "jit__pf", "decode": "jit__decode",
                     "insert": "jit__ins"}
    for kind, name in names.items():
        for other, pat in trace.PROGRAMS.items():
            # the device trace names a module `<name>(<hash>)`
            hit = bool(pat.search(f"{name}(8d2f0c41)"))
            assert hit == (other == kind), (kind, other)


# ---------------------------------------------------------------------------
# readers on hand-built runs
# ---------------------------------------------------------------------------

def _rec(t0, t1):
    return SimpleNamespace(t0=t0, t1=t1)


# step records with gaps 0.3 s (1.0-1.3), 0.01 s (2.0-2.01), 0.2 s
# (3.01-3.21)
STEPS = [_rec(0.0, 1.0), _rec(1.3, 2.0), _rec(2.01, 3.01), _rec(3.21, 4.0)]


def _summary(host_span=(1.1, 3.1), modules=(), host=(), ops=()):
    return trace.Summary(1.0, 0.5, list(modules), list(ops), [], list(host),
                         {}, host_span)


def _ev(name, a, b, meta=""):
    return trace.Event(name, a, b, meta)


HANDOFFS = [_ev("serve.kv_handoff", 0.0, 0.001),
            _ev("serve.kv_handoff", 1.0, 1.001), _ev(trace.STEP, 0.0, 2.0)]
MODULES = [_ev("jit__ins(1a)", 0.0005, 0.0025),
           _ev("jit__ins(2b)", 1.0, 1.004), _ev("jit__decode(3c)", 2.0, 2.04)]
# a splice from before the trace's first hand-off
EARLY = [_ev("jit__ins(0z)", -0.5, -0.49)]
# a hand-off whose splice starts after the next decode step
LATE = ([_ev("serve.kv_handoff", 1.9, 1.901)],
        [_ev("jit__ins(9y)", 2.1, 2.2)])
# two hand-offs before one decode step, the second splice queued
BACK_TO_BACK = ([_ev("serve.kv_handoff", 3.0, 3.001),
                 _ev("serve.kv_handoff", 3.001, 3.002)],
                [_ev("jit__ins(4d)", 3.002, 3.010),
                 _ev("jit__ins(5e)", 3.010, 3.018),
                 _ev("jit__decode(6f)", 3.1, 3.14)])
# no module line (the CPU): one splice is a stretch of its operations
CPU_OPS = [_ev("copy", 0.0005, 0.001, "jit__ins"),
           _ev("scatter", 0.001, 0.0025, "jit__ins"),
           _ev("fusion", 0.003, 0.004, "jit__decode")]
HELD = {"decode.steps": 100, "decode.held_gap_s": 1.0}
HOST = {"decode.steps": 100, "decode.dispatch_s": 0.2, "decode.pull_s": 0.1}


@pytest.mark.parametrize("name,counters,tr,want", [
    # the gaps holding the profiler's start (1.1) and stop (3.1) go
    ("cluster.decode_gap_ms", HELD, _summary(), (1.0 - 0.5) / 100 * 1e3),
    ("cluster.decode_gap_ms", dict(HELD, **{"decode.held_gap_s": 0.1}),
     _summary(), 0.0),
    # a stop after the window's last step record takes nothing out
    ("cluster.decode_gap_ms", HELD, _summary((1.1, 9.0)), 7.0),
    ("cluster.decode_gap_ms", HELD, None, None),
    ("cluster.decode_gap_ms", {"decode.steps": 100}, _summary(), None),
    ("engine.decode_host_ms", HOST, None, 3.0),
    ("engine.decode_host_ms", {"decode.steps": 100}, None, None),
    ("engine.decode_host_ms", dict(HOST, **{"decode.steps": 0}), None, None),
    ("transfer.splice_ms_per_req", {},
     _summary(modules=MODULES, host=HANDOFFS), 3.0),
    ("transfer.splice_ms_per_req", {},
     _summary(modules=MODULES, host=HANDOFFS[2:]), None),
    ("transfer.splice_ms_per_req", {},
     _summary(modules=EARLY + MODULES, host=HANDOFFS), 3.0),
    ("transfer.splice_ms_per_req", {},
     _summary(modules=MODULES + LATE[1], host=HANDOFFS + LATE[0]), 3.0),
    ("transfer.splice_ms_per_req", {},
     _summary(modules=MODULES + BACK_TO_BACK[1],
              host=HANDOFFS + BACK_TO_BACK[0]), (2 + 4 + 8 + 8) / 4),
    ("transfer.splice_ms_per_req", {},
     _summary(ops=CPU_OPS, host=HANDOFFS[:1]), 2.0),
    ("transfer.splice_ms_per_req", {}, None, None),
])
def test_reader(name, counters, tr, want):
    run = Run(None, {}, counters, STEPS, tr)
    got = spec.metric_reader(METRICS, name)(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
