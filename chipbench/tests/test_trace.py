"""`trace` on a small profiler trace recorded here on the CPU, and on
intervals worked out by hand."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace


def test_union_and_gaps_by_hand():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert trace._union(iv) == pytest.approx(3.0)
    assert trace._gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace._gaps(iv, -1.0, 4.0) == [(-1.0, 0.0), (2.0, 3.0)]


def _pf(x):
    return jnp.tanh(x @ x) @ x


def _decode(x):
    return jnp.sin(x) * 2.0


def paged_decode(x):
    return jnp.cos(x) @ x


@pytest.fixture(scope="module")
def recorded():
    """A trace of three `bench.step`s: a prefill-named program, a
    decode-named program calling a `paged_decode` program, and an idle
    step."""
    pf, dec, kern = jax.jit(_pf), jax.jit(_decode), jax.jit(paged_decode)
    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready((pf(x), dec(x), kern(x)))      # compile first
    tr = trace.Tracer({"trace": {"at_s": 0.0, "min_prefills": 1,
                                 "min_decode_steps": 1, "max_span_s": 9.0}})
    records = []
    tr.start()
    for kind in ("prefill", "decode", "idle"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.STEP):
            if kind == "prefill":
                pf(x).block_until_ready()
            elif kind == "decode":
                kern(dec(x)).block_until_ready()
            else:
                time.sleep(0.02)
        records.append(SimpleNamespace(t0=t0, t1=time.perf_counter(),
                                       kind=kind))
    time.sleep(0.05)
    tr.stop()
    s = tr.summary()
    assert not tr.dir.exists()                          # trace removed
    return s, records


def test_tracer_stops_once_it_has_enough():
    tr = trace.Tracer({"trace": {"at_s": 0.0, "min_prefills": 1,
                                 "min_decode_steps": 2, "max_span_s": 60.0}})
    tr.start()
    now = time.perf_counter()
    step = lambda pf, ctx: SimpleNamespace(t0=now + 1, t1=now + 2,
                                           prefill_tokens=pf, decode_ctx=ctx)
    tr.observe(step(100, []))
    tr.observe(step(0, [5, 6]))
    assert tr.running
    tr.observe(step(0, [7]))
    assert not tr.running
    tr.summary()
    assert not tr.dir.exists()


def test_short_op_names():
    assert trace._short('%paged_decode_op.5 = bf16[12,32,128] custom-call('
                        '%fusion.1), custom_call_target="tpu_custom_call"') \
        == "paged_decode_op"
    assert trace._short("%fusion = bf16[2048,4096] fusion(%x)") == "fusion"


def test_busy_and_idle(recorded):
    s, _ = recorded
    assert 0 < s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(
        trace._union([(e.start, e.end) for e in s.ops]))
    idle = 1 - s.busy_s / s.window_s
    assert 0 < idle < 1
    assert len(s.breakdown["device_ops"]) <= 10
    assert all(v > 0 for _, v in s.breakdown["idle_gaps"])


def test_steps_pair_with_records_and_programs(recorded):
    s, records = recorded
    pairs = s.pairs(records)
    assert [r.kind for r, _ in pairs] == ["prefill", "decode", "idle"]
    by = {r.kind: a for r, a in pairs}
    pf = s.program_time("prefill", by["prefill"])
    assert 0 < pf <= by["prefill"].end - by["prefill"].start
    assert s.program_time("prefill", by["decode"]) == 0
    assert s.program_time("decode", by["decode"]) > 0
    assert s.kernel_time("paged_decode", by["decode"]) > 0
    assert s.kernel_time("paged_decode", by["prefill"]) == 0
    assert s.program_time("decode", by["idle"]) == 0
