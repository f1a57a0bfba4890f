"""Host-clock counters of `Engine.stats()` and the host span names: the
decode step's parts, the gap between decode steps that live sequences
sit through, the wait from submit to first prefill, and their way to
`MetricsRegistry.prometheus()`."""
import gc
import re
import time
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.core import telemetry
from repro.core.telemetry import HOST_SPANS, MetricsRegistry
from repro.core.workload import Request
from repro.models.api import build_model
from repro.serving.cluster import ServingCluster
from repro.serving.engine import Engine, Sequence

CFG = get_config("yi-6b-smoke")
SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture
def engine(params):
    return Engine(CFG, params, max_batch=2, max_len=64, page_size=16)


def _resident(eng, rid, out_len=30):
    seq = Sequence(rid, list(range(1, 11)), out_len)
    first, blob, _ = eng.prefill_request(seq)
    seq.append_token(first)
    eng.insert_kv(seq, blob)
    return seq


def _delta(eng, before):
    after = eng.stats()
    return {k: after[k] - before[k] for k in before}


def test_decode_parts_add_up_to_the_step_clock(engine):
    seq = _resident(engine, 0)
    engine.decode_step([seq])                       # compile outside
    s0 = engine.stats()
    for _ in range(3):
        engine.decode_step([seq])
    d = _delta(engine, s0)
    assert d["steps"] == 3
    assert d["dispatch_s"] + d["wait_s"] == pytest.approx(d["clock_s"],
                                                          rel=1e-9)
    assert d["dispatch_s"] > 0 and d["wait_s"] >= 0 and d["pull_s"] > 0


def test_held_gap_counts_only_while_a_sequence_waits(engine):
    seq = _resident(engine, 0)
    engine.decode_step([seq])
    s0 = engine.stats()
    time.sleep(0.05)
    engine.decode_step([seq])
    assert _delta(engine, s0)["held_gap_s"] >= 0.05
    engine.release(seq)
    s1 = engine.stats()
    time.sleep(0.05)
    seq2 = _resident(engine, 1)
    engine.decode_step([seq2])
    assert _delta(engine, s1)["held_gap_s"] == 0.0
    # a step that finishes its last sequence holds nothing after it
    last = _resident(engine, 2, out_len=2)
    engine.release(seq2)
    engine.decode_step([last])
    assert last.done
    s2 = engine.stats()
    time.sleep(0.05)
    engine.decode_step([_resident(engine, 3)])
    assert _delta(engine, s2)["held_gap_s"] == 0.0


def test_queue_wait_runs_from_creation_to_first_prefill(engine):
    seq = Sequence(0, list(range(1, 11)), 4)
    time.sleep(0.05)
    s0 = engine.stats()
    first, _blob, _ = engine.prefill_request(seq)
    d = _delta(engine, s0)
    assert d["queue_waits"] == 1 and d["queue_wait_s"] >= 0.05
    # a prompt prefilled in chunks waits once, up to its first chunk
    chunked = Sequence(1, list(range(1, 41)), 4)
    s1 = engine.stats()
    done = False
    while not done:
        done, *_ = engine.prefill_chunk(chunked, 16)
    d = _delta(engine, s1)
    assert d["queue_waits"] == 1 and d["steps"] == 3
    # a re-prefill of a sequence that has produced tokens is no queue wait
    seq.append_token(first)
    s2 = engine.stats()
    engine.prefill_request(seq)
    assert _delta(engine, s2)["queue_waits"] == 0


def test_every_host_span_name_is_declared_and_used():
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'host_span\("([^"]+)"\)', path.read_text()))
    assert used == set(HOST_SPANS)
    assert len(HOST_SPANS) == len(set(HOST_SPANS))


def test_counters_reach_the_metrics_registry(params):
    reg = MetricsRegistry()
    cluster = ServingCluster(CFG, params, ["prefill", "decode"],
                             max_batch=2, max_len=64, metrics=reg)
    for rid in range(2):
        cluster.submit(Request(rid, 0.0, 12, 4,
                               tokens=tuple(range(1, 13))), t=0.0)
    cluster.drain()
    snap = reg.snapshot()
    assert snap["decode0.steps"] > 0
    assert snap["decode0.dispatch_s"] > 0 and snap["decode0.pull_s"] > 0
    assert snap["prefill0.queue_waits"] == 2
    text = reg.prometheus()
    for key in ("held_gap_s", "wait_s", "queue_wait_s"):
        assert f"repro_decode0_{key} " in text


def test_clusters_mark_collections_once(params):
    for _ in range(2):
        ServingCluster(CFG, params, ["prefill", "decode"], max_batch=2,
                       max_len=64)
    assert gc.callbacks.count(telemetry._gc_span) == 1
    gc.collect()
    assert telemetry._gc_open == []         # every span it opened closed
