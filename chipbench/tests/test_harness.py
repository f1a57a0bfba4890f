"""The harness finds its data by name, meets the benchmark's file format,
and runs a whole cell here on the CPU at smoke widths."""
import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench.model import dims
from chipbench.tests import smoke

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_file_format(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith(bench["paths"][0] + "/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:            # a cut of depth, never a width
            assert NAME.match(key) and key in cfg
            assert not re.search(r"hidden|intermediate|ffn|head|_dim$|"
                                 r"_rank$|channels|group", key)
        # what is served differently from the published file is named
        # apart, and the published value stays at the top level
        for key in cfg.get("departures", {}):
            assert key == "why" or key in cfg
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", ["yi6b.longdoc", "chatglm3.chat-overload"])
def test_cells_found_by_name(cell):
    from repro.configs import get_config
    c = spec.Spec(ROOT).cell(cell)
    m = dims(c.config)
    harness.check_program(m, get_config(c.config["serving"]["arch"]))
    names = [x["name"] for x in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for x in c.per_layer:
        read = spec.metric_reader(c.metrics_dir, x["name"])
        run = harness.Run(m, {}, {}, [], None)
        assert read(run) is None            # nothing to read: no number
    lo, hi = c.traffic["prompt"]["clip"]
    assert hi + c.traffic["output"]["clip"][1] <= \
        c.config["serving"]["max_len"]


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(KeyError):
        spec.Spec(ROOT).cell("no.such.cell")
    with pytest.raises(KeyError):
        spec.peaks("no such chip")
    assert spec.peaks("TPU v5 lite")["flops"] == 197e12


def _smoke_cell(tmp_path):
    return spec.Spec(smoke.make_root(tmp_path)).cell("smoke.cell")


@pytest.mark.parametrize("config", [smoke.CONFIG, smoke.GLM_CONFIG],
                         ids=["yi-wiring", "chatglm-wiring"])
def test_smoke_run_end_to_end(tmp_path, no_disk_cache, config):
    cell = spec.Spec(smoke.make_root(tmp_path, config)).cell("smoke.cell")
    lines = []
    res = harness.run_cell(cell, seed=2 ** 33 + 41, seconds=2.0, trace=False,
                           t_start=time.perf_counter(), log=lines.append)
    assert res["correct"] is True
    assert res["attempted"] == round(smoke.MIX["rate"] * 2.0)
    assert res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap_max"]["value"] <= \
        res["checks"]["logit_gap_max"]["limit"]
    assert any(s.startswith("programs lowered in the window") for s in lines)


def test_window_that_compiles_gives_no_result(tmp_path, no_disk_cache,
                                             monkeypatch):
    """A warm-up that leaves the splice programs cold makes the window
    compile them; the run then refuses to give a result."""
    cell = _smoke_cell(tmp_path)
    from repro.serving.engine import Engine
    monkeypatch.setattr(Engine, "insert_kv", _cold_inserts(Engine.insert_kv))
    with pytest.raises(harness.CompiledInWindow, match="_ins"):
        harness.run_cell(cell, seed=11, seconds=1.5, trace=False,
                         t_start=time.perf_counter(), log=lambda s: None)


def _cold_inserts(orig):
    """`insert_kv` that drops the splice programs compiled so far whenever
    the warm-up releases one of its own sequences."""
    def insert(self, seq, blob, *a, **kw):
        slot = orig(self, seq, blob, *a, **kw)
        if seq.rid <= -10_000:              # warm_shapes' splice sequences
            self._insert_fn.clear()
        return slot
    return insert


def test_smoke_run_traced(tmp_path, no_disk_cache, monkeypatch):
    cell = _smoke_cell(tmp_path)
    monkeypatch.setattr(spec, "peaks", lambda kind: smoke.PEAKS)
    res = harness.run_cell(cell, seed=5, seconds=2.0, trace=True,
                           t_start=time.perf_counter(), log=lambda s: None)
    assert res["correct"] is True
    names = {m["name"] for m in cell.per_layer}
    assert set(res["metrics"]) <= names
    for k in ("engine.prefill_ms_per_ktok", "engine.decode_step_ms",
              "cluster.decode_batch_mean", "kv.pages_used_frac",
              "transfer.kv_mb_per_req"):
        assert res["metrics"][k]["value"] > 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_run_refuses_without_a_chip():
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "yi6b.longdoc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout == ""


def test_run_fails_with_only_the_benchmark(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "chipbench"), str(tmp_path)],
                   check=True)
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "yi6b.longdoc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout == ""


def test_sweep_serves_each_rate_on_one_cluster(tmp_path, no_disk_cache):
    from chipbench.sweep import sweep
    cell = _smoke_cell(tmp_path)
    rows = sweep(cell, [4.0, 16.0], 1.5, 3, log=lambda s: None)
    assert [r["rate"] for r in rows] == [4.0, 16.0]
    assert [r["due"] for r in rows] == [6, 24]
    assert all(0 <= r["slo_attainment"] <= 1 for r in rows)
    assert rows[1]["output_tok_per_s"] > rows[0]["output_tok_per_s"]


class _FakeEngine:
    """Prefills to the program's bucket ladder and records each splice
    key (prompt page count, cache length) that `insert_kv` would compile."""

    BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

    def __init__(self, page):
        self.page, self.keys = page, set()

    def prefill_request(self, seq):
        S = len(seq.tokens)
        b = next(b for b in self.BUCKETS if S <= b)
        return 0, ({"seg0": {"k": np.zeros((1, 1, b, 1, 1))}}, S), 0.0

    def insert_kv(self, seq, blob):
        cache, n_tok = blob
        self.keys.add((-(-n_tok // self.page), cache["seg0"]["k"].shape[2]))

    def release(self, seq):
        pass


class _FakeCluster:
    now = 0.0

    def __init__(self, page):
        self.prefill = [_FakeEngine(page)]
        self.decode = [_FakeEngine(page)]

    def submit(self, req, t):
        pass

    def drain(self):
        pass


@pytest.mark.parametrize("mix", ["longdoc", "chat-overload"])
def test_warm_up_reaches_every_splice_key(mix):
    """Every (page count, cache length) splice a prompt of the mix can
    need is run in set-up, at every bucket edge."""
    traffic = json.loads((ROOT / "chipbench" / "traffic" / f"{mix}.json")
                         .read_text())
    page = 16
    cl = _FakeCluster(page)
    harness.warm_shapes(cl, page, traffic, 1000, np.random.default_rng(0))
    lo, hi = traffic["prompt"]["clip"]
    need = {(-(-S // page), next(b for b in _FakeEngine.BUCKETS if S <= b))
            for S in range(lo, hi + 1)}
    assert need <= cl.decode[0].keys
