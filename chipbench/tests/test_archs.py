"""An architecture is one file under `chipbench/archs/`, found by the
configuration's `model_type`; moving the two dense families there changed
no number."""
import dataclasses
import hashlib
import importlib
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import archs, costs, harness, reference, spec
from chipbench.model import dims, make_params, param_shapes
from chipbench.tests import smoke

ARCHS = Path(archs.__file__).resolve().parent
CFG = ARCHS.parent / "configs"
MODEL_TYPES = sorted(p.stem for p in ARCHS.glob("*.py")
                     if not p.stem.startswith("_"))


def _leaf_hashes(params):
    return {"/".join(k.key for k in path):
            hashlib.sha256(np.asarray(x).view(np.uint16).tobytes())
            .hexdigest()[:16]
            for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("model_type", MODEL_TYPES)
def test_arch_module_has_the_interface(model_type):
    from repro.configs import get_config
    mod = archs.load(model_type)
    assert all(callable(getattr(mod, k)) for k in archs.INTERFACE
               if k != "SMOKE")
    cfg = mod.SMOKE
    m = dims(cfg)
    assert archs.of(m) is mod and m.model_type == model_type
    hash(m)
    with pytest.raises(dataclasses.FrozenInstanceError):
        m.model_type = "other"
    assert m.vocab > 0 and m.kv_bytes_per_token > 0

    pcfg = get_config(cfg["serving"]["arch"])
    harness.check_program(m, pcfg)
    with pytest.raises(SystemExit):
        harness.check_program(m, dataclasses.replace(
            pcfg, num_layers=pcfg.num_layers + 1))

    params = make_params(m, 2 ** 32 + 3)
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree.map(lambda s: (tuple(s), "bfloat16"), param_shapes(m),
                        is_leaf=lambda s: isinstance(s, tuple))
    assert got == want
    assert all(bool(np.isfinite(np.asarray(x, np.float32)).all())
               for x in jax.tree.leaves(params))

    rng = np.random.default_rng(5)
    prompt = rng.integers(1, m.vocab, 29).tolist()
    served = rng.integers(1, m.vocab, 7).tolist()
    for quant in (False, True):
        out = reference.score(m, params, prompt, served, quant=quant,
                              probe=served)
        assert out["gap"].shape == (7,) and np.all(out["gap"] >= 0)
        np.testing.assert_array_equal(out["probe_gap"], out["gap"])
        # where the pass's own first token is served, the gap is 0
        own = reference.score(m, params, prompt, served, quant=quant,
                              probe=out["top"])
        assert np.all(own["probe_gap"] == 0)

    ctx = [17, 300]
    w = costs.weight_bytes(m)
    assert w > 0 and costs.prefill_flops(m, 300) > costs.prefill_flops(m, 17)
    for f in (costs.attention_decode_flops, costs.attention_decode_bytes,
              costs.decode_step_flops, costs.decode_step_bytes):
        assert f(m, ctx) > 0
    assert costs.attention_decode_flops(m, ctx) == \
        costs.attention_decode_flops(m, [17]) + \
        costs.attention_decode_flops(m, [300])
    assert costs.decode_step_flops(m, ctx) > \
        costs.attention_decode_flops(m, ctx)
    assert costs.decode_step_bytes(m, ctx) > w + \
        317 * m.kv_bytes_per_token
    assert costs.roofline_s(1.0, 1.0, smoke.PEAKS) > 0


def test_unknown_model_type_names_the_file():
    with pytest.raises(KeyError, match="chipbench/archs/no_such_arch.py"):
        dims({"model_type": "no_such_arch"})
    with pytest.raises(KeyError, match="chipbench/archs/no_such_arch.py"):
        archs.load("no_such_arch")


# Recorded from the commit before the architectures moved under `archs/`:
# per-leaf sha256 (first 16 hex digits) of the bf16 weights of seed
# 2**33 + 7 at smoke widths (both families have the same shapes there),
# and the reference's gaps on a fixed prompt and served pair.
PARENT_HASHES = {
    "embed": "10bd61293330a6a0",
    "final_norm/w": "ad13c5c0d57371a1",
    "lm_head": "9fcd0ccd559c9aea",
    "seg0/attn/wk": "40f6706cf0430837",
    "seg0/attn/wo": "48384ef94ca9c3e6",
    "seg0/attn/wq": "82cbb8edfcc7056b",
    "seg0/attn/wv": "96bffaeb337bb137",
    "seg0/ffn/wi": "9af9e5e2061bc109",
    "seg0/ffn/wo": "a6c34d3307dcb8ee",
    "seg0/ln1/w": "a37632c610d4bc9c",
    "seg0/ln2/w": "d40195d6904ddbd0",
}
PARENT_SCORES = {
    "llama": dict(
        gap=[4.257950305938721, 1.7495543956756592, 0.9081580638885498,
             3.3414134979248047, 2.9046013355255127, 3.850714683532715,
             1.8516474962234497, 2.984084129333496, 0.5517978668212891],
        probe_gap=[2.9549195766448975, 2.1043667793273926,
                   1.3584144115447998, 3.2648253440856934,
                   2.9046013355255127, 3.0645406246185303,
                   3.5413286685943604, 1.9309966564178467,
                   3.9160401821136475],
        quant_gap=[4.145005702972412, 1.9181628227233887,
                   1.0638808012008667, 3.2406482696533203,
                   2.7785019874572754, 3.682046890258789,
                   1.7287548780441284, 2.95977520942688,
                   0.46192336082458496],
        top=[214, 33, 136, 133, 133, 133, 147, 164, 222]),
    "chatglm": dict(
        gap=[4.099785804748535, 1.736113429069519, 1.1973998546600342,
             3.249359607696533, 2.27555251121521, 3.0378270149230957,
             2.31323504447937, 3.8628058433532715, 0.47292518615722656],
        probe_gap=[2.131572723388672, 1.7394540309906006,
                   1.4640142917633057, 2.9218785762786865,
                   2.27555251121521, 2.2939538955688477,
                   3.7443833351135254, 2.132617473602295,
                   4.14630126953125],
        quant_gap=[3.978684663772583, 1.8952633142471313,
                   1.1616389751434326, 3.378415107727051,
                   2.1922459602355957, 2.8576700687408447,
                   2.2391202449798584, 3.8588972091674805,
                   0.4310488700866699],
        top=[192, 32, 136, 133, 37, 133, 199, 164, 222]),
}


@pytest.mark.parametrize("config", [smoke.CONFIG, smoke.GLM_CONFIG],
                         ids=["llama", "chatglm"])
def test_weights_and_reference_equal_the_parents(config):
    m = dims(config)
    params = make_params(m, 2 ** 33 + 7)
    assert _leaf_hashes(params) == PARENT_HASHES
    want = PARENT_SCORES[config["model_type"]]
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, m.vocab, 37).tolist()
    served = rng.integers(1, m.vocab, 9).tolist()
    out = reference.score(m, params, prompt, served, probe=served[::-1])
    ctrl = reference.score(m, params, prompt, served, quant=True)
    for got, key in ((out["gap"], "gap"), (out["probe_gap"], "probe_gap"),
                     (ctrl["gap"], "quant_gap")):
        np.testing.assert_allclose(got, want[key], rtol=0, atol=1e-6)
    assert out["top"].tolist() == want["top"]


# every cost at published widths, recorded from the same commit (the
# numbers that `test_costs.py` derives by hand among them)
PARENT_COSTS = {
    "yi-6b": dict(kv_bytes_per_token=65536, weight_bytes=11597783040,
                  prefill_flops=20877827637248.0,
                  attention_decode_flops=1258291200.0,
                  attention_decode_bytes=158859264.0,
                  decode_step_flops=36050042880.0,
                  decode_step_bytes=11755674624.0),
    "chatglm3-6b": dict(kv_bytes_per_token=28672, weight_bytes=11954233344,
                        prefill_flops=21400076681216.0,
                        attention_decode_flops=1101004800.0,
                        attention_decode_bytes=70189056.0,
                        decode_step_flops=36962304000.0,
                        decode_step_bytes=12023546880.0),
}


@pytest.mark.parametrize("name", sorted(PARENT_COSTS))
def test_costs_equal_the_parents(name):
    m = dims(json.loads((CFG / f"{name}.json").read_text()))
    ctx = [100, 300, 2000]
    got = dict(kv_bytes_per_token=m.kv_bytes_per_token,
               weight_bytes=costs.weight_bytes(m),
               prefill_flops=costs.prefill_flops(m, 1808),
               attention_decode_flops=costs.attention_decode_flops(m, ctx),
               attention_decode_bytes=costs.attention_decode_bytes(m, ctx),
               decode_step_flops=costs.decode_step_flops(m, ctx),
               decode_step_bytes=costs.decode_step_bytes(m, ctx))
    assert got == PARENT_COSTS[name]


LLAMA_ELSEWHERE = "llama_elsewhere"


@pytest.fixture
def arch_in_tmp(tmp_path, monkeypatch):
    """The llama module, copied under another `model_type` into a
    directory of its own that is put on `chipbench.archs`' search path."""
    d = tmp_path / "more_archs"
    d.mkdir()
    (d / f"{LLAMA_ELSEWHERE}.py").write_text(
        (ARCHS / "llama.py").read_text())
    monkeypatch.setattr(archs, "__path__", [str(d), *archs.__path__])
    importlib.invalidate_caches()
    yield d
    sys.modules.pop(f"{archs.__name__}.{LLAMA_ELSEWHERE}", None)


def test_architecture_is_one_file(arch_in_tmp, tmp_path, no_disk_cache):
    config = dict(smoke.CONFIG, model_type=LLAMA_ELSEWHERE)
    m = dims(config)
    assert Path(archs.of(m).__file__).parent == arch_in_tmp
    root = smoke.make_root(tmp_path / "root", config)
    cell = spec.Spec(root).cell("smoke.cell")
    res = harness.run_cell(cell, seed=2 ** 32 + 77, seconds=1.5,
                           trace=False, t_start=time.perf_counter(),
                           log=lambda s: None)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == round(smoke.MIX["rate"] * 1.5)
