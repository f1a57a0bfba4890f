"""`chip_smoke.py`'s serving phases (3-5) at smoke width on the CPU.

The device check and the kernel phase need the chip; everything after
them is the same control flow at any width, so it is guarded here: 8 of 8
requests finish by length in every phase, the prefix/chunked phase
dispatches both fused and chunked prefills, and colocated greedy tokens
equal the disaggregated ones.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models.api import build_model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_serving_phases(chip_smoke):
    cfg = get_config("yi-6b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0), jnp.bfloat16)
    lines = []
    out = chip_smoke.serving_phases(
        cfg, params, seed=0, prompt_lens=(16, 20, 24, 32, 40, 48, 56, 64),
        system_len=32, suffix_lens=(17, 20, 24, 28, 32, 18, 22, 30),
        out_len=4, max_len=96, chunk_tokens=16, log=lines.append)
    assert len(lines) == 3
    for phase in ("disagg", "prefix", "colocated"):
        r = out[phase]
        assert r["finished"] == 8
        assert all(len(t) == 4 for t in r["tokens"])
    # off the chip the ops dispatch to their jnp oracles
    assert out["disagg"]["decode_kernel"] is False
    assert out["prefix"]["fused_dispatches"] > 0
    assert out["prefix"]["chunk_dispatches"] > 0
    assert out["colocated"]["tokens"] == out["disagg"]["tokens"]


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out
