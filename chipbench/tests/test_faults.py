"""The check fails what it has to fail, here at smoke widths on the CPU.

Each fault breaks the timed path underneath an otherwise whole run, past
the harness's look for a chip, and `correct` has to come out false. The
control, the reference in fp8 put in the program's place, has to come out
not correct through the same comparison that passes the program."""
import time

import jax.numpy as jnp
import pytest

from chipbench import harness, spec
from chipbench.limits import read_limits
from chipbench.tests import smoke


def _alter_token(mp):
    from repro.serving.engine import Sequence
    orig = Sequence.append_token
    mp.setattr(Sequence, "append_token",
               lambda self, tok: orig(self, (tok + 1) % 256))


def _state_unchanged(mp):
    from repro.models.api import Model
    orig = Model.decode_step_paged

    def step(self, params, cache, tokens):
        logits, _new = orig(self, params, cache, tokens)
        return logits, cache
    mp.setattr(Model, "decode_step_paged", step)


def _half_batch(mp):
    from repro.models.api import Model
    orig = Model.decode_step_paged

    def step(self, params, cache, tokens):
        return orig(self, params, cache, tokens.at[1::2].set(0))
    mp.setattr(Model, "decode_step_paged", step)


def _no_kv_handoff(mp):
    from repro.serving.engine import Engine
    orig = Engine.insert_kv

    def insert(self, seq, blob, *a, **kw):
        cache, n = blob
        cache = {k: ({p: jnp.zeros_like(x) for p, x in v.items()}
                     if k.startswith("seg") else v) for k, v in cache.items()}
        return orig(self, seq, (cache, n), *a, **kw)
    mp.setattr(Engine, "insert_kv", insert)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch, _no_kv_handoff])
def test_fault_fails_the_check(fault, tmp_path, monkeypatch, no_disk_cache):
    fault(monkeypatch)
    cell = spec.Spec(smoke.make_root(tmp_path)).cell("smoke.cell")
    res = harness.run_cell(cell, seed=2 ** 32 + 9, seconds=1.5, trace=False,
                           t_start=time.perf_counter(), log=lambda s: None)
    c = res["checks"]
    assert res["correct"] is False
    assert c["logit_gap_max"]["value"] > c["logit_gap_max"]["limit"]


def test_control_fails_where_the_program_passes(tmp_path, no_disk_cache):
    cell = spec.Spec(smoke.make_root(tmp_path)).cell("smoke.cell")
    limit = cell.config["check"]["logit_gap_limit"]
    rows = read_limits(cell, [2 ** 33 + 101, 7, 12345], 3.0,
                       log=lambda s: None)
    for r in rows:
        assert r["correct"] and r["program_gap"] <= limit
        assert r["control_correct"] is False and r["control_gap"] > limit
