"""`costs` against arithmetic done by hand at both configurations' widths."""
import json
from pathlib import Path

import pytest

from chipbench import costs
from chipbench.model import dims

CFG = Path(__file__).resolve().parents[1] / "configs"
PEAK = {"flops": 197e12, "bytes_per_s": 819e9}


def _dims(name):
    return dims(json.loads((CFG / f"{name}.json").read_text()))


def test_yi6b_shapes():
    m = _dims("yi-6b")
    # q, k, v: 4096 x (32 + 2 * 4) x 128; o: 32 x 128 x 4096;
    # gate, up, down: 3 x 4096 x 11008
    layer = 20_971_520 + 16_777_216 + 135_266_304
    assert m.kv_bytes_per_token == 65_536                 # 64 KiB
    # 32 layers with two norms, final norm, output head; bf16
    assert costs.weight_bytes(m) == 2 * (32 * (layer + 8192) + 4096
                                         + 4096 * 64_000)
    assert costs.weight_bytes(m) == 11_597_783_040
    # 1808-token prompt: 2 x 1808 x layer per layer, causal attention
    # 2 x 2 x 32 x 128 x 1808 x 1809 / 2 per layer, one output row
    want = 32 * (2 * 1808 * layer + 2 * 2 * 32 * 128 * 1808 * 1809 / 2) \
        + 2 * 4096 * 64_000
    assert costs.prefill_flops(m, 1808) == pytest.approx(want, rel=1e-12)
    assert costs.prefill_flops(m, 1808) == pytest.approx(2.10e13, rel=0.01)


def test_chatglm3_shapes():
    m = _dims("chatglm3-6b")
    assert (m.layers, m.heads, m.kv_heads, m.hd, m.ff, m.vocab) == \
        (28, 32, 2, 128, 13696, 65024)
    assert m.kv_bytes_per_token == 28_672                 # 28 KiB
    layer = 4096 * 36 * 128 + 32 * 128 * 4096 + 3 * 4096 * 13696
    assert costs.weight_bytes(m) == 2 * (28 * (layer + 8192) + 4096
                                         + 4096 * 65024)
    assert costs.weight_bytes(m) == pytest.approx(11.98e9, rel=0.01)


def test_decode_step_counts_live_tokens_only():
    m = _dims("chatglm3-6b")
    ctx = [100, 300, 2000]
    kv = (100 + 300 + 2000) * 28_672
    per_seq = 2 * (4096 + 65024) + 28_672
    assert costs.decode_step_bytes(m, ctx) == \
        costs.weight_bytes(m) + 3 * per_seq + kv
    attn = 28 * 4 * 32 * 128 * 2400
    assert costs.attention_decode_flops(m, ctx) == attn
    # keys and values of 2400 live tokens, per layer 2 x 2 x 128 x 2 bytes,
    # plus each sequence's query and output
    assert costs.attention_decode_bytes(m, ctx) == \
        28 * (2400 * 1024 + 3 * 2 * 32 * 128 * 2)
    # a decode step is bound by bandwidth
    f = costs.decode_step_flops(m, ctx)
    b = costs.decode_step_bytes(m, ctx)
    assert costs.roofline_s(f, b, PEAK) == b / PEAK["bytes_per_s"]


def test_prefill_is_compute_bound_at_peak():
    m = _dims("yi-6b")
    f = costs.prefill_flops(m, 4096)
    assert costs.roofline_s(f, costs.weight_bytes(m), PEAK) == \
        f / PEAK["flops"]
