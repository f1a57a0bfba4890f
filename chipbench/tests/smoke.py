"""A small cell for the CPU: yi-6b's wiring at smoke widths, under a short
mix, in a root of its own that holds a `BENCHMARK.json` naming it."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "source": "yi-6b-smoke: yi-6b's wiring at the program's smoke widths",
    "model_type": "llama",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "vocab_size": 256,
    "rope_theta": 5000000.0, "rms_norm_eps": 1e-05,
    "assumed": {"head_dim": 16},
    "serving": {"arch": "yi-6b-smoke", "dtype": "bfloat16",
                "roles": ["prefill", "decode"], "max_len": 256,
                "page_size": 16, "max_batch": 4, "decode_num_pages": 64,
                "prefill_num_pages": None, "attn_blocks": [64, 64]},
    # from 12 seeds on the CPU: the program's widest gap 0.0240, the fp8
    # control's narrowest 0.1298
    "check": {"logit_gap_limit": 0.06, "tokens": 120, "max_requests": 6,
              "min_tokens": 40},
}

MIX = {
    "prompt": {"mu": 3.5, "sigma": 0.6, "clip": [8, 100]},
    "output": {"mu": 2.7, "sigma": 0.4, "clip": [4, 32]},
    "rate": 12.0, "warm_s": 0.5, "grace_s": 3.0,
    "limits": {"ttft_s": 5.0, "tpot_s": 1.0},
    "trace": {"at_s": 0.2, "min_prefills": 1, "min_decode_steps": 5,
              "max_span_s": 1.0},
}


# chatglm3-6b's wiring (rotary on half of each head, in adjacent pairs)
# at the program's smoke widths
GLM_CONFIG = dict(
    {k: v for k, v in CONFIG.items()
     if k not in ("hidden_size", "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_hidden_layers", "vocab_size",
                  "rope_theta", "rms_norm_eps", "assumed")},
    source="chatglm3-6b-smoke: chatglm3-6b's wiring at smoke widths",
    model_type="chatglm", num_layers=4, hidden_size=64, ffn_hidden_size=128,
    num_attention_heads=4, multi_query_group_num=2, kv_channels=16,
    padded_vocab_size=256, layernorm_epsilon=1e-05,
    assumed={"rope_theta": 10000.0},
    serving=dict(CONFIG["serving"], arch="chatglm3-6b-smoke"))

PEAKS = {"flops": 1e12, "bytes_per_s": 1e11}   # nominal, for the CPU


def make_root(tmp: Path, config: dict = CONFIG, mix: dict = MIX) -> Path:
    """A root with the real metric readers and one cell, `smoke.cell`."""
    bench = tmp / BENCH.name
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    (bench / "configs" / "yi-6b-smoke.json").write_text(json.dumps(config))
    (bench / "traffic" / "smoke.json").write_text(json.dumps(mix))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    data = dict(real)
    data["configs"] = [{"name": "yi-6b-smoke", "source": CONFIG["source"],
                        "file": f"{BENCH.name}/configs/yi-6b-smoke.json",
                        "reduced": [], "why": "CPU smoke"}]
    data["workloads"] = [{"name": "smoke.cell", "config": "yi-6b-smoke",
                          "traffic": "smoke", "chips": 1, "why": "CPU"}]
    data["end_to_end"] = [dict(m, workloads=["smoke.cell"])
                          for m in real["end_to_end"]]
    data["per_layer"] = [dict(m, workloads=["smoke.cell"])
                         for m in real["per_layer"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(data))
    return tmp
