"""Llama-family decoders (Yi): rotary embeddings on the whole head, in two
halves, with the configuration's `rope_theta`."""
from __future__ import annotations

from ._dense import (Dims, attention_decode_bytes,  # noqa: F401
                     attention_decode_flops, check_program,
                     decode_step_bytes, decode_step_flops, fan_in, logits,
                     param_shapes, prefill_flops, weight_bytes)


# the configuration at the program's smoke widths, for tests on the CPU
SMOKE = {
    "model_type": "llama", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "vocab_size": 256, "rope_theta": 5000000.0,
    "rms_norm_eps": 1e-05, "assumed": {"head_dim": 16},
    "serving": {"arch": "yi-6b-smoke"},
}


def dims(cfg: dict) -> Dims:
    """The model's dimensions from its published `config.json` keys."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    assumed = cfg.get("assumed", {})
    hd = int(cfg.get("head_dim", assumed.get("head_dim", d // heads)))
    return Dims(model_type=cfg["model_type"],
                layers=int(cfg["num_hidden_layers"]), d=d, heads=heads,
                kv_heads=int(cfg["num_key_value_heads"]), hd=hd,
                ff=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
                rope_theta=float(cfg["rope_theta"]), rope_fraction=1.0,
                rope_interleaved=False)
