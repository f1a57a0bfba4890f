"""ChatGLM decoders: rotary embeddings on the first half of each head
(`rotary_dim = kv_channels // 2`), in adjacent pairs, with base 10000,
which the published `config.json` does not state (`assumed.rope_theta`).

Departure from the published model: ChatGLM3's query/key/value biases are
absent, in the reference as in the served program (see `departures` in
the configuration)."""
from __future__ import annotations

from ._dense import (Dims, attention_decode_bytes,  # noqa: F401
                     attention_decode_flops, check_program,
                     decode_step_bytes, decode_step_flops, fan_in, logits,
                     param_shapes, prefill_flops, weight_bytes)


# the configuration at the program's smoke widths, for tests on the CPU
SMOKE = {
    "model_type": "chatglm", "num_layers": 4, "hidden_size": 64,
    "ffn_hidden_size": 128, "num_attention_heads": 4,
    "multi_query_group_num": 2, "kv_channels": 16, "padded_vocab_size": 256,
    "layernorm_epsilon": 1e-05, "assumed": {"rope_theta": 10000.0},
    "serving": {"arch": "chatglm3-6b-smoke"},
}


def dims(cfg: dict) -> Dims:
    """The model's dimensions from its published `config.json` keys."""
    return Dims(model_type=cfg["model_type"], layers=int(cfg["num_layers"]),
                d=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["multi_query_group_num"]),
                hd=int(cfg["kv_channels"]), ff=int(cfg["ffn_hidden_size"]),
                vocab=int(cfg["padded_vocab_size"]),
                eps=float(cfg["layernorm_epsilon"]),
                rope_theta=float(cfg["assumed"]["rope_theta"]),
                rope_fraction=0.5, rope_interleaved=True)
