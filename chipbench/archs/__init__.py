"""What the benchmark knows of each architecture, one module per published
`model_type`.

A configuration file keeps the `model_type` of its model's published
`config.json`, and `load` imports `chipbench/archs/<model_type>.py` (the
package's `__path__` is the search path). Each such module supplies the
names in `INTERFACE`:

- `dims(cfg)`: the model's dimensions from its configuration file, a
  frozen, hashable object that carries at least `model_type`, `vocab` and
  `kv_bytes_per_token` (see `Dims`);
- `check_program(m, pcfg)`: raises `SystemExit` unless the program's
  `ModelConfig` has the file's widths and family;
- `param_shapes(m)`: the serving program's parameter tree as shapes, with
  the layers of each segment `seg<i>` stacked on the leading axis; and
  `fan_in(path, shape)`: the fan-in of the matrix at `path` ("seg0/attn/wq");
- `logits(m, params, tokens, quant)`: the plain reference forward pass over
  one padded sequence, float32 logits of every row, its matrix products
  through `reference._mm`;
- the operations and bytes of the served work, as `costs.py` gives them;
- `SMOKE`: a configuration file of the architecture at the program's
  smoke widths, with `serving.arch` naming that program entry, on which
  `tests/test_archs.py` runs every function above on the CPU.

`model.py`, `reference.py`, `costs.py` and `harness.py` keep what every
architecture shares and reach the rest through `of(m)`. A new
architecture is one new file; a module whose name starts with `_` holds
code that several share and is no architecture.
"""
from __future__ import annotations

import importlib
from typing import Protocol

INTERFACE = ("dims", "check_program", "param_shapes", "fan_in", "logits",
             "weight_bytes", "prefill_flops", "attention_decode_flops",
             "attention_decode_bytes", "decode_step_flops",
             "decode_step_bytes", "SMOKE")


class Dims(Protocol):
    """What the architecture-independent code reads of a dimensions
    object."""
    model_type: str
    vocab: int

    @property
    def kv_bytes_per_token(self) -> int: ...


def load(model_type: str):
    """The module of `model_type`; a `KeyError` naming the file looked for
    where there is none."""
    name = f"{__name__}.{model_type}"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name is None or not (name + ".").startswith(e.name + "."):
            raise                       # the module exists and fails inside
        raise KeyError(f"no architecture for model_type {model_type!r}: "
                       f"chipbench/archs/{model_type}.py not found") from None
    missing = [k for k in INTERFACE if not hasattr(mod, k)]
    if missing:
        raise TypeError(f"chipbench/archs/{model_type}.py lacks {missing}")
    return mod


def of(m: Dims):
    """The module of a dimensions object."""
    return load(m.model_type)
