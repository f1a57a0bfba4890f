"""The benchmark's data, found by name.

`BENCHMARK.json` names the cells; each cell names a configuration
(`chipbench/configs/<name>.json`, through the `file` of its entry) and a
traffic mix (`chipbench/traffic/<name>.json`). A per-layer metric is read
by `chipbench/metrics/<name>.py`, and a configuration's architecture is
`chipbench/archs/<model_type>.py` (`archs.load`). Nothing here is specific
to one cell, so a later cell, mix, configuration, architecture or metric
is a new file and a new entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    metrics_dir: Path


class Spec:
    """`BENCHMARK.json` under `root`, and the files it names."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / HERE.name

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.data["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        w = cells[name]
        cfg_entry = next(c for c in self.data["configs"]
                         if c["name"] == w["config"])
        config = json.loads((self.root / cfg_entry["file"]).read_text())
        traffic = json.loads(
            (self.dir / "traffic" / f"{w['traffic']}.json").read_text())
        e2e = [m for m in self.data["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        layer = [m for m in self.data["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
        return Cell(name, w["config"], w["traffic"], int(w["chips"]),
                    config, traffic, e2e, layer, self.dir / "metrics")


def metric_reader(metrics_dir: Path, name: str
                  ) -> Callable[[object], Optional[float]]:
    """The `read(run)` function of `<metrics_dir>/<name>.py`."""
    path = Path(metrics_dir) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return table[device_kind]
