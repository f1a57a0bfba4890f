#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 chipbench/limits.py --workload <cell> --seeds 11,12,... \
        --seconds 30 [--warm-s 5] [--out limits.json]

For each seed, in one process: one run of the cell at its own load, then
the check's number for the program (the widest gap of a served token below
the reference's best logit) and for the fp8 control at the same positions
(the gap of the token the control puts first), each judged by the check's
own `verdict`: the program has to come out correct and the control not. The limit lies between the
largest program reading and the smallest control reading. Prints one JSON
line per seed and writes them all to `--out`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def read_limits(cell, seeds, seconds, log=print):
    from chipbench import harness
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                               t_start=t0, log=log, control=True)
        ctrl = res["control"]
        row = {"seed": seed, "correct": res["correct"],
               "program_gap": res["checks"]["logit_gap_max"]["value"],
               "control_correct": ctrl["correct"],
               "control_gap": ctrl["checks"]["logit_gap_max"]["value"],
               "tokens": ctrl["checks"]["tokens_compared_min"]["value"],
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warm-s", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from chipbench import spec
    cell = spec.Spec(ROOT).cell(args.workload)
    if args.warm_s is not None:
        cell = dataclasses.replace(
            cell, traffic=dict(cell.traffic, warm_s=args.warm_s))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("limits: needs a TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = read_limits(cell, seeds, args.seconds,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
    summary = {"workload": args.workload,
               "program_gap_max": max(r["program_gap"] for r in rows),
               "control_gap_min": min(r["control_gap"] for r in rows),
               "all_correct": all(r["correct"] for r in rows),
               "control_never_correct": not any(r["control_correct"]
                                                for r in rows)}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, **summary},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
