#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at a list of fixed rates, one after
another on one cluster, and report what each rate did.

    python3 chipbench/sweep.py --workload <cell> --rates 0.5,1,1.5 \
        --seconds 30 [--seed 1] [--out sweep.json]

The knee is the highest rate with no growing backlog: requests due that
have not reached decode (no second token), at the window's open and at
its close. Past it a queue grows, before prefill or, with the first token
already out, before decode. Attainment of the mix's limits is reported
beside it; where the chip cannot meet them for 90% of requests at any
rate, the backlog alone decides. The rate a cell runs at is then written
into its traffic file as a number. Prints one JSON line per rate.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def sweep(cell, rates, seconds, seed, log=print):
    from chipbench import harness
    s = harness.Session(cell, seed, T_START, log=log)
    rows = []
    for rate in rates:
        out = s.serve(dict(cell.traffic, rate=rate), seed, seconds,
                      time.perf_counter())
        v = out.values
        row = {"rate": rate, "due": out.attempted,
               "slo_attainment": v["slo_attainment"],
               "ttft_p90_ms": v["ttft_p90_ms"],
               "tpot_p90_ms": v["tpot_p90_ms"],
               "output_tok_per_s": v["output_tok_per_s"],
               "backlog_open": out.backlog["open"],
               "backlog_close": out.backlog["close"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from chipbench import spec
    cell = spec.Spec(ROOT).cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    rows = sweep(cell, [float(r) for r in args.rates.split(",")],
                 args.seconds, args.seed,
                 log=lambda s: print(s, file=sys.stderr, flush=True))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
