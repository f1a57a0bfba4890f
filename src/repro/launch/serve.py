"""Serving launcher.

Runs the placement search for the arch + workload and prints the chosen
placement. With `--live` it then serves a sampled trace of that workload
on a live disaggregated cluster of the given arch, in bf16 with random
weights, on the default device (all instances share it).
A full-width arch needs the chip; on a CPU pass a `-smoke` arch:

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
        --workload sharegpt --rate 8 [--live]
    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b-smoke --live
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..core import hw
from ..core.latency_model import LatencyModel
from ..core.placement import algo1_high_affinity, algo2_low_affinity
from ..core.workload import WORKLOADS, derive_slos, sample_requests
from ..models.api import build_model
from ..serving.cluster import ServingCluster
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--workload", default="sharegpt",
                    choices=list(WORKLOADS))
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--algo", default="low", choices=["low", "high"])
    ap.add_argument("--n-node", type=int, default=2)
    ap.add_argument("--m-per-node", type=int, default=8)
    ap.add_argument("--n-requests", type=int, default=200)
    ap.add_argument("--live", action="store_true",
                    help="also serve a sampled trace on a live cluster")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    lm = LatencyModel(cfg, hw.V5E)
    spec = derive_slos(WORKLOADS[args.workload], lm)
    search = algo2_low_affinity if args.algo == "low" else algo1_high_affinity
    placement = search(lm, spec, rate=args.rate, n_node=args.n_node,
                       m_per_node=args.m_per_node,
                       n_requests=args.n_requests)
    print("placement (predicted by LatencyModel from core/hw.py peaks, "
          "not measured):")
    print(json.dumps(placement.summary(), indent=1))

    if args.live:
        enable_compile_cache()
        params = build_model(cfg).init(jax.random.PRNGKey(0),
                                       jnp.bfloat16)
        trace = sample_requests(spec, args.rate, 12, seed=0)
        roles = (["prefill"] * min(placement.n_prefill, 2)
                 + ["decode"] * min(placement.n_decode, 2))
        cluster = ServingCluster(
            cfg, params, roles, max_batch=4,
            max_len=max(r.in_len + r.out_len for r in trace) + 1)
        res = cluster.run(trace)
        ttfts = sorted(r.ttft for r in res.values())
        print(f"[live] {cfg.name} bf16 on {jax.devices()[0].device_kind}: "
              f"served {len(res)} requests; "
              f"median ttft {ttfts[len(ttfts) // 2] * 1e3:.0f} ms; "
              f"KV migrated {cluster.tx.total_bytes / 1e6:.1f} MB")


if __name__ == "__main__":
    main()
