"""Benchmark suite entry: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only rq1,...]``
Emits ``name,us_per_call,derived`` CSV lines.

``PYTHONPATH=src python -m benchmarks.run --quick``
Smoke mode: tiny BENCH_N/BENCH_Q, every QuerySpec through the unified
executor, writes BENCH_quick.json (see tools/check.sh).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

MODULES = ["rq1_overall", "rq2_partitioners", "rq3_datasets",
           "rq4_selectivity", "rq4_knn_k", "rq5_build"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: tiny sizes, all QuerySpecs, "
                         "emit BENCH_quick.json")
    ap.add_argument("--traffic", action="store_true",
                    help="mixed read/write traffic through the serve "
                         "scheduler only (coalesced vs serial qps, "
                         "p50/p99 latency, ingest ops/s)")
    ap.add_argument("--crossover", action="store_true",
                    help="measure the query_shard_threshold crossover "
                         "(sharded vs unsharded) and record the pick "
                         "in BENCH_quick.json")
    ap.add_argument("--backend", default=None,
                    choices=["auto", "xla", "pallas"],
                    help="kernel backend for the lilis engines "
                         "(--quick always benchmarks every backend)")
    args = ap.parse_args()
    if args.backend:
        # must be set before benchmarks.common is imported
        os.environ["BENCH_BACKEND"] = args.backend
    picked = MODULES
    if args.quick:
        # must be set before benchmarks.common is imported
        os.environ.setdefault("BENCH_N", "20000")
        os.environ.setdefault("BENCH_Q", "16")
        os.environ.setdefault("BENCH_REPEAT", "1")
        picked = ["quick"]
    elif args.traffic:
        os.environ.setdefault("BENCH_N", "20000")
        picked = ["traffic"]
    elif args.crossover:
        # multi-device host platform BEFORE jax initializes
        os.environ.setdefault("BENCH_N", "20000")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
        picked = ["crossover"]
    elif args.only:
        pre = args.only.split(",")
        picked = [m for m in MODULES if any(m.startswith(p) for p in pre)]
    print("name,us_per_call,derived")
    failures = 0
    for name in picked:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            print(f"# {name} done in {time.time()-t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
