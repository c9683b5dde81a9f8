#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one set-up, then a window at
each offered rate, printing the latencies and whether a backlog grew.

    python3 bench/sweep.py --workload spider-gaussian.interactive \\
        --seed 3 --seconds 6 --rates 200,400,800,1200

A rate is sustained when every request completes and the p95 of the
last third of the window is within twice that of the first third (no
growing backlog). The knee found is written into the
traffic file by hand; runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])
from bench import run as R  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--late", type=float, default=10.0,
                    help="seconds past a window to wait for answers")
    args = ap.parse_args()
    import numpy as np
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE)
    sys.path.insert(0, str(R.SRC))
    cell = R.Cell(args.workload)
    devs = R.devices(int(cell.entry["chips"]), True)
    rates = [float(r) for r in args.rates.split(",")]
    cell.traffic["rate"] = rates[0]      # set-up's warm traffic
    R.drive.LATE_S = args.late
    st = R.Setup(cell, args.seed, args.seconds, False, devs)
    for k, rate in enumerate(rates):
        cell.traffic["rate"] = rate
        st.streams["main"] = st.gen.stream(args.seconds,
                                           R.seed_of(args.seed, 20 + k))
        c0, k0 = R.counters(st), R.programs(st.ex)
        t = time.perf_counter()
        lg = st.window("main", args.seconds)
        wall = time.perf_counter() - t
        d = R.delta(c0, R.counters(st))
        realized = len(R.programs(st.ex) - k0)
        e = R.end_to_end(lg, args.seconds, 0.0)
        n = lg.issued
        lat = (lg.done[:n] - lg.due[:n]) * 1e3
        third = n // 3
        first = float(np.nanpercentile(lat[:third], 95))
        last = float(np.nanpercentile(lat[-third:], 95))
        ok = not np.isnan(lat).any() and last <= 2 * first + 1
        print(json.dumps({"rate": rate, "sustained": bool(ok),
                          "wall_s": wall, "programs_realized": realized,
                          "p95_first_third": first,
                          "p95_last_third": last, **e,
                          "counters": d}), flush=True)
        R.log(R.describe(lg, args.seconds))
        if not ok:
            break
    st.sched.close()


if __name__ == "__main__":
    main()
