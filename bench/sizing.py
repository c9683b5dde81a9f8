#!/usr/bin/env python3
"""Size a deployment drawn on the device before it has a cell: time the
draw of its points over the chips and read their bytes on each chip,
then time the reference over candidate windows (``bench/windows.py``)
on the checked requests of a run of the cell's traffic, with the
reference's own answers in the program's place (they have to come out
right), and the bfloat16 control's (they have to come out wrong). No
index is built. One JSON line per seed.

    python3 bench/sizing.py --workload spider-gaussian.interactive-counts \\
        --generator spider_gaussian_device --points 1000000000 --chips 4 \\
        --seconds 30 --seeds 11,12
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])
from bench import check, deploy, gen, windows  # noqa: E402
from bench import run as R  # noqa: E402


def readings(cell, mesh, seed: int, seconds: float) -> dict:
    import jax
    import ml_dtypes
    cfg = cell.cfg
    out = {"seed": seed, "points": int(cfg["points"])}
    t = time.perf_counter()
    x, y = deploy.points(cfg, int(cfg["points"]), seed, str(cell.root),
                         mesh)
    jax.block_until_ready((x, y))
    out["draw_s"] = time.perf_counter() - t
    out["bytes_by_chip"] = [int((d.memory_stats() or {}).get(
        "bytes_in_use", 0)) for d in mesh.devices.flat]
    t = time.perf_counter()
    g = gen.Generator(cell.traffic, *R.centres(x, y, seed), str(cell.root))
    out["centres_s"] = time.perf_counter() - t
    reqs, _due = R.streams(g, cell.traffic, seed, seconds,
                           trace=False)["main"]
    keep = R.sample(reqs, cell.traffic, seed)
    answers = [(reqs[i], None, True) for i in sorted(keep)]
    t = time.perf_counter()
    ref = windows.Reference(x, y, [r for r, _, _ in answers])
    out["candidates_s"] = time.perf_counter() - t
    out["candidates"] = int(len(ref.ids))
    t = time.perf_counter()
    sound = check.compare(answers, ref, control=ref)
    out["judge_s"] = time.perf_counter() - t
    out["reference_s"] = out["candidates_s"] + out["judge_s"]
    out["sound"] = sound.counts
    t = time.perf_counter()
    ctl = check.compare(answers, ref,
                        control=ref.lowered(ml_dtypes.bfloat16))
    out["control_s"] = time.perf_counter() - t
    out["control"] = dict(ctl.counts, wrong_by_family=ctl.wrong)
    out["peak_by_chip"] = R.memory_peaks(mesh.devices.flat)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="the cell whose configuration and traffic to use")
    ap.add_argument("--generator", required=True,
                    help="a bench/data module with device_points")
    ap.add_argument("--points", type=int, required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE)
    sys.path.insert(0, str(R.SRC))
    import jax
    cell = R.Cell(args.workload)
    cell.cfg = dict(cell.cfg, points=args.points,
                    generator=dict(cell.cfg["generator"],
                                   kind=args.generator))
    mesh = jax.make_mesh((args.chips,), ("data",),
                         devices=jax.devices()[:args.chips])
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, mesh, int(s), args.seconds)),
              flush=True)


if __name__ == "__main__":
    main()
