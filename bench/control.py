#!/usr/bin/env python3
"""The control of a cell's comparison, at the cell's own size: the
plain reference, with points and query arguments in bfloat16 (the
precision below the configuration's float32), put in the program's
place over the same requests a run sends and samples. The check has to
find it wrong; this prints what it reads, one JSON line per seed.

    python3 bench/control.py --workload spider-gaussian.interactive \\
        --seconds 30 --seeds 11,12,13

Points drawn on the host need no chip (the reference runs on the
host); points drawn on the device are drawn over the cell's chips, and
both sides are judged on the same candidate windows
(``bench/windows.py``). The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])
from bench import check, deploy, gen, windows  # noqa: E402
from bench import run as R  # noqa: E402
from bench.oracle import Oracle  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    import jax
    import ml_dtypes
    cfg = cell.cfg
    k = min(int(cell.entry["chips"]), len(jax.devices()))
    mesh = jax.make_mesh((k,), ("data",), devices=jax.devices()[:k])
    x, y = deploy.points(cfg, int(cfg["points"]), seed, str(cell.root),
                         mesh)
    g = gen.Generator(cell.traffic, *R.centres(x, y, seed), str(cell.root))
    reqs, _due = R.streams(g, cell.traffic, seed, seconds,
                           trace=False)["main"]
    keep = R.sample(reqs, cell.traffic, seed)
    answers = [(reqs[i], None, True) for i in sorted(keep)]
    t = time.perf_counter()
    if deploy.on_device(x):
        orc = windows.Reference(x, y, [r for r, _, _ in answers])
        low = orc.lowered(ml_dtypes.bfloat16)
    else:
        orc, low = Oracle(x, y), Oracle(x, y, dtype=ml_dtypes.bfloat16)
    ctl = check.compare(answers, orc, control=low)
    return {"seed": seed, "control": "bfloat16 reference",
            "compared": ctl.counts["compared"],
            "wrong_answers": ctl.counts["wrong_answers"],
            "wrong_by_family": ctl.wrong,
            "seconds": time.perf_counter() - t}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(R.SRC))
    cell = R.Cell(args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), args.seconds)), flush=True)


if __name__ == "__main__":
    main()
