"""Restart cold-start measurement: a fresh process, warm compile cache.

    python -m benchmarks.run --quick     # writes BENCH_quick.json and
    python -m benchmarks.cold            # fills the cache it reads here

Run as its own top-level command AFTER the quick bench has exited, never
as a child of a process that has used JAX (on a TPU that parent would
hold the chip). For every backend in ``BENCH_quick.json`` this process
rebuilds the IDENTICAL index + workloads (same seeds/sizes via
``quick.build_workloads``), points a fresh Executor at the warm cache
(``quick.cache_root()``), and follows the supported restart path:
replay the recorded prewarm manifest at startup — every sticky tier and
(program, signature) loads from the warm disk cache OFF the serving
path, timed separately as ``prewarm_ms`` — then time the FIRST dispatch
of every spec, i.e. the latency a request actually sees after a
restart. The results are merged into ``BENCH_quick.json``
(``cold_warm_us_per_q`` per spec, ``cold_restart`` per backend). Any
failure — a missing manifest included — raises and exits non-zero.
"""
from __future__ import annotations

import json
import os
import time


def probe(w: dict, backend: str, cache_dir: str) -> dict:
    import jax
    from repro.core import EngineConfig, Executor
    from repro.core.compile_cache import load_manifest

    ex = Executor(w["index"], config=EngineConfig(
        backend=backend, compile_cache_dir=cache_dir))
    man_path = os.path.join(cache_dir, f"prewarm_quick_{backend}.json")
    man = load_manifest(man_path)
    if man is None:
        raise FileNotFoundError(
            f"no prewarm manifest at {man_path}: run "
            "`python -m benchmarks.run --quick` first")
    t0 = time.perf_counter()
    pre = ex.prewarm(man, exercise=True)
    prewarm_ms = (time.perf_counter() - t0) * 1e3
    specs = {}
    for name, spec, args, denom in w["workload"]:
        t0 = time.perf_counter()
        jax.block_until_ready(ex.run(spec, *args))
        specs[name] = round((time.perf_counter() - t0) * 1e6 / denom, 2)
    st = ex.stats()
    return {"specs": specs,
            "prewarm_ms": round(prewarm_ms, 1),
            "prewarm_compiled": pre.get("compiled", 0),
            "disk_cache_hits": st["disk_cache_hits"],
            "disk_cache_misses": st["disk_cache_misses"]}


def main() -> None:
    out = os.environ.get("BENCH_QUICK_OUT", "BENCH_quick.json")
    with open(out) as f:
        rep = json.load(f)
    # the quick bench's sizes, set before benchmarks.common is imported
    os.environ["BENCH_N"] = str(rep["bench_n"])
    os.environ["BENCH_Q"] = str(rep["bench_q"])
    from benchmarks.common import emit
    from benchmarks.quick import build_workloads, cache_root

    w = build_workloads()
    root = cache_root()
    for backend, br in rep["backends"].items():
        cold = probe(w, backend, root)
        for name, us in cold["specs"].items():
            br["specs"][name]["cold_warm_us_per_q"] = us
            emit(f"quick/{backend}/{name}/cold_warm", us)
        br["cold_restart"] = {k: v for k, v in cold.items()
                              if k != "specs"}
    rep["specs"] = rep["backends"][rep["backend_default"]]["specs"]
    with open(out, "w") as f:
        json.dump(rep, f, indent=2, sort_keys=True)
    print(f"# merged the restart probe into {out}")


if __name__ == "__main__":
    main()
