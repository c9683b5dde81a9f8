"""Quick smoke benchmark: every QuerySpec through the unified executor.

Runs in seconds on tiny BENCH_N/BENCH_Q (set by ``run.py --quick``),
timing each spec cold (compile + sticky settle) and steady (fused
zero-sync path) on EVERY kernel backend (xla reference + pallas, the
latter in interpret mode off-TPU), and writes ``BENCH_quick.json`` —
the perf-trajectory artifact a CI check diffs across PRs
(tools/check.sh fails on a >25% steady-state regression of the default
backend vs the committed file).
"""
from __future__ import annotations

import json
import os
import shutil
import time

import jax
import numpy as np

from benchmarks.common import BENCH_N, BENCH_Q, emit
from repro.core import (CircleQuery, DeleteBatch, EngineConfig, Executor,
                        InsertBatch, Knn, PointQuery, RangeCount,
                        RangeQuery, SpatialJoin, build_index, fit,
                        resolve_backend)
from repro.data import spatial as ds

OUT = os.environ.get("BENCH_QUICK_OUT", "BENCH_quick.json")


def cache_root() -> str:
    """Persistent compiled-executable cache of the bench executors
    (DESIGN.md §14): ``BENCH_CACHE_DIR``, else the fixed ``bench_quick``
    subdirectory of the program's cache root, which ``main`` empties
    first so the in-process cold_us_per_q measures the empty-cache cold
    path; ``python -m benchmarks.cold``, run after this module exits,
    measures the warm-cache restart against it."""
    from repro.core.compile_cache import default_cache_root
    return (os.environ.get("BENCH_CACHE_DIR")
            or os.path.join(default_cache_root(), "bench_quick"))


def build_workloads():
    """The deterministic index + query workloads the quick bench times.

    Shared with ``benchmarks.cold``, which re-derives the IDENTICAL
    programs in a second process (same seeds, same shapes) to measure
    restart cold-start against a warm compile cache."""
    x, y = ds.make("taxi", BENCH_N, seed=0)
    t0 = time.perf_counter()
    part = fit("kdtree", x, y, min(16, BENCH_N // 256 or 1), seed=0)
    index = build_index(x, y, part)
    jax.block_until_ready(index.key)
    build_ms = (time.perf_counter() - t0) * 1e3

    rng = np.random.default_rng(1)
    q = BENCH_Q
    ix = rng.integers(0, BENCH_N, q)
    qx, qy = x[ix], y[ix]
    rects = ds.random_rects(q, 1e-4, part.bounds, seed=2, centers=(x, y))
    polys, ne = ds.random_polygons(max(q // 8, 4), part.bounds, seed=3)
    r = np.full(q, 0.02, np.float32)

    workload = [
        ("point", PointQuery(), (qx, qy), q),
        ("range_count", RangeCount(), (rects,), q),
        ("range", RangeQuery(), (rects,), q),
        ("circle", CircleQuery(), (qx, qy, r), q),
        ("circle_mat", CircleQuery(materialize=True), (qx, qy, r), q),
        ("knn10", Knn(k=10), (qx, qy), q),
        ("knn10_exact", Knn(k=10, mode="exact"), (qx, qy), q),
        ("join", SpatialJoin(), (polys, ne), len(ne)),
    ]

    # wide-batch column (q=256): the exact-scan specs would dominate
    # wall-clock without adding compaction signal (their work is
    # already ~linear in the batch)
    q2 = 256
    ix2 = rng.integers(0, BENCH_N, q2)
    qx2, qy2 = x[ix2], y[ix2]
    rects2 = ds.random_rects(q2, 1e-4, part.bounds, seed=4,
                             centers=(x, y))
    r2 = np.full(q2, 0.02, np.float32)
    # join at a smaller polygon count: each polygon is a full window
    # scan, so q2 polygons would swamp the column — 32 is wide enough
    # to exercise bucketed dispatch and the monotonicity gate
    polys2, ne2 = ds.random_polygons(32, part.bounds, seed=6)
    # circle_mat last: its candidate planes are the widest of any spec
    # and evict the cache — timing a lighter spec right after it reads
    # the re-warm cost, not the steady cost
    workload256 = [
        ("point", PointQuery(), (qx2, qy2), q2),
        ("range_count", RangeCount(), (rects2,), q2),
        ("range", RangeQuery(), (rects2,), q2),
        ("circle", CircleQuery(), (qx2, qy2, r2), q2),
        ("knn10", Knn(k=10), (qx2, qy2), q2),
        ("join", SpatialJoin(), (polys2, ne2), len(ne2)),
        ("circle_mat", CircleQuery(materialize=True), (qx2, qy2, r2),
         q2),
    ]

    q4 = 4096
    ix4 = rng.integers(0, BENCH_N, q4)
    qx4, qy4 = x[ix4], y[ix4]
    rects4 = ds.random_rects(q4, 1e-4, part.bounds, seed=5,
                             centers=(x, y))
    workload4k = [
        ("point", PointQuery(), (qx4, qy4), q4),
        ("range", RangeQuery(), (rects4,), q4),
        ("knn10", Knn(k=10), (qx4, qy4), q4),
    ]
    return {"x": x, "y": y, "part": part, "index": index,
            "build_ms": build_ms, "q": q, "q2": q2, "q4": q4,
            "workload": workload, "workload256": workload256,
            "workload4k": workload4k}


def bench_backend(index, backend: str, workload, workload256,
                  workload4k, cache_dir=None) -> dict:
    ex = Executor(index, config=EngineConfig(
        backend=backend, compile_cache_dir=cache_dir))
    specs = {}
    for name, spec, args, denom in workload:
        t0 = time.perf_counter()
        jax.block_until_ready(ex.run(spec, *args))
        cold = (time.perf_counter() - t0) * 1e6 / denom
        syncs0 = ex.host_syncs
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(ex.run(spec, *args))
            best = min(best, time.perf_counter() - t0)
        steady = best * 1e6 / denom
        specs[name] = {
            "cold_us_per_q": round(cold, 2),
            "steady_us_per_q": round(steady, 2),
            "steady_host_syncs": ex.host_syncs - syncs0,
        }
        emit(f"quick/{backend}/{name}/steady", steady)
    # q=256 batch column: compaction gains scale with batch width. The
    # SAME executor serves it — sticky tiers are already settled, so the
    # wide batch costs one shape-specialized compile of the warm fused
    # program and then times the zero-sync steady path.
    for name, spec, args, denom in workload256:
        jax.block_until_ready(ex.run(spec, *args))      # shape compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(ex.run(spec, *args))
            best = min(best, time.perf_counter() - t0)
        steady = best * 1e6 / denom
        specs[name]["steady_us_per_q_b256"] = round(steady, 2)
        emit(f"quick/{backend}/{name}/steady_b256", steady)
    # q=4096 column: the tier-bucketed + chunked wide path at serving
    # scale, kept honest under the CI regression gate (point/range/knn10
    # only — wide enough to cross the query-shard threshold on meshes)
    for name, spec, args, denom in workload4k:
        jax.block_until_ready(ex.run(spec, *args))      # shape compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(ex.run(spec, *args))
            best = min(best, time.perf_counter() - t0)
        steady = best * 1e6 / denom
        specs[name]["steady_us_per_q_b4096"] = round(steady, 2)
        emit(f"quick/{backend}/{name}/steady_b4096", steady)
    if cache_dir:
        # record the settled program set for the benchmarks.cold
        # restart probe: its second process replays this manifest at
        # startup (the supported restart path, DESIGN.md §14) before
        # timing first dispatches
        from repro.core.compile_cache import save_manifest
        save_manifest(os.path.join(cache_dir,
                                   f"prewarm_quick_{backend}.json"),
                      ex.manifest())
    executor = {k: v for k, v in ex.stats().items() if k != "sticky"}
    executor["sticky"] = {
        str(k): list(v) for k, v in ex.stats()["sticky"].items()}
    return {"specs": specs, "executor": executor}


def bench_updates(index, x, y, backend: str, workload) -> dict:
    """Update-throughput column (DESIGN.md §11): batched inserts/s into
    the delta buffers, the compaction+re-fit cost, and the post-update
    steady us/q of the range + circle specs — the regression gate pins
    that absorbing updates does not tax steady serving. Shares main()'s
    built index: mutations replace executor state functionally and
    never touch the original pytree."""
    ub = 256
    ex = Executor(index, config=EngineConfig(backend=backend,
                                             delta_cap=4 * ub))
    qspecs = {name: (spec, args, denom) for name, spec, args, denom
              in workload if name in ("range", "circle")}
    for spec, args, _ in qspecs.values():     # settle sticky + fused
        jax.block_until_ready(ex.run(spec, *args, strict=True))
        jax.block_until_ready(ex.run(spec, *args))

    rng = np.random.default_rng(7)
    bx = np.repeat(x, 2)[: 3 * ub] + rng.normal(0, 1e-4, 3 * ub)
    by = np.repeat(y, 2)[: 3 * ub] + rng.normal(0, 1e-4, 3 * ub)
    bx = bx.astype(np.float32)
    by = by.astype(np.float32)
    ex.run(InsertBatch(), bx[:ub], by[:ub])   # compile + grow once
    best = float("inf")
    for i in (1, 2):
        t0 = time.perf_counter()
        ex.run(InsertBatch(), bx[i * ub:(i + 1) * ub],
               by[i * ub:(i + 1) * ub])
        best = min(best, time.perf_counter() - t0)
    insert_us = best * 1e6 / ub
    ex.run(DeleteBatch(), bx[:32], by[:32])

    t0 = time.perf_counter()
    touched = ex.refit()
    jax.block_until_ready(ex.index.key)   # time completion, not dispatch
    refit_ms = (time.perf_counter() - t0) * 1e3

    out = {"insert_batch": ub,
           "insert_us_per_op": round(insert_us, 2),
           "inserts_per_s": round(1e6 / max(insert_us, 1e-9)),
           "refit_partitions": len(touched),
           "refit_ms": round(refit_ms, 2)}
    for name, (spec, args, denom) in qspecs.items():
        jax.block_until_ready(ex.run(spec, *args))    # recompile settle
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(ex.run(spec, *args))
            best = min(best, time.perf_counter() - t0)
        steady = best * 1e6 / denom
        out[f"post_{name}_us_per_q"] = round(steady, 2)
        emit(f"quick/{backend}/upd_{name}/steady", steady)
    emit(f"quick/{backend}/insert/us_per_op", insert_us)
    return out


def main():
    w = build_workloads()
    index, x, y, part = w["index"], w["x"], w["y"], w["part"]

    root = cache_root()
    if not os.environ.get("BENCH_CACHE_DIR"):
        shutil.rmtree(root, ignore_errors=True)
    default = resolve_backend("auto").name
    order = [default] + [b for b in ("xla", "pallas") if b != default]
    report = {"bench_n": BENCH_N, "bench_q": w["q"],
              "bench_q_wide": w["q2"], "bench_q_wide4k": w["q4"],
              "build_ms": w["build_ms"], "backend_default": default,
              "backends": {}}
    from benchmarks.traffic import bench_serve
    for backend in order:
        out = bench_backend(index, backend, w["workload"],
                            w["workload256"], w["workload4k"],
                            cache_dir=root)
        out["updates"] = bench_updates(index, x, y, backend,
                                       w["workload"])
        # serve column: scheduler-coalesced vs serial throughput, mixed
        # read/write latency, idle-only maintenance (benchmarks/traffic.py)
        out["serve"] = bench_serve(index, x, y, part, backend,
                                   cache_dir=root)
        report["backends"][backend] = out
    # back-compat view: the default backend is the serving configuration
    # whose trajectory the CI regression gate tracks
    report["specs"] = report["backends"][default]["specs"]
    report["executor"] = report["backends"][default]["executor"]
    # keep the measured query_shard_threshold record (written by
    # ``run.py --crossover``) stable across --quick reruns
    if os.path.exists(OUT):
        try:
            with open(OUT) as f:
                prev = json.load(f)
            if "crossover" in prev:
                report["crossover"] = prev["crossover"]
        except (OSError, ValueError):
            pass
    with open(OUT, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"# wrote {OUT}")


if __name__ == "__main__":
    main()
