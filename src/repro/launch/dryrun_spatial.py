import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import (device count locks at first init).

"""Production-mesh dry-run for the SPATIAL engine (the paper's own
workload): lower + compile the distributed range / kNN / join programs
over a ~1B-point learned index (ShapeDtypeStructs, no allocation).

Partitions shard over ('data',) on the single pod and ('pod','data') on
the multi-pod mesh; the (tiny) global index and the query batch are
replicated — the same layout the CPU engine uses, scaled up.

  python -m repro.launch.dryrun_spatial --mesh both --out results/dryrun_spatial
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import keys as CK
from repro.core.build import LearnedSpatialIndex
from repro.core.plan import EngineConfig
from repro.launch import hlo
from repro.launch.mesh import make_production_mesh

# ~1.07B points: 4096 partitions x 262144 padded slots
P_TOTAL = 4096
N_PAD = 262144
M_PAD = 512
RADIX_BITS = 10
Q = 1024
PG = 256


def fake_index() -> LearnedSpatialIndex:
    """ShapeDtypeStruct-backed index (no data allocation)."""
    sd = jax.ShapeDtypeStruct
    return LearnedSpatialIndex(
        key=sd((P_TOTAL, N_PAD), jnp.uint32),
        x=sd((P_TOTAL, N_PAD), jnp.float32),
        y=sd((P_TOTAL, N_PAD), jnp.float32),
        vid=sd((P_TOTAL, N_PAD), jnp.int32),
        count=sd((P_TOTAL,), jnp.int32),
        knot_keys=sd((P_TOTAL, M_PAD), jnp.float32),
        knot_pos=sd((P_TOTAL, M_PAD), jnp.float32),
        n_knots=sd((P_TOTAL,), jnp.int32),
        radix_table=sd((P_TOTAL, (1 << RADIX_BITS) + 2), jnp.int32),
        radix_kmin=sd((P_TOTAL,), jnp.float32),
        radix_scale=sd((P_TOTAL,), jnp.float32),
        part_bounds=sd((P_TOTAL, 4), jnp.float32),
        eps=32, radix_bits=RADIX_BITS, probe=128,
        key_spec=CK.KeySpec(bounds=(0.0, 0.0, 1.0, 1.0)),
    )


def measured_shard_threshold(default: int | None = None) -> tuple:
    """The PR-2/3 sharding loop closed: prefer the MEASURED crossover
    recommendation (``python -m benchmarks.run --crossover`` records it
    in BENCH_quick.json) over the hardcoded EngineConfig default when
    sizing the production config."""
    if default is None:
        default = EngineConfig().query_shard_threshold
    path = os.environ.get("BENCH_QUICK_OUT", "BENCH_quick.json")
    try:
        with open(path) as f:
            rec = json.load(f)["crossover"]
        return int(rec["recommended_query_shard_threshold"]), "measured"
    except (OSError, ValueError, KeyError, TypeError):
        return int(default), "default"


def run(mesh_kind: str, out_dir: str, backend: str = "xla"):
    import repro.core.local_ops as E
    from repro.core.backends import resolve_backend

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    part_axis = ("pod", "data") if mesh_kind == "multi" else ("data",)
    index = fake_index()
    shard_threshold, shard_src = measured_shard_threshold()
    cfg = EngineConfig(part_chunk=8, range_cap=64, knn_cap=64,
                       range_cand=8, knn_cand=8, join_cap=128,
                       join_cand=8, backend=backend,
                       query_shard_threshold=shard_threshold)
    print(f"# query_shard_threshold={shard_threshold} ({shard_src}"
          " crossover)", flush=True)
    bk = resolve_backend(backend)

    # build the shardable parts dict as SDS (mirror _part_arrays)
    parts = {
        "keys_f": jax.ShapeDtypeStruct((P_TOTAL, N_PAD), jnp.float32),
        "x": index.x, "y": index.y, "vid": index.vid,
        "count": index.count,
        "knot_keys": index.knot_keys, "knot_pos": index.knot_pos,
        "n_knots": index.n_knots, "radix_table": index.radix_table,
        "radix_kmin": index.radix_kmin, "radix_scale": index.radix_scale,
    }
    bounds = index.part_bounds
    pspec = NamedSharding(mesh, P(part_axis))
    rspec = NamedSharding(mesh, P())
    parts_shard = jax.tree_util.tree_map(lambda _: pspec, parts)

    sd = jax.ShapeDtypeStruct
    cells = {}

    def lower_one(name, local_fn, qargs, qshapes):
        axes = part_axis
        in_specs = (P(axes),) + (P(),) * (local_fn.n_query_args + 1)
        from functools import partial as fpartial
        wrapped = jax.shard_map(fpartial(local_fn, axis=axes),
                                mesh=mesh, in_specs=in_specs,
                                out_specs=P(), check_vma=False)
        t0 = time.time()
        lowered = jax.jit(wrapped, in_shardings=(
            parts_shard, rspec) + (rspec,) * len(qshapes)).lower(
            parts, bounds, *qshapes)
        compiled = lowered.compile()
        rep = hlo.analyze_compiled(compiled, chips, model_flops=0.0)
        rep.update({"arch": "lilis-spatial", "shape": name,
                    "mesh": mesh_kind, "chips": chips,
                    "compile_s": round(time.time() - t0, 1),
                    "points": P_TOTAL * N_PAD, "queries": qargs,
                    "query_shard_threshold": shard_threshold,
                    "query_shard_threshold_src": shard_src})
        path = os.path.join(out_dir, f"lilis-spatial__{name}__"
                                     f"{mesh_kind}.json")
        hlo.dump(rep, path)
        r = rep["roofline"]
        print(f"OK   spatial/{name}/{mesh_kind}: "
              f"bottleneck={r['bottleneck']} tc={r['t_compute_s']:.2e} "
              f"tm={r['t_memory_s']:.2e} tl={r['t_collective_s']:.2e}",
              flush=True)
        cells[name] = rep

    # 1) baseline range: full-refine mask path (partition-centric scan)
    lower_one("range_mask", E._RangeCountLocal(index, cfg, bk), Q,
              (sd((Q, 4), jnp.float32), sd((Q,), jnp.float32),
               sd((Q,), jnp.float32)))
    # 2) optimized range: query-centric windowed + z-split
    lower_one("range_window",
              E._RangeWindowLocal(index, cfg, bk, cfg.range_cap,
                                  cfg.range_cand), Q,
              (sd((Q, 4), jnp.float32), sd((Q,), jnp.float32),
               sd((Q,), jnp.float32)))
    # 3) kNN pruned (k=10)
    lower_one("knn10",
              E._KnnPrunedLocal(index, cfg, bk, 10, index.key_spec,
                                cfg.knn_cand, cfg.knn_cap), Q,
              (sd((Q,), jnp.float32), sd((Q,), jnp.float32),
               sd((Q,), jnp.float32)))
    # 4) join (256 polygons x 16 edges)
    lower_one("join",
              E._JoinLocal(index, cfg, bk, cfg.join_cap, cfg.join_cand),
              PG,
              (sd((PG, 16, 2), jnp.float32), sd((PG,), jnp.int32),
               sd((PG, 6), jnp.float32)))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--backend", default="xla",
                    choices=["auto", "xla", "pallas"],
                    help="kernel backend to lower (pallas lowers the "
                         "real kernels when run on TPU)")
    ap.add_argument("--out", default="results/dryrun_spatial")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for mk in (["single", "multi"] if args.mesh == "both"
               else [args.mesh]):
        try:
            run(mk, args.out, backend=args.backend)
        except Exception:
            failures += 1
            traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
