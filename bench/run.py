#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process finds.

    python3 bench/run.py --workload spider-gaussian.interactive \
        --seed 7 --seconds 30 --trace 0

The cell (an entry of BENCHMARK.json's ``workloads``) names a
configuration (``bench/configs/<config>.json``, whose points come from
``bench/data/<generator>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose loop and query families are
``bench/loops/<loop>.py`` and ``bench/families/<family>.py``). One
run:

  1. fails (exit 3, no result) when JAX finds no TPU or fewer chips
     than the cell asks for, or a device kind ``bench/peaks.py`` lacks;
  2. generates the deployment's points from ``--seed``: on the host, or
     on the device where the configuration's generator draws there
     (sharded over the cell's chips; the traffic then centres its
     queries on a seeded sample of them);
  3. builds the index with the configuration's build
     (``bench/builds/<build>.py``) at its pinned shapes; a cell on more
     than one chip serves it over a ``("data",)`` mesh of its chips;
  4. warms up the cell's shapes: the tiers its mix settles, every
     power-of-two width the scheduler can coalesce its requests to, the
     scheduler's concatenation shapes, then ``warm_seconds`` of the
     cell's own traffic through the scheduler (programs come from the
     compile cache in ``.jax_cache/`` of the checkout after a first run);
  5. drives ``--seconds`` of traffic through
     ``SpatialServeSession(index).scheduler()`` (worker mode, defaults),
     as ``bench/drive.py``'s client;
  6. with ``--trace 1``, profiles a few seconds of traffic of its own;
  7. frees the program's state and compares a seeded sample of the
     answers with the plain reference (``bench/check.py``): the
     whole-set one for points drawn on the host, else the one over
     candidate windows of points drawn again on the device
     (``bench/windows.py``);
  8. prints the compared numbers beside their limits as the last lines
     of stderr, and one JSON result line as the last line of stdout.

Set-up (``setup_s``) runs from the start of this process to the start
of the window. With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read by
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
CACHE = ROOT / ".jax_cache"       # fixed: the path is part of the key
TRACE_SECONDS = 4.0               # length of the traced window
STRICT_QUERIES = 128              # strict warm-up queries per family
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).parent:
    sys.path[0] = str(ROOT)       # import bench.* as a package
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, deploy, drive, gen, peaks, windows  # noqa: E402
from bench import trace as tracing  # noqa: E402


class NoChip(SystemExit):
    pass


def log(*a):
    print(f"[{time.perf_counter() - T0:8.2f}s]", *a, file=sys.stderr,
          flush=True)


def fail(msg: str, code: int = 1):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def seed_of(seed: int, k: int) -> int:
    """The k-th stream of a run's seed (any whole number)."""
    return (int(seed) % (1 << 60)) * 16 + k


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

class Cell:
    """A workload of BENCHMARK.json with its configuration, traffic and
    metrics, each found by name under ``root``."""

    def __init__(self, name: str, root: Path = ROOT):
        path = Path(root) / "BENCHMARK.json"
        if not path.is_file():
            fail(f"no BENCHMARK.json at {root}", 2)
        bench = json.loads(path.read_text())
        entry = [w for w in bench["workloads"] if w["name"] == name]
        if not entry:
            fail(f"no workload {name!r} in BENCHMARK.json", 2)
        self.name, self.root = name, Path(root)
        self.entry = entry[0]
        self.cfg = deploy.load(str(root), self.entry["config"])
        self.traffic = gen.load(str(root), self.entry["traffic"])
        self.e2e = [m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])]
        names = {m["name"] for m in self.e2e}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in names)]


def reader(name: str, root: Path = ROOT):
    """The per-layer metric's reader, ``bench/metrics/<name>.py``."""
    return gen.module("metrics", name, str(root)).read


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU found (JAX platform "
                         f"{devs[0].platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
        peaks.lookup(devs[0].device_kind)
    return devs


def centres(x, y, seed: int):
    """The points a run's traffic centres its queries on
    (``deploy.centres``), from the run's seed."""
    return deploy.centres(x, y, seed_of(seed, 12))


def _bucket(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def streams(g: gen.Generator, traffic: dict, seed: int, seconds: float,
            trace: bool) -> dict:
    """A run's windows in the order they are sent, each (requests, due
    times): the warm-up rounds, the measured window, the traced
    window."""
    ws = float(traffic.get("warm_seconds", 0))
    rounds = int(traffic.get("warm_rounds", 1)) if ws > 0 else 0
    plan = [(f"warm{r}", ws, seed_of(seed, 30 + r)) for r in range(rounds)]
    plan.append(("main", seconds, seed_of(seed, 1)))
    if trace:
        plan.append(("trace", TRACE_SECONDS, seed_of(seed, 2)))
    return {label: g.stream(secs, s) for label, secs, s in plan}


def sample(reqs, traffic: dict, seed: int) -> set:
    """Indices of the requests the check compares: per family, up to
    ``check_per_family`` drawn from the seed."""
    import numpy as np
    rng = np.random.default_rng(seed_of(seed, 11))
    k = int(traffic.get("check_per_family", 50))
    by = {}
    for i, r in enumerate(reqs):
        by.setdefault(r.family, []).append(i)
    keep = set()
    for _fam, idx in sorted(by.items()):
        pick = rng.choice(len(idx), min(k, len(idx)), replace=False)
        keep.update(idx[j] for j in pick)
    return keep


class Setup:
    """Everything a run builds before its window."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 trace: bool, devs, cache: bool = True,
                 engine: dict = None):
        import jax
        from repro.core import EngineConfig
        from repro.serve.spatial import SpatialServeSession

        cfg, traffic = cell.cfg, cell.traffic
        self.cell, self.seed, self.seconds = cell, seed, seconds
        chips = int(cell.entry["chips"])
        devs = devs[:chips]
        # the serve mesh (none on one chip) and the mesh the points are
        # drawn over
        self.mesh = (jax.make_mesh((chips,), ("data",), devices=devs)
                     if chips > 1 else None)
        self.data_mesh = self.mesh or jax.make_mesh((1,), ("data",),
                                                    devices=devs)
        t = time.perf_counter()
        x, y = deploy.points(cfg, int(cfg["points"]), seed, str(cell.root),
                             self.data_mesh)
        self.on_device = deploy.on_device(x)
        log(f"data: {cfg['generator']['kind']} n={x.shape[0]} "
            f"{'on the device' if self.on_device else 'on the host'} in "
            f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        index, _ = deploy.build(cfg, x, y, seed, self.mesh, str(cell.root))
        self.shapes = deploy.shapes(index)
        log(f"index built by {cfg['build']!r} in "
            f"{time.perf_counter() - t:.3f} s: {self.shapes}")
        # the points drawn on the device are freed here; the check draws
        # them again
        self.x, self.y = centres(x, y, seed)
        del x, y
        root = None
        if cache:
            from repro.core.compile_cache import default_cache_root
            root = default_cache_root()
        self.session = SpatialServeSession(
            index, mesh=self.mesh, part_axis="data",
            config=EngineConfig(compile_cache_dir=root, **(engine or {})))
        del index
        self.ex = self.session.executor
        log(f"backend {self.ex.backend.name} "
            f"interpret={getattr(self.ex.backend, 'interpret', None)}")
        self.gen = gen.Generator(traffic, self.x, self.y, str(cell.root))
        self.streams = streams(self.gen, traffic, seed, seconds, trace)
        ws = float(traffic.get("warm_seconds", 0))
        rounds = sum(1 for k in self.streams if k.startswith("warm"))
        self.warm_programs()
        self.sched = self.session.scheduler()
        self.logs = {}
        # the cell's own traffic until a round realizes no program and
        # moves no tier: the tiers the mix settles (and the programs
        # maintain() moves them to) are compiled before the window
        for r in range(rounds):
            t, k0 = time.perf_counter(), programs(self.ex)
            c0, s0 = self.ex.compile_ms_total, self.ex.stats()["sticky"]
            self.window(f"warm{r}", ws)
            new = programs(self.ex) - k0
            moved = self.ex.stats()["sticky"] != s0
            log(f"warm traffic round {r}: {ws} s in "
                f"{time.perf_counter() - t:.3f} s, {len(new)} programs "
                f"realized, compile "
                f"{self.ex.compile_ms_total - c0:.1f} ms, tiers moved "
                f"{moved}")
            if not new and not moved:
                break
        jax.block_until_ready(self.ex.parts)

    # -- warm-up -------------------------------------------------------

    def warm_programs(self):
        """Settle each read family's tier on strict passes over a sample
        (STRICT_QUERIES queries, in batches of the widest width), run every
        coalesced width twice (the second call compiles the fused steady
        program), then the scheduler's concatenation of k requests and
        its padding, for every k it can form."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        rng = np.random.default_rng(seed_of(self.seed, 9))
        for f in self.cell.traffic["mix"]:
            t, c0 = time.perf_counter(), self.ex.compile_ms_total
            one = self.gen.family(f, 1, rng)[0]
            ws = self.gen.loop.widths(self.ex.cfg, self.cell.traffic,
                                      one.queries)
            per = max(1, ws[-1] // one.queries)
            nb = -(-STRICT_QUERIES // (per * one.queries))
            reqs = self.gen.family(f, per * nb, rng)
            spec = one.spec
            batches = [tuple(np.concatenate(c) for c in
                             zip(*(r.args for r in reqs[i:i + per])))
                       for i in range(0, len(reqs), per)]
            for b in batches:
                jax.block_until_ready(self.ex.run(spec, *b, strict=True))
            cols = batches[0]
            for w in ws:
                args = tuple(c[:w] for c in cols)
                pad = w - len(args[0])
                if pad > 0:
                    args = tuple(np.concatenate([a, np.repeat(a[:1], pad,
                                                              0)])
                                 for a in args)
                for _ in range(2):
                    jax.block_until_ready(self.ex.run(spec, *args))
            for w in ws:
                for k in range(1, w // one.queries + 1):
                    if _bucket(k * one.queries) != w:
                        continue
                    cat = tuple(jnp.concatenate([a] * k, axis=0)
                                for a in one.args)
                    pad = w - k * one.queries
                    if pad > 0:
                        cat = tuple(jnp.concatenate(
                            [a, jnp.repeat(a[:1], pad, axis=0)], axis=0)
                            for a in cat)
                    jax.block_until_ready(cat)
            log(f"warm-up {f['family']}: widths {ws} in "
                f"{time.perf_counter() - t:.3f} s, compile "
                f"{self.ex.compile_ms_total - c0:.1f} ms, "
                f"{self.ex.stats()['cache_size']} programs, sticky "
                f"{self.ex.stats()['sticky']}")
        self.warm_ladder()

    def warm_ladder(self):
        """Compile each family's fused program at every tier of its
        escalation ladder up to the settled one, at every width warmed
        there, through the executor's own manifest-and-prewarm path.
        The executor keeps the whole ladder up to its sticky tier and the
        tier above it, so a demotion, or one escalation, in the window
        finds its programs compiled. The ladder follows the executor's
        rule: from the configured (cap, cand),
        cap x4 and cand x2 per step (kNN keeps cand), clamped at n_pad
        and the partition count."""
        t, c0 = time.perf_counter(), self.ex.compile_ms_total
        cfg, idx = self.ex.cfg, self.ex.index
        man = self.session.manifest()
        sticky = {tuple(b): tuple(v) for b, v in man["sticky"]}
        extra = []
        for p in man["programs"]:
            bk, qs, base, tag, variant = p["key"]
            kind = base[0]
            if tag != "fused" or qs or tuple(base) not in sticky:
                continue
            cap = getattr(cfg, f"{kind}_cap")
            cand = getattr(cfg, f"{kind}_cand")
            tier, top, above = (cap, cand), sticky[tuple(base)], 0
            while above < 2:
                if tier != tuple(variant):
                    extra.append({"key": [bk, qs, base, "fused",
                                          list(tier)], "sigs": p["sigs"]})
                nxt = (min(tier[0] * 4, idx.n_pad),
                       tier[1] if kind == "knn"
                       else min(tier[1] * 2, idx.num_partitions))
                if nxt == tier:
                    break
                above += tier == top or above > 0
                tier = nxt
        got = self.session.prewarm(dict(man, programs=extra))
        log(f"warm-up ladder: {got} in {time.perf_counter() - t:.3f} s, "
            f"compile {self.ex.compile_ms_total - c0:.1f} ms")

    # -- windows -------------------------------------------------------

    def window(self, label: str, seconds: float, spans: bool = False):
        reqs, due = self.streams[label]
        keep = sample(reqs, self.cell.traffic, self.seed) \
            if label == "main" else set()
        lg = drive.Log(reqs, keep)
        self.gen.loop.drive(self.sched, reqs, due, seconds, lg,
                            drive.span_fn(spans))
        self.logs[label] = lg
        return lg


# ---------------------------------------------------------------------------
# counters, metrics, result
# ---------------------------------------------------------------------------

EX_KEYS = ("host_syncs", "probe_syncs", "dispatches", "compile_ms_total",
           "disk_cache_hits", "disk_cache_misses", "async_compiles")
SCHED_KEYS = ("submitted", "reads", "read_batches", "maintain_runs",
              "maintain_busy", "width_fallbacks")


def counters(st: Setup) -> dict:
    e, s = st.ex.stats(), st.sched.stats()
    return {"executor": {k: e[k] for k in EX_KEYS},
            "scheduler": {k: s[k] for k in SCHED_KEYS}}


def programs(ex) -> set:
    """(exec key, signature) of every program the executor holds."""
    return {(repr(k[2:5]), repr(sig[:1]))
            for k, sig, _ in ex.compiled_programs()}


def memory_peaks(devs) -> list:
    """Each device's peak bytes in use (0 where the backend keeps no
    count)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs]


def delta(a: dict, b: dict) -> dict:
    return {layer: {k: b[layer][k] - a[layer][k] for k in a[layer]}
            for layer in a}


def end_to_end(lg: drive.Log, seconds: float, setup_s: float) -> dict:
    import numpy as np
    n = lg.issued
    done = ~np.isnan(lg.done[:n])
    inside = done & (lg.done[:n] <= seconds)
    queries = sum(r.queries for r, ok in zip(lg.reqs[:n], inside) if ok)
    out = {"setup_s": setup_s, "queries_per_s": queries / seconds}
    if done.any():
        lat = (lg.done[:n] - lg.due[:n])[done] * 1e3
        out["read_p50_ms"] = float(np.percentile(lat, 50))
        out["read_p95_ms"] = float(np.percentile(lat, 95))
    return out


def describe(lg: drive.Log, seconds: float) -> str:
    import numpy as np
    n = lg.issued
    late = (lg.sent[:n] - lg.due[:n]) * 1e3
    done = ~np.isnan(lg.done[:n])
    fams = {}
    for r, d, l in zip(lg.reqs[:n], done, (lg.done[:n] - lg.due[:n]) * 1e3):
        if d:
            fams.setdefault(r.family, []).append(l)
    per = " ".join(f"{f}:n={len(v)},p50={np.percentile(v, 50):.3f},"
                   f"p95={np.percentile(v, 95):.3f}"
                   for f, v in sorted(fams.items()))
    return (f"requests {n} done {int(done.sum())}, asked again "
            f"{int((lg.asked[:n] - 1).clip(0).sum())} times for cut "
            f"windows; sender late p50 "
            f"{np.nanpercentile(late, 50):.3f} ms p99 "
            f"{np.nanpercentile(late, 99):.3f} ms; {per}")


def traced_window(st: Setup):
    """Profile TRACE_SECONDS of the cell's traffic; reduce the trace."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            st.window("trace", TRACE_SECONDS, spans=True)
        finally:
            jax.profiler.stop_trace()
        path = tracing.find(tmp)
        red = tracing.reduce(tracing.load(path))
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            shutil.copy(path, keep)
        return red
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, cache: bool = True, fault=None,
        engine: dict = None) -> dict:
    """One run of a cell; returns the result object. Tests only:
    ``require_tpu=False`` skips the look for a chip, ``engine`` shrinks
    the scheduler's widths, ``fault`` breaks the session's timed path
    before the window."""
    import jax
    import numpy as np
    chips = int(cell.entry["chips"])
    devs = devices(chips, require_tpu)
    st = Setup(cell, seed, seconds, trace, devs, cache=cache, engine=engine)
    if fault is not None:
        fault(st.session)
    setup_s = time.perf_counter() - T0
    log(f"set-up {setup_s:.3f} s; window {seconds} s")
    c0, k0 = counters(st), programs(st.ex)
    lg = st.window("main", seconds)
    c1, k1 = counters(st), programs(st.ex)
    d = delta(c0, c1)
    log(describe(lg, seconds))
    if k1 - k0:
        log(f"programs realized in the window: {sorted(k1 - k0)}")
    log(f"counters in the window: {json.dumps(d)}")
    red = traced_window(st) if trace else None
    if red is not None:
        log(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} "
            f"s, kernels {red['kernel_s']:.6f} s, {red['gaps']} gaps")
    mem = memory_peaks(devs[:chips])
    st.sched.close()
    # free the program's state before the reference runs
    answers = lg.answers()
    e2e = end_to_end(lg, seconds, setup_s)
    attempted = lg.issued
    failed = sum(1 for i in range(lg.issued)
                 if lg.error[i] is not None or np.isnan(lg.done[i]))
    x, y, mesh, drawn = st.x, st.y, st.data_mesh, st.on_device
    del st, lg
    gc.collect()
    jax.clear_caches()
    t = time.perf_counter()
    if drawn:
        cfg = cell.cfg
        x, y = deploy.points(cfg, int(cfg["points"]), seed, str(cell.root),
                             mesh)
        orc = windows.Reference(x, y, [req for req, _, _ in answers])
    else:
        orc = check.Oracle(x, y)
    rp = check.compare(answers, orc)
    log(f"reference: {rp.counts['compared']} answers compared in "
        f"{time.perf_counter() - t:.3f} s; wrong by family {rp.wrong}")
    checks = {k: {"value": rp.counts[k], "limit": lim}
              for k, lim in check.LIMITS.items()}
    correct = (rp.counts["compared"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    metrics = {}
    if trace:
        ctx = dict(d, trace=red, window_s=seconds)
        for m in cell.per_layer:
            v = reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.e2e:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": max(mem),
              "memory_peak_bytes_per_device": mem}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"the program (src/repro) is not in this checkout ({ROOT})", 2)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path.insert(0, str(SRC))
    cell = Cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        fail(str(e), 3)
    except deploy.PinExceeded as e:
        fail(f"pinned size exceeded: {e}", 4)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
