#!/usr/bin/env python3
"""Smoke run of the LiLIS serve path on a TPU, checked against oracles.

    python chip_smoke.py                  # one chip, 10^7 points
    python chip_smoke.py --chips 4        # partition-sharded executors
    python chip_smoke.py --cpu-rehearsal --n 200000 --parts 16

One chip (the default): 10^7 points from the seeded ``taxi`` generator,
a ``kdtree`` fit of 256 partitions, the index built on the device, one
``SpatialServeSession`` with its scheduler worker and the backend left
at ``auto`` (the Pallas kernels on a TPU). Every read family goes in as
scheduler tickets: point, range count, range, circle (count and
materialised), kNN k=10 (windowed and exact) and a spatial join, with
interactive query sizes (tens to hundreds of points per query). Then
an insert, a read that must see it, a delete and a read that must not
see the deleted points. Every answer is compared with a plain NumPy
oracle on the host and, bitwise, with an ``xla``-backend executor on
the same chip.

``--chips 4`` runs only the sharded path: the same index over a
4-device ``("data",)`` mesh and over a ``(2, 2)`` ``("data", "query")``
mesh with query sharding engaged, both compared bitwise with a one-chip
executor on device 0, for one family per collective pattern
(``SHARDED_FAMILIES``).

The script exits non-zero, and prints no result line, when JAX finds no
TPU (unless ``--cpu-rehearsal`` asks for a small CPU run, which never
reports a TPU), when the repository's ``src/`` is not beside it, and on
any mismatch or error. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

Compiled programs persist in ``JAX_COMPILATION_CACHE_DIR`` when that is
set, else in ``.jax_cache/`` of the checkout, so a second run compiles
little.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


T0 = time.perf_counter()
BATCHES = 4        # ticket batches per read family on the one-chip path
SHARDED_FAMILIES = ("point", "range_count", "range", "knn10", "join")


def log(*a):
    print(f"[{time.perf_counter() - T0:8.1f}s]", *a, flush=True)


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


# ---------------------------------------------------------------------------
# plain NumPy oracle over the live point set (host only, no JAX)
# ---------------------------------------------------------------------------

def coord_keys(x, y):
    """Exact (x, y) identity as one uint64 per point."""
    import numpy as np
    xb = np.ascontiguousarray(x, np.float32).view(np.uint32)
    yb = np.ascontiguousarray(y, np.float32).view(np.uint32)
    return (xb.astype(np.uint64) << np.uint64(32)) | yb.astype(np.uint64)


class Oracle:
    """The semantics of each query family, computed directly: exact
    coordinate membership, inclusive rectangles, an MBR-then-distance
    circle test and ray casting, all in float32 as the index stores
    points. Candidates come from an x-sorted copy of the live points."""

    def __init__(self, x, y):
        import numpy as np
        self.np = np
        self.x = np.asarray(x, np.float32).copy()
        self.y = np.asarray(y, np.float32).copy()
        self.vid = np.arange(len(self.x), dtype=np.int64)
        self.alive = np.ones(len(self.x), bool)
        self._reindex()

    def _reindex(self):
        np = self.np
        live = np.flatnonzero(self.alive)
        order = live[np.argsort(self.x[live], kind="stable")]
        self.sx, self.sy, self.sv = (self.x[order], self.y[order],
                                     self.vid[order])
        self.keys = np.sort(coord_keys(self.x[live], self.y[live]))

    def insert(self, xs, ys, vids):
        np = self.np
        self.x = np.concatenate([self.x, np.asarray(xs, np.float32)])
        self.y = np.concatenate([self.y, np.asarray(ys, np.float32)])
        self.vid = np.concatenate([self.vid, np.asarray(vids, np.int64)])
        self.alive = np.concatenate([self.alive, np.ones(len(xs), bool)])
        self._reindex()

    def delete(self, xs, ys) -> int:
        """Remove every live copy of each coordinate; returns how many."""
        np = self.np
        gone = np.isin(coord_keys(self.x, self.y),
                       np.unique(coord_keys(xs, ys))) & self.alive
        self.alive &= ~gone
        self._reindex()
        return int(gone.sum())

    def _slab(self, xl, xh):
        np = self.np
        i = np.searchsorted(self.sx, xl, side="left")
        j = np.searchsorted(self.sx, xh, side="right")
        return self.sx[i:j], self.sy[i:j], self.sv[i:j]

    def point(self, qx, qy):
        np = self.np
        k = coord_keys(qx, qy)
        i = np.clip(np.searchsorted(self.keys, k), 0, len(self.keys) - 1)
        return self.keys[i] == k

    def rect_ids(self, rect):
        xl, yl, xh, yh = (self.np.float32(v) for v in rect)
        sx, sy, sv = self._slab(xl, xh)
        return sv[(sy >= yl) & (sy <= yh)]

    def circle_ids(self, cx, cy, r):
        np = self.np
        cx, cy, r = np.float32(cx), np.float32(cy), np.float32(r)
        sx, sy, sv = self._slab(cx - r, cx + r)
        dx, dy = sx - cx, sy - cy
        m = ((sy >= cy - r) & (sy <= cy + r) &
             (dx * dx + dy * dy <= r * r))
        return sv[m]

    def knn_d2(self, qx, qy, k):
        """The k smallest squared distances, exact: float64 from the
        stored float32 coordinates, over a square window grown until
        its k-th distance lies inside the window's inscribed circle."""
        np = self.np
        w = 1e-3
        while True:
            sx, sy, _ = self._slab(qx - w, qx + w)
            m = np.abs(sy - np.float32(qy)) <= w
            if m.sum() >= k or w > 4.0:
                d2 = np.sort(self._d2(sx[m], sy[m], qx, qy))[:k]
                if len(d2) == k and (np.sqrt(d2[-1]) < 0.999 * w
                                     or w > 4.0):
                    return d2
            w *= 2.0

    def vid_d2(self, vids, qx, qy):
        """Exact squared distances of the points with these ids."""
        pos = self.np.searchsorted(self.vid, vids)
        return self._d2(self.x[pos], self.y[pos], qx, qy)

    def _d2(self, px, py, qx, qy):
        np = self.np
        dx = px.astype(np.float64) - np.float64(np.float32(qx))
        dy = py.astype(np.float64) - np.float64(np.float32(qy))
        return dx * dx + dy * dy

    def polygon_count(self, poly, ne):
        np = self.np
        v = np.asarray(poly[:ne], np.float32)
        xl, yl = v[:, 0].min(), v[:, 1].min()
        xh, yh = v[:, 0].max(), v[:, 1].max()
        px, py, _ = self._slab(xl, xh)
        keep = (py >= yl) & (py <= yh)
        px, py = px[keep], py[keep]
        parity = np.zeros(len(px), bool)
        for i in range(ne):
            x1, y1 = v[i]
            x2, y2 = v[(i + 1) % ne]
            den = np.float32(1e-30) if y2 == y1 else y2 - y1
            t = (py - y1) / den
            xin = x1 + t * (x2 - x1)
            parity ^= ((y1 > py) != (y2 > py)) & (px < xin)
        return int(parity.sum())


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def leaves(out):
    return out if isinstance(out, tuple) else (out,)


def same_bits(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and a.tobytes() == b.tobytes())


def row_ids(vids, row):
    import numpy as np
    v = np.asarray(vids[row])
    return np.sort(v[v >= 0])


class Checker:
    def __init__(self):
        self.failures = []

    @contextlib.contextmanager
    def guard(self, what: str):
        """Record an error as a failure and go on with the next phase,
        so one run reports every phase; any failure still fails it."""
        try:
            yield
        except Exception as e:
            traceback.print_exc()
            self.expect(False, f"{what}: {type(e).__name__}: {e}")

    def expect(self, ok: bool, what: str):
        if not ok:
            self.failures.append(what)
            log(f"MISMATCH {what}")

    def bitwise(self, name, got, ref):
        """Every leaf bitwise; a materialised id plane whose padded
        width differs (the two executors may settle different window
        tiers) is compared row by row as the ids it holds."""
        g, r = leaves(got), leaves(ref)
        self.expect(len(g) == len(r), f"{name}: result arity")
        for i, (a, b) in enumerate(zip(g, r)):
            if same_bits(a, b):
                continue
            import numpy as np
            a, b = np.asarray(a), np.asarray(b)
            if (a.ndim == 2 and b.ndim == 2 and a.dtype == b.dtype
                    and a.shape[0] == b.shape[0] and a.shape[1] != b.shape[1]
                    and all(same_bits(row_ids(a, q), row_ids(b, q))
                            for q in range(a.shape[0]))):
                continue
            self.expect(False, f"{name}: leaf {i} differs from the "
                               "xla reference")


def check_oracle(chk, name, spec, args, out, orc):
    """Compare one family's answer with the NumPy oracle."""
    import numpy as np
    kind = spec.kind
    if kind == "point":
        want = orc.point(args[0], args[1])
        chk.expect(same_bits(np.asarray(out, bool), want),
                   f"{name}: membership")
    elif kind == "range_count":
        want = np.array([len(orc.rect_ids(r)) for r in args[0]], np.int32)
        chk.expect(same_bits(np.asarray(out, np.int32), want),
                   f"{name}: counts")
    elif kind == "range":
        cnt, vids, ok = (np.asarray(a) for a in out)
        chk.expect(bool(ok.all()), f"{name}: incomplete windows")
        for q, r in enumerate(args[0]):
            want = np.sort(orc.rect_ids(r)).astype(np.int32)
            chk.expect(int(cnt[q]) == len(want)
                       and same_bits(row_ids(vids, q), want),
                       f"{name}: row {q} ids")
    elif kind == "circle":
        cx, cy, r = args
        want = [np.sort(orc.circle_ids(cx[q], cy[q], r[q])).astype(np.int32)
                for q in range(len(cx))]
        if spec.materialize:
            cnt, vids, ok = (np.asarray(a) for a in out)
            chk.expect(bool(ok.all()), f"{name}: incomplete windows")
            for q, w in enumerate(want):
                chk.expect(int(cnt[q]) == len(w)
                           and same_bits(row_ids(vids, q), w),
                           f"{name}: row {q} ids")
        else:
            chk.expect(same_bits(np.asarray(out, np.int32),
                                 np.array([len(w) for w in want],
                                          np.int32)),
                       f"{name}: counts")
    elif kind == "knn":
        # the neighbours' exact distances must be the k smallest; the
        # float32 d2 values are pinned bitwise to the xla reference
        _, vids = (np.asarray(a) for a in out)
        for q in range(len(args[0])):
            qx, qy = args[0][q], args[1][q]
            want = orc.knn_d2(qx, qy, spec.k)
            got = np.sort(orc.vid_d2(vids[q], qx, qy))
            chk.expect(len(np.unique(vids[q])) == spec.k
                       and same_bits(got, want),
                       f"{name}: row {q} neighbours")
    elif kind == "join":
        polys, ne = args
        want = np.array([orc.polygon_count(polys[g], int(ne[g]))
                         for g in range(len(ne))], np.int32)
        chk.expect(same_bits(np.asarray(out, np.int32), want),
                   f"{name}: counts")
    else:
        raise ValueError(kind)


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------

# Query sizes in the unit square the taxi points fill: answers return
# tens to a few hundred points per query at 10^7 points — map lookups
# of a block or two.
RECT_SEL = 1e-6                   # rect area / unit square
CIRCLE_R = (0.0003, 0.001)        # circle radius range
ZONE_R = 0.003                    # join polygon radius


def read_batches(x, y, bounds, q: int, batches: int, seed: int):
    """{family: [(spec, args), ...]} — ``batches`` batches of ``q``
    queries per read family, drawn from a seeded generator."""
    import numpy as np
    from repro.core import (CircleQuery, Knn, PointQuery, RangeCount,
                            RangeQuery, SpatialJoin)
    from repro.data import spatial as ds
    rng = np.random.default_rng(seed + 1)
    n = len(x)
    fam = {k: [] for k in ("point", "range_count", "range", "circle",
                           "circle_mat", "knn10", "knn10_exact", "join")}
    for b in range(batches):
        ix = rng.integers(0, n, q)
        px, py = x[ix].copy(), y[ix].copy()
        miss = rng.random(q) < 0.5              # half are not in the set
        px[miss] = rng.random(int(miss.sum()), dtype=np.float32)
        fam["point"].append((PointQuery(), (px, py)))
        rects = ds.random_rects(q, RECT_SEL, bounds, seed=seed + 10 + b,
                                centers=(x, y))
        fam["range_count"].append((RangeCount(), (rects,)))
        fam["range"].append((RangeQuery(), (rects,)))
        jx = rng.integers(0, n, q)
        cx, cy = x[jx], y[jx]
        r = rng.uniform(*CIRCLE_R, q).astype(np.float32)
        fam["circle"].append((CircleQuery(), (cx, cy, r)))
        fam["circle_mat"].append((CircleQuery(materialize=True),
                                  (cx, cy, r)))
        kx = rng.integers(0, n, q)
        qx = (x[kx] + rng.normal(0, 1e-3, q)).astype(np.float32)
        qy = (y[kx] + rng.normal(0, 1e-3, q)).astype(np.float32)
        fam["knn10"].append((Knn(k=10), (qx, qy)))
        fam["knn10_exact"].append((Knn(k=10, mode="exact"), (qx, qy)))
        polys, ne = ds.random_polygons(max(q // 4, 4), bounds,
                                       seed=seed + 20 + b, radius=ZONE_R)
        fam["join"].append((SpatialJoin(), (polys, ne)))
    return fam


def concat_batches(batches):
    """One request holding every batch of a family, in order."""
    import numpy as np
    spec = batches[0][0]
    return (spec,) + tuple(np.concatenate(col)
                           for col in zip(*(a for _, a in batches)))


def compiled_kernel_report(ex, chk, require: bool):
    """Print, per compiled program, whether it holds a Pallas kernel
    (``tpu_custom_call``); with ``require``, every read family must
    have at least one such program."""
    fams = {}
    n = 0
    for key, sig, compiled in ex.compiled_programs():
        has = "tpu_custom_call" in compiled.as_text()
        base = key[2]
        log(f"program {base!r} tag={key[3]} tier={key[4]} "
            f"width={sig[0][0][0] if sig and sig[0][0] else '-'} "
            f"tpu_custom_call={has}")
        fams[base[0]] = fams.get(base[0], False) or has
        n += 1
    log(f"compiled programs: {n}")
    if require:
        for fam in ("point", "range_count", "range", "circle", "knn",
                    "knn_exact", "join"):
            chk.expect(fams.get(fam, False),
                       f"no compiled {fam} program holds a Pallas kernel")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def build(args):
    import jax
    from repro.core import build_index, fit
    from repro.data import spatial as ds
    t0 = time.perf_counter()
    x, y = ds.make("taxi", args.n, seed=args.seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    part = fit("kdtree", x, y, args.parts, seed=args.seed)
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = build_index(x, y, part)
    jax.block_until_ready(index)
    build_s = time.perf_counter() - t0
    dev = sorted({str(d) for d in index.x.devices()})
    log(f"data: taxi n={args.n} seed={args.seed} "
        f"(generated in {gen_s:.3f} s); kdtree fit of {args.parts} "
        f"partitions in {fit_s:.3f} s")
    log(f"index built on {dev} in {build_s:.3f} s: partitions="
        f"{index.num_partitions} n_pad={index.n_pad} knots="
        f"{index.knot_keys.shape[1]} radix={index.radix_table.shape[1]} "
        f"probe={index.probe} device bytes="
        f"{sum(a.nbytes for a in jax.tree_util.tree_leaves(index))}")
    return x, y, part, index


def check_backend(label, bk, args, on_tpu: bool):
    """Print the backend and interpret flag in use; on a TPU, 'auto'
    must have picked the compiled Pallas kernels."""
    interpret = getattr(bk, "interpret", None)
    log(f"{label}: backend {bk.name} (asked: {args.backend}) "
        f"interpret={interpret}")
    if on_tpu and args.backend == "auto" and (bk.name != "pallas"
                                              or interpret is not False):
        fail(f"on a TPU 'auto' must pick pallas with interpret off, "
             f"got {bk.name} interpret={interpret}")
    return interpret


def one_chip(args, on_tpu: bool, cache: str):
    from repro.core import EngineConfig, Executor
    from repro.serve.spatial import SpatialServeSession

    x, y, part, index = build(args)
    session = SpatialServeSession(index, config=EngineConfig(
        backend=args.backend, compile_cache_dir=cache))
    ex = session.executor
    bk = ex.backend
    interpret = check_backend("session", bk, args, on_tpu)
    ref = Executor(index, config=EngineConfig(backend="xla",
                                              compile_cache_dir=cache))
    orc = Oracle(x, y)
    chk = Checker()
    fam = read_batches(x, y, part.bounds, args.queries, BATCHES,
                       args.seed)

    # warm-up (the documented pre-traffic step): settles the sticky
    # window tiers and compiles the fused serving programs at the width
    # the scheduler coalesces a family's tickets to. The xla reference
    # needs none: it answers every batch on the strict path.
    t0 = time.perf_counter()
    session.warmup([concat_batches(batches) for batches in fam.values()])
    log(f"warmup: {time.perf_counter() - t0:.3f} s, compile_ms_total "
        f"{ex.stats()['compile_ms_total']}")

    sched = session.scheduler()
    try:
        for name, batches in fam.items():
            with chk.guard(name):
                t0 = time.perf_counter()
                tickets = [sched.submit(spec, *a) for spec, a in batches]
                outs = [t.result() for t in tickets]
                wall = time.perf_counter() - t0
                q = sum(len(a[0]) for _, a in batches)
                log(f"family {name}: {len(batches)} tickets, {q} queries, "
                    f"wall {wall * 1e3:.3f} ms")
                for b, ((spec, a), out) in enumerate(zip(batches, outs)):
                    check_oracle(chk, f"{name}[{b}]", spec, a, out, orc)
                    chk.bitwise(f"{name}[{b}]", out,
                                ref.run(spec, *a, strict=True))
        # before the writes: a write that grows the delta buffers moves
        # the shape epoch, which evicts the read programs compiled so far
        compiled_kernel_report(ex, chk, require=(bk.name == "pallas"
                                                 and not interpret))
        with chk.guard("writes"):
            writes(args, x, y, sched, ref, orc, chk)
        sst = sched.stats()
    finally:
        sched.close()

    log(f"scheduler: submitted={sst['submitted']} reads={sst['reads']} "
        f"read_batches={sst['read_batches']} writes={sst['writes']} "
        f"maintain_runs={sst['maintain_runs']}")
    for label, e in (("session", ex), ("xla reference", ref)):
        st = e.stats()
        log(f"{label}: backend={st['backend']} compile_ms_total="
            f"{st['compile_ms_total']} disk_cache_hits="
            f"{st['disk_cache_hits']} disk_cache_misses="
            f"{st['disk_cache_misses']} disk_cache_uncacheable="
            f"{st['disk_cache_uncacheable']} dispatches={st['dispatches']}")
    return chk


def writes(args, x, y, sched, ref, orc, chk):
    """An insert, reads that must see it, a delete, reads that must not
    see the deleted points — each answer checked like the reads."""
    import numpy as np
    from repro.core import DeleteBatch, InsertBatch, PointQuery, RangeCount

    def reads(label, px, py, found: bool):
        w = np.float32(1e-3)
        rects = np.stack([px[:64] - w, py[:64] - w,
                          px[:64] + w, py[:64] + w], 1)
        for spec, a in ((PointQuery(), (px, py)), (RangeCount(), (rects,))):
            out = sched.submit(spec, *a).result()
            check_oracle(chk, f"{label} {spec.kind}", spec, a, out, orc)
            chk.bitwise(f"{label} {spec.kind}", out, ref.run(spec, *a))
            if spec.kind == "point":
                chk.expect(bool(np.all(np.asarray(out) == found)),
                           f"{label}: a point is {'not ' * found}found")

    rng = np.random.default_rng(args.seed + 2)
    nb = 2048
    ins_x = rng.normal(0.5, 0.05, nb).astype(np.float32)
    ins_y = rng.normal(0.55, 0.09, nb).astype(np.float32)
    t0 = time.perf_counter()
    vids = np.asarray(sched.submit(InsertBatch(), ins_x, ins_y).result())
    log(f"insert {nb}: {(time.perf_counter() - t0) * 1e3:.3f} ms")
    chk.bitwise("insert vids", vids, ref.run(InsertBatch(), ins_x, ins_y))
    orc.insert(ins_x, ins_y, vids)
    reads("after insert", ins_x[:128], ins_y[:128], found=True)

    # two deletes of the same shape, each half originals and half
    # buffered inserts: the first compiles, the second times the program
    gone = rng.choice(args.n, 2 * 1024, replace=False)
    for run, h in (("compile included", slice(0, 1024)),
                   ("warm", slice(1024, 2048))):
        del_x = np.concatenate([x[gone[h]], ins_x[h]])
        del_y = np.concatenate([y[gone[h]], ins_y[h]])
        t0 = time.perf_counter()
        removed = int(sched.submit(DeleteBatch(), del_x, del_y).result())
        log(f"delete {len(del_x)} ({run}): removed {removed} in "
            f"{(time.perf_counter() - t0) * 1e3:.3f} ms")
        chk.expect(removed == int(ref.run(DeleteBatch(), del_x, del_y)),
                   "delete count differs from the xla reference")
        chk.expect(removed == orc.delete(del_x, del_y),
                   "delete count differs from the oracle")
        reads("after delete", np.concatenate([del_x[:64], del_x[-64:]]),
              np.concatenate([del_y[:64], del_y[-64:]]), found=False)


def four_chips(args, on_tpu: bool, cache: str):
    import jax
    from repro.core import EngineConfig, Executor

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    x, y, part, index = build(args)
    chk = Checker()
    one = Executor(index, config=EngineConfig(backend=args.backend,
                                              compile_cache_dir=cache))
    devs = jax.devices()[:4]
    meshes = {
        "data4": (jax.make_mesh((4,), ("data",), devices=devs), None,
                  EngineConfig(backend=args.backend)),
        "data2xquery2": (jax.make_mesh((2, 2), ("data", "query"),
                                       devices=devs), "query",
                         EngineConfig(backend=args.backend,
                                      query_shard_threshold=16)),
    }
    fam = read_batches(x, y, part.bounds, args.queries, 1, args.seed)
    # one family per collective pattern: partition psum (point, exact
    # count, windowed join), gathered id windows (range) and the
    # gathered top-k merge (kNN); each executor compiles ~10 programs
    reqs = [(spec,) + a for name in SHARDED_FAMILIES
            for spec, a in fam[name]]
    check_backend("one chip", one.backend, args, on_tpu)
    t0 = time.perf_counter()
    want = one.run_batch(reqs, strict=True)
    log(f"one chip on {jax.devices()[0]}: first run (compiles included) "
        f"{(time.perf_counter() - t0) * 1e3:.3f} ms")
    for label, (mesh, qaxis, cfg) in meshes.items():
        with chk.guard(label):
            sharded(label, index, mesh, qaxis, cfg, reqs, want, chk,
                    args, on_tpu)
    return chk


def sharded(label, index, mesh, qaxis, cfg, reqs, want, chk, args,
            on_tpu: bool):
    """One sharded executor's answers, bitwise against one chip's."""
    from repro.core import Executor
    ex = Executor(index, mesh=mesh, part_axis="data", query_axis=qaxis,
                  config=cfg)
    check_backend(label, ex.backend, args, on_tpu)
    shard_devs = {s.device for s in ex.parts["x"].addressable_shards}
    log(f"{label}: mesh {dict(mesh.shape)}, partition shards on "
        f"{len(shard_devs)} devices")
    chk.expect(len(shard_devs) == 4,
               f"{label}: partition shards on {len(shard_devs)} devices, "
               "not 4")
    t0 = time.perf_counter()
    got = ex.run_batch(reqs, strict=True)
    st = ex.stats()
    log(f"{label}: first run (compiles included) "
        f"{(time.perf_counter() - t0) * 1e3:.3f} ms, "
        f"{st['qshard_executables']} query-sharded executables")
    if qaxis is not None:
        chk.expect(st["qshard_executables"] > 0,
                   f"{label}: query sharding never engaged")
    before = len(chk.failures)
    for (spec, *_), g, w in zip(reqs, got, want):
        chk.bitwise(f"{label} {spec.kind}", g, w)
    log(f"{label}: {len(reqs)} answers compared bitwise with one chip, "
        f"{len(chk.failures) - before} mismatches")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--parts", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=64,
                    help="queries per batch")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "xla", "pallas"))
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on CPU at a small size (never reports a "
                         "TPU; Pallas runs in interpret mode)")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repository's src/ is not beside this script ({SRC})", 2)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, SRC)
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        fail(f"no TPU found (JAX platform {platform!r}); use "
             "--cpu-rehearsal for a small CPU run", 3)
    log(f"device: platform={platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    from repro.core.compile_cache import default_cache_root
    cache = default_cache_root()
    log(f"compile cache root: {cache} (JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')})")

    t0 = time.perf_counter()
    chk = four_chips(args, on_tpu, cache) if args.chips == 4 else \
        one_chip(args, on_tpu, cache)
    log(f"total wall: {time.perf_counter() - t0:.3f} s")
    if chk.failures:
        fail(f"{len(chk.failures)} mismatches: {chk.failures[:20]}")
    count = 4 if args.chips == 4 else len(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
