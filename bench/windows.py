"""The plain reference over points that live on the device.

A deployment drawn on the device (a ``bench/data/<kind>.py`` with
``device_points``) may hold more points than the host should, so the
whole-set ``oracle.Oracle`` never sees it. For the requests a run
checks, plain ``jax.numpy`` passes over the sharded points, a block at
a time, gather a candidate superset to the host: every point inside
some query's window. ``oracle.Oracle`` then judges exactly on those
candidates, with their global ids (a point's id is its position). The
windows, each widened by ``MARGIN`` against rounding and its edges
rounded outward to float32:

  point   exact coordinate equality (only the outward rounding)
  rect    the rect
  circle  the circle's bounding box
  knn     the square of half-width 2w, where w is the Oracle's first
          window 1e-3 * 2^j that holds at least k points

The kNN window is exact by construction: the k points inside the w
square lie within w * sqrt(2) < 0.999 * 2w of the query, so the
Oracle's own loop stops at 2w at the latest, and every window it
looks at lies inside the candidates. w is counted on a square shrunk
by the margin, so rounding cannot make it too small.

The coordinates of the ids a program returns (``vid_d2``) are read
from the device points by position: a wrong id outside every window is
judged on its true distance.

This module imports nothing of the program.
"""
from __future__ import annotations

import copy
import functools

import numpy as np

from bench.oracle import KNN_W0, KNN_WMAX, Oracle

AXIS = "data"           # the mesh axis the points are sharded over
BLOCK = 1 << 14         # points a pass tests against every window at once
MARGIN = 2.0 ** -20     # how far a window is widened against rounding
EMPTY = (np.inf, np.inf, -np.inf, -np.inf)   # a window nothing is inside


def _pow2(n: int, least: int = 8) -> int:
    return max(least, 1 << (max(int(n), 1) - 1).bit_length())


def _padded(boxes) -> np.ndarray:
    """(Q, 4) float32 windows padded with empty ones to a power of two,
    so a pass compiles for few shapes."""
    boxes = np.asarray(boxes, np.float32).reshape(-1, 4)
    out = np.tile(np.float32(EMPTY), (_pow2(len(boxes)), 1))
    out[:len(boxes)] = boxes
    return out


def _inside(xb, yb, boxes):
    """(Q, B): whether point b lies in window q (edges included)."""
    return ((xb[None] >= boxes[:, 0:1]) & (xb[None] <= boxes[:, 2:3])
            & (yb[None] >= boxes[:, 1:2]) & (yb[None] <= boxes[:, 3:4]))


def _over_blocks(x, y, step, init):
    """Fold ``step(acc, i, off, xb, yb, fresh)`` over a shard's blocks.
    The last block is moved back to end at the shard's end; ``fresh``
    masks the points an earlier block already saw."""
    import jax
    import jax.numpy as jnp
    m = x.shape[0]
    r = min(BLOCK, m)

    def body(i, acc):
        off = jnp.minimum(i * r, m - r)
        xb = jax.lax.dynamic_slice_in_dim(x, off, r)
        yb = jax.lax.dynamic_slice_in_dim(y, off, r)
        fresh = off + jnp.arange(r) >= i * r
        return step(acc, i, off, xb, yb, fresh)

    return jax.lax.fori_loop(0, -(-m // r), body, init)


@functools.lru_cache(maxsize=None)
def _count_fn(mesh):
    """Points inside each window, over every shard."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def shard(x, y, boxes):
        def step(acc, i, off, xb, yb, fresh):
            hit = _inside(xb, yb, boxes) & fresh[None]
            return acc + hit.sum(1, dtype=jnp.int32)
        acc = _over_blocks(x, y, step,
                           jnp.zeros(boxes.shape[0], jnp.int32))
        return jax.lax.psum(acc, AXIS)

    return jax.jit(jax.shard_map(shard, mesh=mesh,
                                 in_specs=(P(AXIS), P(AXIS), P()),
                                 out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=None)
def _union_fn(mesh, k: int):
    """Every point inside some window, at most ``k`` a block: per block
    of each shard, (ids or -1, x, y) rows and the block's true count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def shard(x, y, boxes):
        m = x.shape[0]
        r, nb = min(BLOCK, m), -(-m // min(BLOCK, m))
        base = jax.lax.axis_index(AXIS) * m

        def step(acc, i, off, xb, yb, fresh):
            ids, xs, ys, cnt = acc
            hit = _inside(xb, yb, boxes).any(0) & fresh
            j = jnp.nonzero(hit, size=k, fill_value=r)[0]
            ok = j < r
            j = jnp.minimum(j, r - 1)
            row = lambda a, v: jax.lax.dynamic_update_index_in_dim(  # noqa
                a, v, i, 0)
            return (row(ids, jnp.where(ok, base + off + j, -1)),
                    row(xs, xb[j]), row(ys, yb[j]),
                    row(cnt, hit.sum(dtype=jnp.int32)))

        init = (jnp.zeros((nb, k), jnp.int32),
                jnp.zeros((nb, k), jnp.float32),
                jnp.zeros((nb, k), jnp.float32),
                jnp.zeros((nb,), jnp.int32))
        return _over_blocks(x, y, step, init)

    return jax.jit(jax.shard_map(shard, mesh=mesh,
                                 in_specs=(P(AXIS), P(AXIS), P()),
                                 out_specs=(P(AXIS),) * 4,
                                 check_vma=False))


@functools.lru_cache(maxsize=None)
def _take_fn(mesh):
    """Each shard's coordinates at the global positions it holds, NaN
    at the others: (shards, K) each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def shard(x, y, ids):
        m = x.shape[0]
        loc = ids - jax.lax.axis_index(AXIS) * m
        ok = (loc >= 0) & (loc < m)
        loc = jnp.clip(loc, 0, m - 1)
        return (jnp.where(ok, x[loc], jnp.nan)[None],
                jnp.where(ok, y[loc], jnp.nan)[None])

    return jax.jit(jax.shard_map(shard, mesh=mesh,
                                 in_specs=(P(AXIS), P(AXIS), P()),
                                 out_specs=(P(AXIS), P(AXIS)),
                                 check_vma=False))


def _mesh(x):
    return x.sharding.mesh


def count(x, y, boxes) -> np.ndarray:
    """How many points lie in each window (xl, yl, xh, yh)."""
    q = len(np.asarray(boxes).reshape(-1, 4))
    return np.asarray(_count_fn(_mesh(x))(x, y, _padded(boxes)))[:q]


def union(x, y, boxes):
    """(x, y, id) on the host of every point inside some window, in the
    order of their ids."""
    boxes = _padded(boxes)
    k = 64
    while True:
        ids, xs, ys, cnt = (np.asarray(a) for a in
                            _union_fn(_mesh(x), k)(x, y, boxes))
        if cnt.max(initial=0) <= k:
            break
        k = _pow2(cnt.max())
    keep = ids >= 0
    return xs[keep], ys[keep], ids[keep].astype(np.int64)


def take(x, y, ids):
    """The coordinates (x, y) at global positions ``ids``, on the host;
    NaN for an id that names no point."""
    ids = np.asarray(ids, np.int64).reshape(-1)
    n = x.shape[0]
    ok = (ids >= 0) & (ids < n)
    q = np.full(_pow2(len(ids)), -1, np.int32)
    q[:len(ids)] = np.where(ok, ids, -1)
    xs, ys = (np.asarray(a) for a in _take_fn(_mesh(x))(x, y, q))
    shards = xs.shape[0]
    owner = np.where(ok, ids // (n // shards), 0)
    col = np.arange(len(ids))
    nan = np.float32(np.nan)
    return (np.where(ok, xs[owner, col], nan),
            np.where(ok, ys[owner, col], nan))


def knn_windows(x, y, qx, qy, k) -> np.ndarray:
    """Per kNN query, the Oracle's first window w = KNN_W0 * 2^j whose
    square, shrunk by MARGIN, holds at least k points (or the first
    above KNN_WMAX, where the Oracle stops looking)."""
    qx, qy = np.float64(qx), np.float64(qy)
    k = np.asarray(k)
    w = np.full(len(qx), KNN_W0)
    todo = np.ones(len(qx), bool)
    while todo.any():
        h = w[todo] - MARGIN
        c = count(x, y, np.stack([qx[todo] - h, qy[todo] - h,
                                  qx[todo] + h, qy[todo] + h], 1))
        done = (c >= k[todo]) | (w[todo] > KNN_WMAX)
        idx = np.flatnonzero(todo)
        todo[idx[done]] = False
        w[idx[~done]] *= 2.0
    return w


class Reference:
    """``oracle.Oracle``'s answers to ``reqs`` over the device points
    ``x``, ``y`` (sharded over a ``"data"`` mesh), judged on the
    candidates of those requests' windows. It answers as the Oracle
    does, with global ids, and with ``check.judge`` it decides the same.
    ``dtype``, below float32, is the control (see ``oracle.Oracle``)."""

    def __init__(self, x, y, reqs, dtype=None):
        self.x, self.y = x, y
        ux, uy, self.ids = union(x, y, self._windows(reqs))
        self.orc = Oracle(ux, uy, dtype)
        self._union = (ux, uy)

    def lowered(self, dtype) -> "Reference":
        """The same candidates, judged in ``dtype`` (the control)."""
        ref = copy.copy(self)
        ref.orc = Oracle(*self._union, dtype)
        return ref

    def _windows(self, reqs) -> np.ndarray:
        f32 = lambda v: np.float64(np.float32(v))  # noqa: E731
        boxes, knn = [], []
        for r in reqs:
            kind, a = r.spec.kind, r.args
            for q in range(len(a[0])):
                if kind == "point":
                    px, py = f32(a[0][q]), f32(a[1][q])
                    boxes.append((px, py, px, py))
                elif kind in ("range", "range_count"):
                    xl, yl, xh, yh = (f32(v) for v in a[0][q])
                    boxes.append((xl - MARGIN, yl - MARGIN,
                                  xh + MARGIN, yh + MARGIN))
                elif kind == "circle":
                    cx, cy, rad = f32(a[0][q]), f32(a[1][q]), f32(a[2][q])
                    h = rad + MARGIN
                    boxes.append((cx - h, cy - h, cx + h, cy + h))
                elif kind == "knn":
                    knn.append((f32(a[0][q]), f32(a[1][q]), r.spec.k))
                else:
                    raise ValueError(f"no window for {kind!r}")
        if knn:
            qx, qy, k = (np.array(c) for c in zip(*knn))
            h = 2.0 * knn_windows(self.x, self.y, qx, qy, k) + MARGIN
            boxes.extend(zip(qx - h, qy - h, qx + h, qy + h))
        # the edges as float32, widened outward by the rounding
        b = np.asarray(boxes, np.float64).reshape(-1, 4)
        lo = np.nextafter(b[:, :2].astype(np.float32), np.float32(-np.inf))
        hi = np.nextafter(b[:, 2:].astype(np.float32), np.float32(np.inf))
        return np.concatenate([lo, hi], 1)

    # -- the Oracle's interface, with global ids -----------------------

    def point(self, qx, qy):
        if not len(self.ids):           # no candidate: every lookup misses
            return np.zeros(len(np.atleast_1d(qx)), bool)
        return self.orc.point(qx, qy)

    def rect_ids(self, rect):
        return self.ids[self.orc.rect_ids(rect)]

    def circle_ids(self, cx, cy, r):
        return self.ids[self.orc.circle_ids(cx, cy, r)]

    def knn_d2(self, qx, qy, k):
        return self.orc.knn_d2(qx, qy, k)

    def knn_ids(self, qx, qy, k):
        return self.ids[self.orc.knn_ids(qx, qy, k)]

    def vid_d2(self, vids, qx, qy):
        """Exact squared distances of the points with these ids, read
        from the device points (NaN for an id that names no point)."""
        px, py = take(self.x, self.y, vids)
        qx, qy = (np.float32(v) for v in self.orc.q([qx, qy]))
        return Oracle._d2(px, py, qx, qy)
