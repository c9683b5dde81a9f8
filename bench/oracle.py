"""The plain reference: every query family computed directly in NumPy.

A copy of ``chip_smoke.Oracle`` (exact coordinate membership, inclusive
rectangles, an MBR-then-distance circle test in float32, and kNN on
float64 distances of the stored float32 coordinates), independent of
``src/``; ``test_bench.py`` holds the two to each other.

``Oracle(x, y, dtype=...)`` with a ``dtype`` below float32 stores the
points (and rounds every query argument) in that type: the control
that a sound comparison has to reject.
"""
from __future__ import annotations

import numpy as np

KNN_W0 = 1e-3       # the first half-width of the kNN window
KNN_WMAX = 4.0      # past it, the kNN window takes every point


def coord_keys(x, y):
    """Exact (x, y) identity as one uint64 per point."""
    xb = np.ascontiguousarray(x, np.float32).view(np.uint32)
    yb = np.ascontiguousarray(y, np.float32).view(np.uint32)
    return (xb.astype(np.uint64) << np.uint64(32)) | yb.astype(np.uint64)


def lower_precision(a, dtype):
    """``a`` rounded to ``dtype`` and widened back to float32."""
    if dtype is None:
        return np.asarray(a, np.float32)
    return np.asarray(np.asarray(a, np.float32).astype(dtype), np.float32)


class Oracle:
    """Answers over the points (ids 0..n-1)."""

    def __init__(self, x, y, dtype=None):
        self.dtype = dtype
        self.x = lower_precision(x, dtype)
        self.y = lower_precision(y, dtype)
        self.n = len(self.x)
        order = np.argsort(self.x, kind="stable")
        self.sx, self.sy, self.sv = self.x[order], self.y[order], order
        self.keys = np.sort(coord_keys(self.x, self.y))

    def q(self, a):
        """A query argument as the reference sees it."""
        return lower_precision(a, self.dtype)

    # -- candidate sets ------------------------------------------------

    def _slab(self, xl, xh):
        """Points with xl <= x <= xh: (x, y, id)."""
        i = np.searchsorted(self.sx, xl, side="left")
        j = np.searchsorted(self.sx, xh, side="right")
        return self.sx[i:j], self.sy[i:j], self.sv[i:j]

    # -- the query families --------------------------------------------

    def point(self, qx, qy):
        k = coord_keys(self.q(qx), self.q(qy))
        pos = np.searchsorted(self.keys, k, side="left")
        return (pos < len(self.keys)) & (self.keys[np.minimum(
            pos, len(self.keys) - 1)] == k)

    def rect_ids(self, rect):
        xl, yl, xh, yh = (np.float32(v) for v in self.q(rect))
        sx, sy, sv = self._slab(xl, xh)
        return sv[(sy >= yl) & (sy <= yh)]

    def circle_ids(self, cx, cy, r):
        cx, cy, r = (np.float32(v) for v in self.q([cx, cy, r]))
        sx, sy, sv = self._slab(cx - r, cx + r)
        dx, dy = sx - cx, sy - cy
        m = ((sy >= cy - r) & (sy <= cy + r) &
             (dx * dx + dy * dy <= r * r))
        return sv[m]

    def knn_d2(self, qx, qy, k):
        """The k smallest squared distances, exact: float64 from the
        stored float32 coordinates, over a square window grown until
        its k-th distance lies inside the window's inscribed circle."""
        qx, qy = (np.float32(v) for v in self.q([qx, qy]))
        w = KNN_W0
        while True:
            sx, sy, _ = self._slab(qx - w, qx + w)
            m = np.abs(sy - qy) <= w
            if m.sum() >= k or w > KNN_WMAX:
                d2 = np.sort(self._d2(sx[m], sy[m], qx, qy))[:k]
                if len(d2) == k and (np.sqrt(d2[-1]) < 0.999 * w
                                     or w > KNN_WMAX):
                    return d2
            w *= 2.0

    def knn_ids(self, qx, qy, k):
        """Ids of the k nearest points (ties broken by id)."""
        qx, qy = (np.float32(v) for v in self.q([qx, qy]))
        kth = self.knn_d2(qx, qy, k)[-1]
        w = float(np.sqrt(kth)) * 1.001 + 1e-12
        sx, sy, sv = self._slab(qx - w, qx + w)
        d2 = self._d2(sx, sy, qx, qy)
        order = np.lexsort((sv, d2))[:k]
        return sv[order]

    def vid_d2(self, vids, qx, qy):
        """Exact squared distances of the points with these ids (NaN
        for an id that names no point)."""
        vids = np.asarray(vids, np.int64)
        ok = (vids >= 0) & (vids < self.n)
        px = np.where(ok, self.x[np.where(ok, vids, 0)], np.nan)
        py = np.where(ok, self.y[np.where(ok, vids, 0)], np.nan)
        qx, qy = (np.float32(v) for v in self.q([qx, qy]))
        return self._d2(px, py, qx, qy)

    @staticmethod
    def _d2(px, py, qx, qy):
        dx = px.astype(np.float64) - np.float64(np.float32(qx))
        dy = py.astype(np.float64) - np.float64(np.float32(qy))
        return dx * dx + dy * dy
