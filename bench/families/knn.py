"""Knn(k) at a data point moved by N(0, ``jitter``) in each axis."""
import numpy as np


def requests(g, f, n, rng):
    ix = rng.integers(0, len(g.x), n)
    j = f.get("jitter", 0.0)
    qx = (g.x[ix] + rng.normal(0, j, n)).astype(np.float32)
    qy = (g.y[ix] + rng.normal(0, j, n)).astype(np.float32)
    spec = g.core.Knn(k=int(f["k"]), mode=f.get("mode", "pruned"))
    return [g.Request("knn", spec, (qx[i:i + 1], qy[i:i + 1]), 1)
            for i in range(n)]
