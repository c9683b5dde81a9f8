"""PointQuery of one data point; ``miss_share`` of them moved to a
uniform random spot of the unit square (a miss)."""
import numpy as np


def requests(g, f, n, rng):
    ix = rng.integers(0, len(g.x), n)
    px, py = g.x[ix].copy(), g.y[ix].copy()
    miss = rng.permutation(n) < int(round(f.get("miss_share", 0) * n))
    px[miss] = rng.random(int(miss.sum()), dtype=np.float32)
    return [g.Request("point", g.core.PointQuery(),
                      (px[i:i + 1], py[i:i + 1]), 1) for i in range(n)]
