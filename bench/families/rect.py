"""RangeQuery (``materialize``) or RangeCount of one square of area
``selectivity`` centred on a data point."""
import numpy as np


def requests(g, f, n, rng):
    ix = rng.integers(0, len(g.x), n)
    w = np.float32(np.sqrt(f["selectivity"]))
    cx, cy = g.x[ix], g.y[ix]
    r = np.stack([cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2],
                 axis=1).astype(np.float32)
    spec = (g.core.RangeQuery() if f.get("materialize")
            else g.core.RangeCount())
    return [g.Request("rect", spec, (r[i:i + 1],), 1) for i in range(n)]
