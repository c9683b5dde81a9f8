"""The ``gaussian`` distribution of the Spider spatial data generator
(Vu, Migliorini, Eldawy and Belussi, "Spatial Data Generators",
SpatialGems 2019): each coordinate drawn from a normal distribution of
mean 0.5 and standard deviation 0.1, and a point that falls outside the
unit square drawn again."""
from __future__ import annotations

import numpy as np


def points(n: int, seed: int, mean: float = 0.5, sd: float = 0.1):
    """``n`` float32 points (x, y) for ``seed``."""
    rng = np.random.default_rng(seed)
    pts = rng.normal(mean, sd, (n, 2))
    bad = ((pts < 0) | (pts > 1)).any(1)
    while bad.any():
        pts[bad] = rng.normal(mean, sd, (int(bad.sum()), 2))
        bad = ((pts < 0) | (pts > 1)).any(1)
    pts = pts.astype(np.float32)
    return pts[:, 0].copy(), pts[:, 1].copy()
