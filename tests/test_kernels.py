"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py),
interpret mode on CPU, across shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, fit
from repro.core import keys as CK
from repro.data import spatial as ds
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def part_index():
    x, y = ds.make("taxi", 6000, seed=2)
    part = fit("kdtree", x, y, 4, seed=0)
    idx = build_index(x, y, part)
    return x, y, idx


@pytest.mark.parametrize("n", [7, 128, 1000, 4096])
def test_morton_kernel_sweep(n):
    rng = np.random.default_rng(n)
    qx = jnp.asarray(rng.integers(0, 1 << 11, n), jnp.uint32)
    qy = jnp.asarray(rng.integers(0, 1 << 11, n), jnp.uint32)
    got = np.asarray(ops.morton_encode(qx, qy))
    want = np.asarray(ref.morton_encode(qx, qy))
    assert (got == want).all()


@pytest.mark.parametrize("p", [0, 1, 3])
@pytest.mark.parametrize("nq", [5, 300])
def test_spline_search_kernel_sweep(part_index, p, nq):
    x, y, idx = part_index
    rng = np.random.default_rng(p * 100 + nq)
    q = jnp.asarray(np.sort(rng.integers(0, 1 << 22, nq)), jnp.float32)
    keys_f = CK.keys_to_f32(idx.key[p])
    args = (q, idx.knot_keys[p], idx.knot_pos[p], idx.radix_table[p],
            keys_f, idx.radix_kmin[p], idx.radix_scale[p],
            idx.n_knots[p], idx.count[p])
    kw = dict(probe=idx.probe, radix_bits=idx.radix_bits)
    got = np.asarray(ops.spline_search(*args, **kw))
    want = np.asarray(ref.spline_search(*args, **kw))
    assert (got == want).all()
    # and the oracle itself is a true lower bound
    c = int(idx.count[p])
    truth = np.searchsorted(np.asarray(keys_f)[:c], np.asarray(q),
                            side="left")
    assert (want == truth).all()


@pytest.mark.parametrize("p", [0, 3])
def test_spline_search_kernel_row_ends(part_index, p):
    """Keys below the first and past the last key of a partition put
    the probe window at both clipped ends of the row (the kernel loads
    it from a 128-aligned lane and masks it)."""
    x, y, idx = part_index
    keys_f = np.asarray(CK.keys_to_f32(idx.key[p]))
    c = int(idx.count[p])
    q = np.sort(np.concatenate([
        [0.0, keys_f[0], keys_f[0] + 1, keys_f[c - 1], keys_f[c - 1] + 1,
         float(1 << 22)],
        keys_f[:c][np.linspace(0, c - 1, 122).astype(int)]])
    ).astype(np.float32)
    args = (jnp.asarray(q), idx.knot_keys[p], idx.knot_pos[p],
            idx.radix_table[p], jnp.asarray(keys_f), idx.radix_kmin[p],
            idx.radix_scale[p], idx.n_knots[p], idx.count[p])
    kw = dict(probe=idx.probe, radix_bits=idx.radix_bits)
    got = np.asarray(ops.spline_search(*args, **kw))
    assert (got == np.asarray(ref.spline_search(*args, **kw))).all()
    assert (got == np.searchsorted(keys_f[:c], q, side="left")).all()


@pytest.mark.parametrize("nq", [3, 64, 200])
def test_range_count_kernel_sweep(part_index, nq):
    x, y, idx = part_index
    p = 1
    rng = np.random.default_rng(nq)
    rects = jnp.asarray(
        ds.random_rects(nq, 1e-2, (0, 0, 1, 1), seed=nq))
    n_pad = idx.n_pad
    s = rng.integers(0, n_pad // 2, nq)
    e = s + rng.integers(0, n_pad // 2, nq)
    se = jnp.asarray(np.stack([s, e], 1), jnp.float32)
    got = np.asarray(ops.range_count(rects, se, idx.count[p],
                                     idx.x[p], idx.y[p]))
    want = np.asarray(ref.range_count(rects, se, idx.count[p],
                                      idx.x[p], idx.y[p]))
    assert (got == want).all()


@pytest.mark.parametrize("nq", [3, 64, 200])
def test_circle_count_kernel_sweep(part_index, nq):
    x, y, idx = part_index
    p = 1
    rng = np.random.default_rng(nq + 7)
    ix = rng.integers(0, len(x), nq)
    cx, cy = x[ix], y[ix]
    r = rng.uniform(1e-3, 5e-2, nq).astype(np.float32)
    # query 0: a full-interval circle around a partition point, so the
    # sweep always exercises at least one in-circle match
    cx[0], cy[0], r[0] = float(idx.x[p][0]), float(idx.y[p][0]), 0.01
    rects = jnp.asarray(np.stack([cx - r, cy - r, cx + r, cy + r], 1))
    circ = jnp.asarray(np.stack([cx, cy, r], 1))
    n_pad = idx.n_pad
    s = rng.integers(0, n_pad // 2, nq)
    e = s + rng.integers(0, n_pad // 2, nq)
    s[0], e[0] = 0, n_pad
    se = jnp.asarray(np.stack([s, e], 1), jnp.float32)
    got = np.asarray(ops.circle_count(rects, se, circ, idx.count[p],
                                      idx.x[p], idx.y[p]))
    want = np.asarray(ref.circle_count(rects, se, circ, idx.count[p],
                                       idx.x[p], idx.y[p]))
    assert (got == want).all()
    assert want.sum() > 0      # the sweep actually exercises matches


@pytest.mark.parametrize("nq", [2, 40, 150])
def test_point_probe_kernel_sweep(part_index, nq):
    x, y, idx = part_index
    p = 2
    rng = np.random.default_rng(nq + 3)
    c = int(idx.count[p])
    # half real partition points (must be found), half misses
    pos = rng.integers(0, c, nq)
    keys_f = np.asarray(CK.keys_to_f32(idx.key[p]))
    px, py = np.asarray(idx.x[p]), np.asarray(idx.y[p])
    qx = px[pos].copy()
    qy = py[pos].copy()
    qk = keys_f[pos].copy()
    miss = rng.random(nq) < 0.5
    qx[miss] += 1.0            # same key, wrong coordinate
    probe = idx.probe
    start = np.clip(pos - probe // 2, 0, idx.n_pad - probe)
    lanes = start[:, None] + np.arange(probe)[None, :]
    args = (jnp.asarray(qk), jnp.asarray(qx), jnp.asarray(qy),
            jnp.asarray(keys_f[lanes]), jnp.asarray(px[lanes]),
            jnp.asarray(py[lanes]))
    got = np.asarray(ops.point_probe(*args, probe=probe))
    want = np.asarray(ref.point_probe(*args, probe=probe))
    assert (got == want).all()
    assert ((want > 0) == ~miss).all()


@pytest.mark.parametrize("k", [1, 8, 16])
@pytest.mark.parametrize("nq", [4, 130])
def test_knn_topk_kernel_sweep(part_index, k, nq):
    x, y, idx = part_index
    p = 2
    rng = np.random.default_rng(k * 7 + nq)
    ix = rng.integers(0, len(x), nq)
    qxy = jnp.asarray(np.stack([x[ix], y[ix]], 1))
    gn, gi = ops.knn_topk(qxy, idx.count[p], idx.x[p], idx.y[p], k=k)
    wn, wi = ref.knn_topk(qxy, idx.count[p], idx.x[p], idx.y[p], k=k)
    assert np.allclose(np.asarray(gn), np.asarray(wn), rtol=1e-6)
    for a, b in zip(np.asarray(gi), np.asarray(wi)):
        assert set(a[a >= 0]) == set(b[b >= 0])


@pytest.mark.parametrize("k", [3, 10])
def test_knn_topk_tie_order_is_top_k(k):
    """Points on a coarse grid put many candidates at equal distance:
    the kernel must break every tie by lowest position, bitwise as
    lax.top_k does (its indices, not just its distances)."""
    rng = np.random.default_rng(k)
    n = 3000
    px = (rng.integers(0, 6, n) / 8).astype(np.float32)
    py = (rng.integers(0, 6, n) / 8).astype(np.float32)
    qxy = jnp.asarray(np.stack([px[:40], py[:40]], 1))
    cnt = np.int32(n - 5)
    gn, gi = ops.knn_topk(qxy, cnt, jnp.asarray(px), jnp.asarray(py), k=k)
    wn, wi = ref.knn_topk(qxy, cnt, jnp.asarray(px), jnp.asarray(py), k=k)
    assert np.array_equal(np.asarray(gn), np.asarray(wn))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))


@pytest.mark.parametrize("edges", [3, 7, 12])
def test_pip_kernel_sweep(part_index, edges):
    x, y, idx = part_index
    p = 0
    polys, ne = ds.random_polygons(1, (0, 0, 1, 1), seed=edges,
                                   max_edges=edges)
    got = np.asarray(ops.point_in_polygon(polys[0], ne[0],
                                          idx.x[p], idx.y[p]))
    want = np.asarray(ref.point_in_polygon(jnp.asarray(polys[0]), ne[0],
                                           idx.x[p], idx.y[p]))
    assert (got == want).all()


def test_kernels_f32_vs_f64_oracle(part_index):
    """dtype sweep: the f32 kernel's counts match a float64 numpy oracle
    on rect containment (coords are exactly representable)."""
    x, y, idx = part_index
    p = 1
    rects = ds.random_rects(32, 1e-2, (0, 0, 1, 1), seed=99)
    se = np.stack([np.zeros(32), np.full(32, idx.n_pad)], 1)
    got = np.asarray(ops.range_count(
        jnp.asarray(rects), jnp.asarray(se, jnp.float32),
        idx.count[p], idx.x[p], idx.y[p]))
    c = int(idx.count[p])
    px = np.asarray(idx.x[p][:c], np.float64)
    py = np.asarray(idx.y[p][:c], np.float64)
    want = np.array([np.sum((px >= r[0]) & (px <= r[2]) &
                            (py >= r[1]) & (py <= r[3]))
                     for r in np.asarray(rects, np.float64)])
    assert (got == want).all()
