"""Compiled read prep (core/prep.py, DESIGN.md §9).

What must hold:

  bit-exact   each read kind's prepared argument tuple (pargs) from its
              compiled prep program equals, bit for bit, what the
              eager helpers build op by op (the references below, the
              executor's host prep before it was compiled). A point
              lookup finds its point only when the query key equals
              the stored key, so one ulp off in a quantization is a
              wrong answer. Inputs hit exact cell edges, the key
              bounds, values just outside them and zero-width rects.
  no eager    once a kind and width are warm, ``run()`` calls no key
              encode, box distance or edge mask on the host; every
              read run launches one prep program, which is not a
              dispatch: ``dispatches`` counts what it counted before.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CircleQuery, EngineConfig, Executor, Knn,
                        PointQuery, RangeCount, RangeQuery, SpatialJoin,
                        build_index, fit)
from repro.core import keys as K
from repro.core import local_ops as L
from repro.core import queries as Q
from repro.core.keys import KeySpec
from repro.data import spatial as ds

KINDS = ("point", "range_count", "range", "circle", "knn", "join")
K_NN = 5
EDGES = 6


# -- the eager references ---------------------------------------------

def _rect_keys(ex, rects):
    klo, khi = K.rect_key_range(rects, ex.spec)
    return K.keys_to_f32(klo), K.keys_to_f32(khi)


def _knn_r0(ex, qx, qy, k):
    r0g = float(np.sqrt(max(k, 1) / (np.pi * ex.density)))
    bd2 = Q.box_min_dist2(qx, qy, ex.bounds)
    pid0 = jnp.argmin(bd2, axis=1)
    b0 = ex.bounds[pid0]
    area0 = jnp.maximum((b0[:, 2] - b0[:, 0]) *
                        (b0[:, 3] - b0[:, 1]), 1e-30)
    d0 = jnp.maximum(ex.index.count[pid0] / area0, 1e-30)
    r0 = jnp.sqrt(k / (jnp.pi * d0)).astype(jnp.float32)
    return jnp.maximum(r0, r0g)


def eager_pargs(ex, kind, args):
    """The executor's op-by-op host prep, as it was."""
    if kind == "point":
        qx = jnp.asarray(args[0], jnp.float32)
        qy = jnp.asarray(args[1], jnp.float32)
        return qx, qy, K.keys_to_f32(K.make_keys(qx, qy, ex.spec))
    if kind in ("range_count", "range"):
        rects = jnp.asarray(args[0], jnp.float32)
        return (rects,) + _rect_keys(ex, rects)
    if kind == "circle":
        cx, cy, r = (jnp.asarray(a, jnp.float32) for a in args)
        rects = jnp.stack([cx - r, cy - r, cx + r, cy + r], axis=-1)
        return ((rects,) + _rect_keys(ex, rects)
                + (jnp.stack([cx, cy, r], axis=-1),))
    if kind == "knn":
        qx = jnp.asarray(args[0], jnp.float32)
        qy = jnp.asarray(args[1], jnp.float32)
        return qx, qy, _knn_r0(ex, qx, qy, K_NN)
    polys = jnp.asarray(args[0], jnp.float32)
    n_edges = jnp.asarray(args[1], jnp.int32)
    em = L._edge_mask(polys, n_edges)
    mbrs = jnp.concatenate([
        jnp.min(jnp.where(em, polys, 3e38), axis=1),
        jnp.max(jnp.where(em, polys, -3e38), axis=1)], axis=-1)
    klo, khi = _rect_keys(ex, mbrs)
    return polys, n_edges, jnp.concatenate(
        [mbrs, klo[:, None], khi[:, None]], axis=-1)


# -- inputs -----------------------------------------------------------

SPEC = {"point": PointQuery(), "range_count": RangeCount(),
        "range": RangeQuery(), "circle": CircleQuery(),
        "knn": Knn(k=K_NN), "join": SpatialJoin()}


def coords(spec: KeySpec, axis: int, rng, n: int = 64) -> np.ndarray:
    """n float32 coordinates on one axis: exact cell edges
    lo + j (hi - lo) / 2^bits, the bounds, the floats just outside and
    inside them, and random values, shuffled."""
    lo, hi = spec.bounds[axis], spec.bounds[axis + 2]
    f32 = np.float32
    cells = 1 << spec.bits_per_dim
    j = rng.integers(0, cells + 1, 24)
    edges = (f32(lo) + j.astype(f32) * f32((hi - lo) / cells)).astype(f32)
    # the same edges computed the other way round, which rounds apart
    edges2 = (f32(lo) + (j / cells * (hi - lo))).astype(f32)
    rim = np.array([lo, hi, np.nextafter(f32(lo), f32(-np.inf)),
                    np.nextafter(f32(hi), f32(np.inf)),
                    np.nextafter(f32(lo), f32(np.inf)),
                    np.nextafter(f32(hi), f32(-np.inf)),
                    lo - 0.25 * (hi - lo), hi + 0.25 * (hi - lo)], f32)
    rand = rng.uniform(lo, hi, n).astype(f32)
    out = np.concatenate([edges, edges2, rim, rand])[:n]
    return out[rng.permutation(n)]


def inputs(kind: str, spec: KeySpec, seed: int, n: int = 64):
    rng = np.random.default_rng(seed)
    x, y = coords(spec, 0, rng, n), coords(spec, 1, rng, n)
    if kind in ("point", "knn"):
        return x, y
    if kind in ("range_count", "range"):
        x2, y2 = coords(spec, 0, rng, n), coords(spec, 1, rng, n)
        flat = rng.random(n) < 0.25           # zero-width rects
        x2 = np.where(flat, x, x2)
        y2 = np.where(rng.random(n) < 0.25, y, y2)
        return (np.stack([np.minimum(x, x2), np.minimum(y, y2),
                          np.maximum(x, x2), np.maximum(y, y2)],
                         -1).astype(np.float32),)
    if kind == "circle":
        cell = (spec.bounds[2] - spec.bounds[0]) / (1 << spec.bits_per_dim)
        r = rng.choice(np.array([0.0, cell, 0.5 * cell, 1e-3, 0.05],
                                np.float32), n)
        return x, y, r.astype(np.float32)
    polys = np.stack([coords(spec, 0, rng, n * EDGES).reshape(n, EDGES),
                      coords(spec, 1, rng, n * EDGES).reshape(n, EDGES)],
                     -1).astype(np.float32)
    return polys, rng.integers(3, EDGES + 1, n).astype(np.int32)


@pytest.fixture(scope="module", params=["data", "unit"])
def ex(request):
    """An executor over a small index, keyed either on the data's own
    padded bounds (a scale that is no power of two) or on the unit
    square."""
    x, y = ds.make("gaussian", 3000, seed=5)
    part = fit("kdtree", x, y, 4, seed=0)
    spec = None if request.param == "data" else \
        KeySpec(bounds=(0.0, 0.0, 1.0, 1.0))
    return Executor(build_index(x, y, part, key_spec=spec),
                    config=EngineConfig(backend="xla"))


def _bits(a):
    a = np.asarray(a)
    return a.shape, a.dtype, a.tobytes()


@pytest.mark.parametrize("width", [1, 2, 64])
@pytest.mark.parametrize("kind", KINDS)
def test_compiled_prep_is_bitwise_the_eager_prep(ex, kind, width):
    args = inputs(kind, ex.spec, seed=width)
    n = len(args[0])
    before = ex.stats()["prep_launches"]
    for s in range(0, n, width):
        chunk = tuple(a[s:s + width] for a in args)
        got = ex._prepare(SPEC[kind], chunk)
        want = eager_pargs(ex, kind, chunk)
        assert len(got) == len(want)
        for i, (g, w) in enumerate(zip(got, want)):
            assert _bits(g) == _bits(w), \
                f"{kind} w{width} rows {s}+: parg {i} differs"
    assert ex.stats()["prep_launches"] - before == n // width
    names = {name for k, _sig, c in ex.compiled_programs()
             if k[2][0] == "prep"
             for name in [c.as_text().split("\n", 1)[0]]}
    assert any("jit_lilis_prep_" in nm and f"_w{width}" in nm
               for nm in names), names


# -- nothing of the prep runs eagerly once warm ------------------------

@pytest.fixture(scope="module")
def warm_ex():
    x, y = ds.make("gaussian", 3000, seed=6)
    part = fit("kdtree", x, y, 4, seed=0)
    return Executor(build_index(x, y, part),
                    config=EngineConfig(backend="xla"))


@pytest.mark.parametrize("kind", KINDS)
def test_warm_reads_run_no_eager_prep(warm_ex, kind, monkeypatch):
    ex = warm_ex
    calls = []

    def counted(mod, name):
        fn = getattr(mod, name)

        def wrapper(*a, **kw):
            calls.append(name)
            return fn(*a, **kw)
        monkeypatch.setattr(mod, name, wrapper)

    for mod, name in ((K, "quantize"), (K, "morton_encode"),
                      (Q, "box_min_dist2"), (L, "_edge_mask")):
        counted(mod, name)
    spec = SPEC[kind]
    for width in (1, 2):
        args = inputs(kind, ex.spec, seed=30 + width, n=width)
        for _ in range(2):       # the first run settles the tier, the
            jax.block_until_ready(ex.run(spec, *args))   # second fuses
        calls.clear()
        st0 = ex.stats()
        for _ in range(3):
            jax.block_until_ready(ex.run(spec, *args))
        st1 = ex.stats()
        assert calls == [], f"{kind} w{width}: eager prep ran {calls}"
        assert st1["prep_launches"] - st0["prep_launches"] == 3
        # one compiled program per narrow read, as before the prep was
        # compiled: prep launches are not dispatches
        assert st1["dispatches"] - st0["dispatches"] == 3
        assert st1["compile_ms_total"] == st0["compile_ms_total"]
