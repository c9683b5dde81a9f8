"""Wide-batch tier-bucketed dispatch + streaming chunked merges
(DESIGN.md §13): bitwise parity pins.

A wide non-strict batch over a sticky spec routes through
``Executor._run_bucketed``: one device probe (counted in
``probe_syncs``, NOT ``host_syncs``), per-tier buckets at their own
(cap, cand) fused executables, scatter-back in request order. The
batches here deliberately MIX escalation tiers (tiny windows next to
near-global ones) so several buckets actually form, and every result
must equal per-query serial submission bit for bit on both kernel
backends — including the stable request order after the scatter-back
and the -1 width normalization of materialized id planes.

The chunked paths ride along implicitly (the escalated sticky caps
push the candidate planes past EngineConfig.scan_chunk_elems) and
explicitly via the chunked-vs-monolithic helper equivalence tests.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CircleQuery, EngineConfig, Executor, Knn,
                        RangeQuery, SpatialJoin, build_index, fit)
from repro.core import local_ops as L
from repro.data import spatial as ds

N = 20000


@pytest.fixture(scope="module")
def built():
    x, y = ds.make("taxi", N, seed=2)
    part = fit("kdtree", x, y, 24)
    return x, y, part, build_index(x, y, part)


def _mixed_queries(x, y, part, n_q=40):
    """Query sets spanning escalation tiers: mostly tiny, a few huge."""
    rng = np.random.default_rng(0)
    ix = rng.integers(0, len(x), n_q)
    qx = x[ix].astype(np.float32)
    qy = y[ix].astype(np.float32)
    rects = np.asarray(ds.random_rects(n_q, 1e-3, part.bounds, seed=3,
                                       centers=(x, y)))
    rects[:6] = np.asarray(ds.random_rects(6, 0.3, part.bounds, seed=4,
                                           centers=(x, y)))
    r = np.full(n_q, 2e-3, np.float32)
    r[:5] = 0.25
    polys, ne = ds.random_polygons(n_q, part.bounds, seed=5)
    return [("range", RangeQuery(), (rects,)),
            ("circle", CircleQuery(), (qx, qy, r)),
            ("circle_mat", CircleQuery(materialize=True), (qx, qy, r)),
            ("knn5", Knn(k=5), (qx, qy)),
            ("join", SpatialJoin(), (polys, ne))]


@pytest.fixture(scope="module", params=["xla", "pallas"])
def warmed(request, built):
    x, y, part, index = built
    ex = Executor(index, config=EngineConfig(backend=request.param))
    specs = _mixed_queries(x, y, part)
    for _, spec, args in specs:          # strict warm settles sticky
        ex.run(spec, *args, strict=True)
    ex.maintain()
    return ex, specs


def test_bucketed_bitwise_vs_serial(warmed):
    ex, specs = warmed
    for name, spec, args in specs:
        serial = [ex.run(spec, *(a[i:i + 1] for a in args))
                  for i in range(args[0].shape[0])]
        hs0, ps0 = ex.host_syncs, ex.probe_syncs
        wide = ex.run(spec, *args)
        # the probe readback is accounted separately from the ok-flag
        # retry syncs (the zero-sync steady contract)
        assert ex.host_syncs == hs0, name
        assert ex.probe_syncs == ps0 + 1, name
        wl = wide if isinstance(wide, tuple) else (wide,)
        for i, s in enumerate(serial):
            sl = s if isinstance(s, tuple) else (s,)
            assert len(sl) == len(wl), name
            for a, b in zip(sl, wl):
                a, b = np.asarray(a), np.asarray(b)[i:i + 1]
                assert a.shape == b.shape, (name, i)
                assert (a == b).all(), (name, i)


def test_bucketed_forms_multiple_buckets(warmed):
    ex, specs = warmed
    # the mixed-tier batches must actually split: more than one fused
    # dispatch per wide run for at least the materializing specs
    for name, spec, args in specs:
        d0 = ex.dispatches
        ex.run(spec, *args)
        launches = ex.dispatches - d0 - 1      # minus the probe
        assert launches >= 1, name
        if name in ("range", "circle_mat"):
            assert launches >= 2, (name, launches)


def test_narrow_batches_skip_the_probe(warmed):
    ex, specs = warmed
    for name, spec, args in specs:
        ps0 = ex.probe_syncs
        ex.run(spec, *(a[:4] for a in args))
        assert ex.probe_syncs == ps0, name     # below tier_bucket_min


def test_probe_executables_cached_per_base(warmed):
    ex, _ = warmed
    probes = [k for k in ex.cache_keys() if k[3] == "p"]
    assert len(probes) >= 4        # range/circle x2/knn/join bases
    assert all(not k[1] for k in probes)       # never query-sharded


# -- chunked helper micro-equivalence ------------------------------------

def test_compact_ids_matches_streamed_compaction():
    rng = np.random.default_rng(7)
    vids = np.where(rng.random((8, 96)) < 0.4,
                    rng.integers(0, 999, (8, 96)), -1).astype(np.int32)
    keep = 24
    mono = np.asarray(L._compact_ids(jnp.asarray(vids), keep))
    # streamed: carry-before-chunk recompaction over 4 chunks
    carry = jnp.full((8, keep), -1, jnp.int32)
    for ch in np.split(vids, 4, axis=1):
        carry = L._compact_ids(
            jnp.concatenate([carry, jnp.asarray(ch)], axis=1), keep)
    assert (np.asarray(carry) == mono).all()


def test_topk_merge_matches_monolithic_topk():
    from repro.core.backends import resolve_backend
    bk = resolve_backend("xla")
    rng = np.random.default_rng(9)
    k = 5
    neg = -rng.random((6, 64)).astype(np.float32)
    vid = rng.integers(0, 999, (6, 64)).astype(np.int32)
    neg[rng.random((6, 64)) < 0.5] = -3e38    # masked slots
    bn, ix = jax.lax.top_k(jnp.asarray(neg), k)
    bv = jnp.take_along_axis(jnp.asarray(vid), ix, axis=1)
    cn = jnp.full((6, k), -3e38, jnp.float32)
    cv = jnp.full((6, k), -1, jnp.int32)
    for lo in range(0, 64, 16):
        cn, cv = bk.topk_merge(cn, cv, jnp.asarray(neg[:, lo:lo + 16]),
                               jnp.asarray(vid[:, lo:lo + 16]), k)
    assert (np.asarray(cn) == np.asarray(bn)).all()
    # vids may differ only where the value is the -3e38 sentinel (the
    # init carry is payload-identical to masked slots there)
    real = np.asarray(bn) > -3e38
    assert (np.asarray(cv)[real] == np.asarray(bv)[real]).all()


def test_chunked_circle_equals_monolithic():
    """Force chunking via a tiny scan_chunk_elems budget and compare
    against the monolithic path bitwise (same sticky tier)."""
    x, y = ds.make("gaussian", 8000, seed=5)
    part = fit("kdtree", x, y, 12, seed=0)
    idx = build_index(x, y, part)
    rng = np.random.default_rng(3)
    ix = rng.integers(0, len(x), 8)
    qx, qy = x[ix].astype(np.float32), y[ix].astype(np.float32)
    r = np.full(8, 0.05, np.float32)
    spec = CircleQuery(materialize=True)
    mono = Executor(idx).run(spec, qx, qy, r, strict=True)
    chunked = Executor(idx, config=EngineConfig(scan_chunk_elems=512)
                       ).run(spec, qx, qy, r, strict=True)
    for a, b in zip(mono, chunked):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_chunked_knn_equals_monolithic():
    x, y = ds.make("gaussian", 8000, seed=5)
    part = fit("kdtree", x, y, 12, seed=0)
    idx = build_index(x, y, part)
    rng = np.random.default_rng(4)
    ix = rng.integers(0, len(x), 8)
    qx, qy = x[ix].astype(np.float32), y[ix].astype(np.float32)
    spec = Knn(k=7)
    mono = Executor(idx).run(spec, qx, qy, strict=True)
    chunked = Executor(idx, config=EngineConfig(scan_chunk_elems=512)
                       ).run(spec, qx, qy, strict=True)
    for a, b in zip(mono, chunked):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_row_chunked_dispatch_equals_unchunked(warmed):
    """A wide bucket past the row_chunk_elems budget splits into
    equal-width chunk dispatches of one reused executable — per-row
    purity makes the concat bitwise the single-dispatch result."""
    ex, specs = warmed
    name, spec, args = next(s for s in specs if s[0] == "knn5")
    wide_args = tuple(np.concatenate([a] * 8, axis=0) for a in args)
    base = ex.run(spec, *wide_args)
    ex2 = Executor(ex.index,
                   config=dataclasses.replace(ex.cfg,
                                              row_chunk_elems=1))
    for _, s2, a2 in specs:              # settle the same sticky tiers
        ex2.run(s2, *a2, strict=True)
    ex2.maintain()
    d0 = ex2.dispatches
    chunked = ex2.run(spec, *wide_args)
    assert ex2.dispatches - d0 > 2       # probe + >1 chunk dispatch
    for a, b in zip(base, chunked):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("name", ["range", "circle", "join"])
def test_memory_bounded_row_chunks_equal_unchunked(built, name):
    """Past the candidate-plane memory budget (scan_chunk_elems) a batch
    dispatches in row slices, on the strict escalation path and on the
    served (bucketed, fused) path alike — and the concatenation is
    bitwise the single-dispatch result."""
    x, y, part, idx = built
    _, spec, args = next(s for s in _mixed_queries(x, y, part)
                         if s[0] == name)
    args = tuple(a[8:24] for a in args)     # the small queries only
    big = Executor(idx)
    small = Executor(idx, config=EngineConfig(scan_chunk_elems=1 << 12))
    for strict in (True, False):
        want = big.run(spec, *args, strict=strict)
        d0 = small.dispatches
        got = small.run(spec, *args, strict=strict)
        assert small.dispatches - d0 > 2      # row slices engaged
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
