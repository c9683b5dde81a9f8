"""Compaction-equivalence suite (DESIGN.md §10 invariants).

The windowed refinement pipeline replaced its argsort hot paths with
lax.top_k candidate selection and cumsum stream compaction, and fused
the circle distance refine into the window gather. The invariants those
rewrites must preserve, asserted here across cap/cand tiers and both
kernel backends:

  counts    bitwise-equal to the golden fixture (the exact results the
            pre-compaction pipeline produced);
  id sets   materialized vids equal the exact full-refine sets
            (order-insensitive) whenever the window reported ok, and a
            subset on overflow rows (which the fused serving path
            answers with the exact on-device fallback count);
  demotion  maintain() steps clean sticky tiers back down (and vetoes
            ping-pong).

Plus direct micro-equivalence: the new helpers are bitwise the argsort
implementations they replaced, including overflow rows.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "golden"))
from gen_golden import build_inputs  # noqa: E402

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "spatial_golden.json")
TIERS = [(8, 2), (64, 8), (256, 16)]


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def inputs():
    return build_inputs()


@pytest.fixture(scope="module",
                params=[(b, cap, cand) for b in ("xla", "pallas")
                        for cap, cand in TIERS],
                ids=lambda p: f"{p[0]}-cap{p[1]}-cand{p[2]}")
def tier_ex(request, inputs):
    from repro.core import EngineConfig, Executor
    backend, cap, cand = request.param
    _, _, index, _ = inputs
    cfg = EngineConfig(backend=backend, range_cap=cap, range_cand=cand,
                       circle_cap=cap, circle_cand=cand)
    return Executor(index, config=cfg)


def _exact_rect_sets(x, y, rects):
    return [set(np.flatnonzero((x >= r[0]) & (x <= r[2]) &
                               (y >= r[1]) & (y <= r[3])))
            for r in np.asarray(rects)]


def _exact_circle_sets(x, y, cx, cy, cr):
    return [set(np.flatnonzero((x - a) ** 2 + (y - b) ** 2 <= r * r))
            for a, b, r in zip(np.asarray(cx), np.asarray(cy),
                               np.asarray(cr))]


def test_range_counts_bitwise_and_id_sets_exact(tier_ex, inputs, golden):
    """Whatever tier the ladder starts from, escalation must end on a
    complete window: counts bitwise the golden fixture, vid sets the
    exact full-refine sets."""
    from repro.core import RangeQuery
    x, y, _, q = inputs
    cnt, vids, ok = tier_ex.run(RangeQuery(), q["rects"], strict=True)
    assert np.asarray(cnt).tolist() == golden["range_query_cnt"]
    assert bool(np.asarray(ok).all())
    want = _exact_rect_sets(x, y, q["rects"])
    for row, w in zip(np.asarray(vids), want):
        assert set(row[row >= 0]) == w


def test_circle_counts_bitwise_and_id_sets_exact(tier_ex, inputs,
                                                 golden):
    from repro.core import CircleQuery
    x, y, _, q = inputs
    got = tier_ex.run(CircleQuery(), q["cx"], q["cy"], q["cr"],
                      strict=True)
    assert np.asarray(got).tolist() == golden["circle_count"]
    cnt, vids, ok = tier_ex.run(CircleQuery(materialize=True), q["cx"],
                                q["cy"], q["cr"], strict=True)
    assert np.asarray(cnt).tolist() == golden["circle_count"]
    want = _exact_circle_sets(x, y, q["cx"], q["cy"], q["cr"])
    for row, w, okq in zip(np.asarray(vids), want, np.asarray(ok)):
        got_set = set(row[row >= 0])
        if okq:
            assert got_set == w
        else:
            assert got_set <= w


def test_overflow_rows_fall_back_to_exact_counts(inputs, golden):
    """The fused serving path at a deliberately tiny sticky tier: the
    overflow rows' counts come from the on-device exact fallback
    (bitwise golden), the truncated windows stay subsets."""
    from repro.core import CircleQuery, EngineConfig, Executor
    x, y, index, q = inputs
    ex = Executor(index, config=EngineConfig(circle_cap=2,
                                             circle_cand=1))
    spec = CircleQuery(materialize=True)
    ex._sticky[spec.sticky_key()] = (2, 1)       # deliberately tiny tier
    cnt, vids, ok = ex.run(spec, q["cx"], q["cy"], q["cr"])  # fused
    assert not bool(np.asarray(ok).all())
    assert np.asarray(cnt).tolist() == golden["circle_count"]
    want = _exact_circle_sets(x, y, q["cx"], q["cy"], q["cr"])
    for row, w in zip(np.asarray(vids), want):
        assert set(row[row >= 0]) <= w


def test_maintain_demotes_clean_sticky_tiers(inputs):
    """Online re-tune, downward: after a hard burst escalates the tier,
    demote_after consecutive clean maintain() checks step it back."""
    from repro.core import EngineConfig, Executor, RangeQuery
    from repro.data import spatial as ds
    x, y, index, q = inputs
    cfg = EngineConfig(range_cap=2, range_cand=2, demote_after=2)
    ex = Executor(index, config=cfg)
    base = RangeQuery().sticky_key()
    easy = ds.random_rects(8, 1e-8, (0, 0, 1, 1), seed=5,
                           centers=(x, y))
    ex.run(RangeQuery(), easy, strict=True)
    assert ex._sticky[base] == (2, 2)
    ex.run(RangeQuery(), q["rects"])             # overflows the tier
    while ex.maintain():                          # escalate until clean
        ex.run(RangeQuery(), q["rects"])
    peak = ex._sticky[base]
    assert peak != (2, 2)
    moved = {}
    for _ in range(10):                           # easy traffic again
        ex.run(RangeQuery(), easy)
        moved = ex.maintain()
        if moved:
            break
    assert moved == {base: ex._sticky[base]}
    assert ex._sticky[base] < peak
    # counts stay exact across the demotion (fused fallback covers it)
    cnt, _, _ = ex.run(RangeQuery(), q["rects"])
    want = [len(s) for s in _exact_rect_sets(x, y, q["rects"])]
    assert np.asarray(cnt).tolist() == want


def test_demotion_ping_pong_backs_off(inputs):
    """A demotion the very next overflow undoes must DOUBLE the clean
    streak required before the next demotion attempt (exponential
    backoff) — steady serving cannot churn compiles, but downward
    re-tuning is never disabled for good."""
    from repro.core import EngineConfig, Executor, RangeQuery
    from repro.data import spatial as ds
    x, y, index, q = inputs
    cfg = EngineConfig(range_cap=2, range_cand=2, demote_after=2)
    ex = Executor(index, config=cfg)
    base = RangeQuery().sticky_key()
    easy = ds.random_rects(8, 1e-8, (0, 0, 1, 1), seed=5,
                           centers=(x, y))
    ex.run(RangeQuery(), easy, strict=True)
    ex.run(RangeQuery(), q["rects"])
    while ex.maintain():                          # escalate until clean
        ex.run(RangeQuery(), q["rects"])
    peak = ex._sticky[base]
    demoted = {}
    for _ in range(5):                            # easy traffic demotes
        ex.run(RangeQuery(), easy)
        demoted = ex.maintain()
        if demoted:
            break
    assert demoted and ex._sticky[base] < peak
    # demotion retraces the escalation ladder: re-escalating from the
    # demoted tier lands exactly on the warm peak executable
    assert ex._escalators[base](*ex._sticky[base]) == peak
    ex.run(RangeQuery(), q["rects"])              # bounces straight back
    assert ex.maintain() == {base: peak}
    assert ex._demote_backoff[base] == 2
    for _ in range(2 * cfg.demote_after - 1):     # doubled streak req
        ex.run(RangeQuery(), easy)
        assert ex.maintain() == {}                # rate-limited
        assert ex._sticky[base] == peak
    ex.run(RangeQuery(), easy)
    assert ex.maintain()                          # backoff elapsed:
    assert ex._sticky[base] < peak                # demotion recovers


def test_demotions_keep_the_ladder_compiled(inputs):
    """Demoting a tier never sweeps the fused programs of the tiers it
    left (up to the one above the highest tier held): when the next
    hard burst escalates back, every tier it climbs is still compiled,
    so serving compiles nothing."""
    from repro.core import EngineConfig, Executor, RangeQuery
    from repro.data import spatial as ds
    x, y, index, q = inputs
    cfg = EngineConfig(range_cap=2, range_cand=2, demote_after=2)
    ex = Executor(index, config=cfg)
    base = RangeQuery().sticky_key()
    easy = ds.random_rects(8, 1e-8, (0, 0, 1, 1), seed=5,
                           centers=(x, y))
    hard = ds.random_rects(8, 1e-2, (0, 0, 1, 1), seed=6,
                           centers=(x, y))
    ex.run(RangeQuery(), easy, strict=True)
    ex.run(RangeQuery(), hard)
    while ex.maintain():                          # escalate until clean
        ex.run(RangeQuery(), hard)
    peak = ex._sticky[base]
    ladder = ex._ladder_tiers(ex._op_range(base), peak)
    assert len(ladder) >= 3, ladder               # two demotions fit
    for _ in range(20):                           # easy traffic: down
        ex.run(RangeQuery(), easy)                # to the initial tier
        ex.maintain()
    assert ex._sticky[base] == ladder[0]
    kept = {v for t, v in ex.cache_variants(base) if t == "fused"}
    assert set(ladder) <= kept, (ladder, kept)
    ms = ex.compile_ms_total
    ex.run(RangeQuery(), hard)
    while ex.maintain():                          # the burst returns
        ex.run(RangeQuery(), hard)
    assert ex._sticky[base] == peak
    assert ex.compile_ms_total == ms              # nothing recompiled


# -- helper micro-equivalence (bitwise vs the argsort forms) -------------

def _ref_top_candidates(flags, c):
    import jax.numpy as jnp
    p = flags.shape[1]
    c = min(c, p)
    order = jnp.argsort(~flags, axis=1, stable=True)[:, :c]
    valid = jnp.take_along_axis(flags, order, axis=1)
    within = jnp.sum(flags.astype(jnp.int32), axis=1) <= c
    return np.asarray(order), np.asarray(valid), np.asarray(within)


def _ref_keep_window(vids, cnt, cap):
    import jax.numpy as jnp
    order = jnp.argsort(-(vids >= 0).astype(jnp.int32), axis=1,
                        stable=True)
    keep = min(vids.shape[1], max(cap * 8, 256))
    kept = jnp.take_along_axis(vids, order[:, :keep], axis=1)
    cap_ok = jnp.sum((kept >= 0).astype(jnp.int32), axis=1) == cnt
    return np.asarray(kept), np.asarray(cap_ok)


@pytest.mark.parametrize("c", [1, 3, 8, 64])
def test_top_candidates_matches_argsort(c):
    import jax.numpy as jnp
    from repro.core.local_ops import _top_candidates
    rng = np.random.default_rng(c)
    flags = jnp.asarray(rng.random((17, 23)) < 0.3)
    got = [np.asarray(a) for a in _top_candidates(flags, c)]
    want = _ref_top_candidates(flags, c)
    for g, w in zip(got, want):
        assert (g == w).all()


@pytest.mark.parametrize("cap,density", [(4, 0.02), (4, 0.9), (32, 0.5),
                                         (32, 0.0)])
def test_keep_window_matches_argsort(cap, density):
    """Includes overflow rows (density high enough that valid > keep)
    and the all-empty row."""
    import jax.numpy as jnp
    from repro.core.local_ops import _keep_window
    rng = np.random.default_rng(int(cap * 100 + density * 10))
    w = 1500
    vids = np.where(rng.random((9, w)) < density,
                    rng.integers(0, 10 ** 6, (9, w)), -1).astype(np.int32)
    cnt = jnp.asarray((vids >= 0).sum(axis=1), jnp.int32)
    vids = jnp.asarray(vids)
    gk, gok = _keep_window(vids, cnt, cap)
    wk, wok = _ref_keep_window(vids, cnt, cap)
    assert (np.asarray(gk) == wk).all()
    assert (np.asarray(gok) == wok).all()
