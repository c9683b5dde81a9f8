"""Streaming serve scheduler determinism (DESIGN.md §12).

The drain-on-demand test mode (``start=False``: no worker thread, the
caller pumps the SAME batch-forming code synchronously) pins:

  (a) concurrent submissions actually coalesce into micro-batches of
      width > 1 (and respect the per-spec caps derived from the
      measured wide-batch columns);
  (b) every result routes back to exactly the request that asked for
      it (distinct queries -> distinct answers);
  (c) coalesced results are BITWISE-identical to serial ``submit()``
      through the same session, for every query spec, on both kernel
      backends — batching (and the power-of-two row-0 padding that
      bounds the executable count) must never change a single bit.

Plus a real worker-thread smoke test: concurrent submitters, all
tickets resolve, results still match serial.
"""
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (CircleQuery, EngineConfig, Knn, PointQuery,
                        RangeCount, RangeQuery, SpatialJoin, build_index,
                        fit)
from repro.data import spatial as ds
from repro.serve import SpatialServeSession, micro_batch_caps
from repro.serve.scheduler import bench_spec_name

N = 2500


@pytest.fixture(scope="module")
def built():
    x, y = ds.make("gaussian", N, seed=3)
    part = fit("kdtree", x, y, 6, seed=0)
    return x, y, part, build_index(x, y, part)


def _warm_requests(x, y, part, qn=6, seed=0):
    rng = np.random.default_rng(seed)
    ix = rng.integers(0, len(x), qn)
    rects = ds.random_rects(qn, 1e-3, part.bounds, seed=seed + 1,
                            centers=(x, y))
    polys, ne = ds.random_polygons(4, part.bounds, seed=seed + 2)
    r = np.full(qn, 0.03, np.float32)
    return [(PointQuery(), x[ix], y[ix]),
            (RangeCount(), rects),
            (RangeQuery(), rects),
            (CircleQuery(), x[ix], y[ix], r),
            (CircleQuery(materialize=True), x[ix], y[ix], r),
            (Knn(k=5), x[ix], y[ix]),
            (SpatialJoin(), polys, ne)]


@pytest.fixture(scope="module", params=["xla", "pallas"])
def sess(request, built):
    x, y, part, index = built
    s = SpatialServeSession(
        index, config=EngineConfig(backend=request.param))
    s.warmup(_warm_requests(x, y, part))   # settle sticky + fused
    return x, y, part, s


def _assert_tree_equal(a, b, what=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for u, v in zip(la, lb):
        u, v = np.asarray(u), np.asarray(v)
        assert u.shape == v.shape and u.dtype == v.dtype, what
        assert np.array_equal(u, v), what


def _mixed_singles(x, y, part, n, seed):
    """n single-query requests over 4 spec kinds, all distinct."""
    rng = np.random.default_rng(seed)
    rects = ds.random_rects(n, 1e-3, part.bounds, seed=seed + 1,
                            centers=(x, y))
    reqs = []
    for i in range(n):
        j = int(rng.integers(0, len(x)))
        kind = i % 4
        if kind == 0:
            reqs.append((PointQuery(), x[j:j + 1], y[j:j + 1]))
        elif kind == 1:
            reqs.append((RangeCount(), rects[i:i + 1]))
        elif kind == 2:
            reqs.append((Knn(k=5), x[j:j + 1], y[j:j + 1]))
        else:
            reqs.append((CircleQuery(), x[j:j + 1], y[j:j + 1],
                         np.full(1, 0.03, np.float32)))
    return reqs


def test_coalesce_routes_and_matches_serial(sess):
    x, y, part, s = sess
    reqs = _mixed_singles(x, y, part, 24, seed=11)
    serial = [s.submit(spec, *args) for spec, *args in reqs]
    jax.block_until_ready(serial)

    sched = s.scheduler(start=False)
    tickets = [sched.submit(spec, *args) for spec, *args in reqs]
    assert not any(t.done() for t in tickets)   # nothing ran yet
    sched.drain()
    st = sched.stats()
    # (a) concurrent submissions coalesced: 24 single-query requests
    # formed one batch per spec kind, each wider than 1
    assert st["read_batches"] == 4
    assert st["max_batch"] > 1 and st["mean_batch"] > 1
    # (b)+(c) every ticket carries ITS request's serial answer, bitwise
    for i, (t, ref) in enumerate(zip(tickets, serial)):
        assert t.done() and t.batched > 1
        _assert_tree_equal(t.result(), ref, f"request {i}")
    sched.close()


def test_bitwise_matches_serial_every_spec(sess):
    """Every spec x request widths 1..3, coalesced vs serial bitwise
    (includes the materializing range/circle windows and the join)."""
    x, y, part, s = sess
    rng = np.random.default_rng(23)
    rects = ds.random_rects(9, 1e-3, part.bounds, seed=24,
                            centers=(x, y))
    polys, ne = ds.random_polygons(6, part.bounds, seed=25)
    reqs = []
    for lo, hi in ((0, 1), (1, 3), (3, 6)):     # widths 1, 2, 3
        ix = rng.integers(0, len(x), hi - lo)
        qx, qy = x[ix], y[ix]
        r = np.full(hi - lo, 0.03, np.float32)
        reqs += [(PointQuery(), qx, qy),
                 (RangeCount(), rects[lo:hi]),
                 (RangeQuery(), rects[lo:hi]),
                 (CircleQuery(), qx, qy, r),
                 (CircleQuery(materialize=True), qx, qy, r),
                 (Knn(k=5), qx, qy),
                 (SpatialJoin(), polys[lo:hi], ne[lo:hi])]
    serial = [s.submit(spec, *args) for spec, *args in reqs]
    jax.block_until_ready(serial)

    sched = s.scheduler(start=False)
    tickets = [sched.submit(spec, *args) for spec, *args in reqs]
    sched.drain()
    st = sched.stats()
    assert st["read_batches"] == 7              # one batch per spec
    assert st["max_batch"] == 6                 # 1+2+3 coalesced
    for i, (t, ref) in enumerate(zip(tickets, serial)):
        assert t.batched == 6
        _assert_tree_equal(t.result(), ref,
                           f"request {i} ({reqs[i][0]!r})")
    sched.close()


def test_micro_batch_caps_from_bench_columns():
    cfg = EngineConfig()
    bench = {"bench_q": 16, "bench_q_wide": 256,
             "backends": {"xla": {"specs": {
                 "point": {"steady_us_per_q": 100.0,
                           "steady_us_per_q_b256": 10.0},
                 "knn10": {"steady_us_per_q": 100.0,
                           "steady_us_per_q_b256": 900.0},
                 "join": {"steady_us_per_q": 100.0}}}}}
    caps = micro_batch_caps(bench, "xla", cfg)
    # both columns measured -> coalesce to the measured wide batch
    # (the executor's tier-bucketed dispatch makes wide batches no
    # slower for EVERY spec — no inverted-spec narrow clamp anymore);
    # no wide measurement -> no cap entry (defaults to serve_max_batch)
    assert caps == {"point": 256, "knn10": 256}
    assert micro_batch_caps("/nonexistent/path.json", "xla", cfg) == {}
    assert bench_spec_name(Knn(k=10)) == "knn10"
    assert bench_spec_name(CircleQuery(materialize=True)) == "circle_mat"


def test_scheduler_honors_per_spec_cap(sess):
    x, y, part, s = sess
    # the cap is the measured wide batch (bench_q_wide) — a bench
    # measured at a narrow wide-batch width caps coalescing there
    bench = {"bench_q": 4, "bench_q_wide": 4,
             "specs": {"knn5": {"steady_us_per_q": 1.0,
                                "steady_us_per_q_b256": 0.9}}}
    sched = s.scheduler(bench=bench, start=False)
    assert sched.caps["knn5"] == 4
    rng = np.random.default_rng(31)
    ix = rng.integers(0, len(x), 10)
    tickets = [sched.submit(Knn(k=5), x[j:j + 1], y[j:j + 1])
               for j in ix]
    sched.drain()
    # 10 single-query kNN requests under a cap of 4 -> batches of at
    # most 4 (3 dispatches), never one 10-wide batch
    widths = [e[2] for e in sched.events if e[0] == "batch"]
    assert len(widths) == 3 and max(widths) == 4
    for t in tickets:
        assert t.done()
    sched.close()


def test_worker_thread_concurrent_submitters(sess):
    x, y, part, s = sess
    reqs = _mixed_singles(x, y, part, 32, seed=41)
    serial = [s.submit(spec, *args) for spec, *args in reqs]
    jax.block_until_ready(serial)

    with s.scheduler(start=True) as sched:
        tickets = [None] * len(reqs)

        def client(k):
            for i in range(k, len(reqs), 4):
                spec, *args = reqs[i]
                tickets[i] = sched.submit(spec, *args)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, t in enumerate(tickets):
            _assert_tree_equal(t.result(timeout=60.0), serial[i],
                               f"request {i}")
        st = sched.stats()
        assert st["reads"] == len(reqs)
        assert st["maintain_busy"] == 0
    # closed: the scheduler rejects new work
    with pytest.raises(RuntimeError):
        sched.submit(PointQuery(), x[:1], y[:1])


def test_submit_validates_like_executor(sess):
    x, y, part, s = sess
    sched = s.scheduler(start=False)
    with pytest.raises(TypeError):
        sched.submit("point", x[:1], y[:1])
    with pytest.raises(TypeError):
        sched.submit(PointQuery(), x[:1])      # wrong arity
    sched.close()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_failures_are_raised_not_swallowed(sess, monkeypatch):
    """A failed dispatch raises from its ticket; a failure outside any
    dispatch ends the worker loudly — the scheduler then refuses work
    instead of leaving callers blocked on tickets that never resolve."""
    x, y, part, s = sess
    sched = s.scheduler(start=True)
    bad = sched.submit(RangeCount(), np.zeros((2, 3), np.float32))
    with pytest.raises(Exception):
        bad.result(timeout=60.0)

    def boom():
        raise ValueError("maintenance probe failed")

    monkeypatch.setattr(s.executor, "maintenance_due", boom)
    ok = sched.submit(PointQuery(), x[:1], y[:1])
    assert bool(np.asarray(ok.result(timeout=60.0))[0])
    deadline = time.monotonic() + 60.0
    while sched._thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not sched._thread.is_alive()
    with pytest.raises(RuntimeError) as err:
        sched.submit(PointQuery(), x[:1], y[:1])
    assert isinstance(err.value.__cause__, ValueError)
    with pytest.raises(RuntimeError):
        sched.drain(timeout=5.0)
    monkeypatch.undo()
    sched.close()


def test_worker_death_fails_submit_blocked_on_backpressure(sess):
    """A submit waiting on serve_queue_depth when the worker dies must
    raise, not enqueue a request no thread will ever serve."""
    x, y, part, s = sess
    sched = s.scheduler(start=True)
    sched.cfg = dataclasses.replace(sched.cfg, serve_queue_depth=1)
    gate = threading.Event()

    def stuck(straggler_wait):
        gate.wait(60.0)          # leaves the queued request in place
        raise ValueError("batch former failed")

    sched._form_and_run = stuck
    first = sched.submit(PointQuery(), x[:1], y[:1])   # fills the queue
    got = {}

    def blocked_submit():
        try:
            got["ticket"] = sched.submit(PointQuery(), x[:1], y[:1])
        except BaseException as e:
            got["error"] = e

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.3)              # let it reach the backpressure wait
    assert t.is_alive() and sched.submitted == 1
    gate.set()
    t.join(60.0)
    assert not t.is_alive()
    assert "ticket" not in got
    assert isinstance(got["error"], RuntimeError)
    assert isinstance(got["error"].__cause__, ValueError)
    with pytest.raises(ValueError):
        first.result(timeout=5.0)
    sched.close()
