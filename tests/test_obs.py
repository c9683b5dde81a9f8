"""Spans, counters and program names of the serve path (DESIGN.md §15).

  spans      a drained scheduler under ``jax.profiler.trace`` records
             each read dispatch as one ``lilis.sched.dispatch`` span on
             one thread, with the batch's metadata, and the form /
             prep / launch / device-wait / resolve spans inside it;
  counters   queue waits are counted once per read request; a
             dispatch's time holds its device wait; compile time splits
             into inline + background and the two add up to the total;
  names      every compiled program is ``jit_lilis_<...>``, the same in
             two fresh executors, with or without the disk cache.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.core import (CircleQuery, EngineConfig, Knn, PointQuery,
                        RangeCount, build_index, fit)
from repro.core.executor import Executor, program_name
from repro.data import spatial as ds
from repro.serve import SpatialServeSession

N = 2500


@pytest.fixture(scope="module")
def built():
    x, y = ds.make("gaussian", N, seed=5)
    part = fit("kdtree", x, y, 6, seed=0)
    return x, y, part, build_index(x, y, part)


def _reads(x, y, part, n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        j = int(rng.integers(0, len(x)))
        kind = i % 4
        if kind == 0:
            out.append((PointQuery(), x[j:j + 1], y[j:j + 1]))
        elif kind == 1:
            out.append((RangeCount(), ds.random_rects(
                1, 1e-3, part.bounds, seed=seed + i, centers=(x, y))))
        elif kind == 2:
            out.append((CircleQuery(), x[j:j + 1], y[j:j + 1],
                        np.full(1, 0.02, np.float32)))
        else:
            out.append((Knn(k=5), x[j:j + 1], y[j:j + 1]))
    return out


def _host_spans(trace_dir):
    """{line index: [(start, end, name, stats)]} of the lilis.* host
    events of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("lilis."):
                    s = float(ev.start_ns)
                    lines.setdefault(i, []).append(
                        (s, s + float(ev.duration_ns), ev.name,
                         dict(ev.stats)))
    return lines


def test_drained_dispatch_spans(built, tmp_path):
    x, y, part, index = built
    sess = SpatialServeSession(index, config=EngineConfig(backend="xla"))
    reqs = _reads(x, y, part, 8, seed=1)
    for spec, *args in reqs:                 # compile outside the trace
        jax.block_until_ready(sess.submit(spec, *args))
    sched = sess.scheduler(start=False)
    with jax.profiler.trace(str(tmp_path)):
        tickets = [sched.submit(spec, *args) for spec, *args in reqs]
        sched.drain()
        for t in tickets:
            jax.block_until_ready(t.result(60.0))
    st = sched.stats()
    lines = _host_spans(tmp_path)
    holders = [i for i, evs in lines.items()
               if any(n == "lilis.sched.dispatch" for _, _, n, _ in evs)]
    assert len(holders) == 1                 # one thread dispatches
    evs = lines[holders[0]]
    disp = sorted(e for e in evs if e[2] == "lilis.sched.dispatch")
    assert len(disp) == st["read_batches"] >= 4
    seqs = [t.seq for t in tickets]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    need = {"lilis.sched.form", "lilis.exec.lock", "lilis.exec.prep",
            "lilis.exec.launch", "lilis.sched.device_wait",
            "lilis.sched.resolve"}
    queries = 0
    for k, (s, e, _, meta) in enumerate(disp):
        assert meta["batch"] == k + 1
        assert meta["width"] >= meta["queries"] >= meta["requests"] >= 1
        assert meta["ticket"] in seqs
        assert meta["spec"] in ("point", "range_count", "circle", "knn")
        queries += meta["queries"]
        inside = {n for s2, e2, n, _ in evs if s <= s2 and e2 <= e}
        assert need <= inside, need - inside
        launches = [m for s2, e2, n, m in evs
                    if n == "lilis.exec.launch" and s <= s2 and e2 <= e]
        assert all(m["program"].startswith("lilis_") for m in launches)
    assert queries == st["reads"]


def test_counters_count_what_they_name(built):
    x, y, part, index = built
    sess = SpatialServeSession(index, config=EngineConfig(backend="xla"))
    sched = sess.scheduler(start=False)
    reqs = _reads(x, y, part, 10, seed=2)
    tickets = [sched.submit(spec, *args) for spec, *args in reqs]
    sched.drain()
    for t in tickets:
        t.result(60.0)
    st = sched.stats()
    assert st["queue_waits"] == len(reqs) == st["reads"]
    assert st["queue_wait_ns"] > 0
    assert st["dispatch_ns"] >= st["device_wait_ns"] > 0
    assert "precompile_pending" not in st and "caps" not in st
    ticket = sched.request_maintain()        # a barrier: not a read
    sched.drain()
    ticket.result(60.0)
    st2 = sched.stats()
    assert st2["queue_waits"] == st["queue_waits"]
    assert st2["maintain_ns"] > st["maintain_ns"]


def test_compile_time_splits_by_thread(built):
    x, y, part, index = built
    ex = Executor(index, config=EngineConfig(backend="xla"))
    rects = ds.random_rects(4, 1e-3, part.bounds, seed=3, centers=(x, y))
    jax.block_until_ready(ex.run(RangeCount(), rects[:1]))
    inline = ex.compile_ms_inline
    assert inline > 0 and ex.compile_ms_background == 0
    assert ex.start_precompiler()
    try:
        assert ex.precompile_async(RangeCount(), rects) is not None
        assert ex.precompile_quiesce(120.0)
    finally:
        ex.stop_precompiler()
    assert ex.async_compiles >= 1
    assert ex.compile_ms_background > 0
    assert ex.compile_ms_inline == inline    # nothing compiled inline
    assert ex.compile_ms_total == pytest.approx(
        ex.compile_ms_inline + ex.compile_ms_background)
    st = ex.stats()
    assert st["compile_ms_total"] == pytest.approx(
        st["compile_ms_inline"] + st["compile_ms_background"], abs=0.11)
    jax.block_until_ready(ex.run(RangeCount(), rects))   # precompiled
    assert ex.compile_ms_inline == inline


@pytest.mark.parametrize("disk", [False, True], ids=["jit", "disk"])
def test_program_names_are_stable(built, tmp_path, disk):
    x, y, part, index = built
    cfg = EngineConfig(backend="xla", compile_cache_dir=(
        str(tmp_path / "cache") if disk else None))
    reqs = _reads(x, y, part, 4, seed=4)
    names = []
    for _ in range(2):                       # the second hits the disk
        ex = Executor(index, config=cfg)
        for spec, *args in reqs:
            jax.block_until_ready(ex.run(spec, *args))
            jax.block_until_ready(ex.run(spec, *args))   # fused tier
        got = {}
        for key, sig, compiled in ex.compiled_programs():
            head = compiled.as_text().split(",", 1)[0]
            assert head == f"HloModule jit_{program_name(key, sig)}"
            got[key[:5], sig] = head
        names.append(got)
    assert names[0] == names[1]
    assert all(re.fullmatch(r"HloModule jit_lilis_[a-z0-9_]+", h)
               for h in names[0].values())
    assert any("_fused_" in h for h in names[0].values())


def test_program_name_reads_the_key():
    key = ("pallas", False, ("circle", False), "fused", (64, 8), 3)
    sig = (((2, 4), "float32"), ((2,), "float32"))
    assert program_name(key, sig) == "lilis_circle_false_fused_64x8_w2"
    assert program_name(key[:5] + (9,), sig) == program_name(key, sig)
    assert program_name(("xla", True, ("range_count",), "x", None, 0),
                        (((16, 4), "float32"),)) == \
        "lilis_range_count_x_q_w16"
    assert program_name(("xla", False, ("insert",), "u", (8, 64), 0),
                        (((257, 64), "float32"),)) == "lilis_insert_u_8x64"
