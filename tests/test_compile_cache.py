"""Persistent compile cache + async precompilation (DESIGN.md §14).

What must hold:

  parity       a SECOND PROCESS pointed at a warm cache dir serves
               every query spec from deserialized ``jax.export``
               artifacts with results BITWISE-identical to the fresh-
               compiled first process — on both kernel backends — and
               actually hits the disk (``disk_cache_hits > 0``).
  fallback     a corrupt entry, or a schema/context mismatch, silently
               degrades to a fresh in-process compile: identical
               results, the bad entry invalidated and re-billed as a
               miss, and the re-store heals the dir for the next
               process.
  hygiene      eviction is size-capped LRU by mtime — ``load`` bumps
               recency, sweeps drop oldest-first — and an executor
               configured with a tiny ``compile_cache_bytes`` keeps
               its entries/ tree under the cap.
  neutrality   manifest prewarm and the async precompile worker only
               move WHEN compilation happens, never what it computes:
               prewarmed serving compiles nothing new for recorded
               traffic, and worker-mode scheduling with background
               precompiles stays bitwise-equal to serial submits.
"""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (CircleQuery, EngineConfig, Executor, Knn,
                        PointQuery, RangeCount, RangeQuery, SpatialJoin,
                        build_index, fit)
from repro.core.compile_cache import (CompileCache, load_manifest,
                                      save_manifest)
from repro.data import spatial as ds
from repro.serve import SpatialServeSession

N = 2000


def _build():
    """Deterministic tiny index — rebuilt bit-identically by the
    restart subprocess from the same seeds."""
    x, y = ds.make("gaussian", N, seed=3)
    part = fit("kdtree", x, y, 4, seed=0)
    return x, y, part, build_index(x, y, part)


def _workload(x, y, part):
    """One named request per query spec (every dispatch family)."""
    rng = np.random.default_rng(11)
    ix = rng.integers(0, len(x), 5)
    rects = ds.random_rects(5, 1e-3, part.bounds, seed=12,
                            centers=(x, y))
    polys, ne = ds.random_polygons(4, part.bounds, seed=13)
    r = np.full(5, 0.03, np.float32)
    return [("point", PointQuery(), (x[ix], y[ix])),
            ("range_count", RangeCount(), (rects,)),
            ("range", RangeQuery(), (rects,)),
            ("circle", CircleQuery(), (x[ix], y[ix], r)),
            ("circle_mat", CircleQuery(materialize=True),
             (x[ix], y[ix], r)),
            ("knn", Knn(k=5), (x[ix], y[ix])),
            ("join", SpatialJoin(), (polys, ne))]


def _leaves(res):
    return [np.asarray(v) for v in jax.tree_util.tree_leaves(res)]


def _run_all(ex, x, y, part):
    out = {}
    for name, spec, args in _workload(x, y, part):
        res = ex.run(spec, *args)
        jax.block_until_ready(res)
        out[name] = _leaves(res)
    return out


@pytest.fixture(scope="module")
def built():
    return _build()


# -- cross-process bitwise parity -------------------------------------

_RESTART = """
import json, sys
import numpy as np
from test_compile_cache import _build, _run_all
from repro.core import EngineConfig, Executor

backend, cache_dir, out_npz = sys.argv[1:4]
x, y, part, index = _build()
ex = Executor(index, config=EngineConfig(
    backend=backend, compile_cache_dir=cache_dir))
res = _run_all(ex, x, y, part)
np.savez(out_npz, **{f"{n}.{i}": lf for n, ls in res.items()
                     for i, lf in enumerate(ls)})
st = ex.stats()
print(json.dumps({"hits": st["disk_cache_hits"],
                  "misses": st["disk_cache_misses"]}))
"""


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_restart_bitwise_parity(tmp_path, built, backend):
    """A restarted process on a warm cache dir reproduces every spec
    bit-for-bit from deserialized artifacts (the tentpole claim)."""
    x, y, part, index = built
    cache = tmp_path / "cache"
    ex = Executor(index, config=EngineConfig(
        backend=backend, compile_cache_dir=str(cache)))
    ref = _run_all(ex, x, y, part)      # fresh compiles, write-through

    script = tmp_path / "restart.py"
    script.write_text(_RESTART)
    out_npz = tmp_path / f"res_{backend}.npz"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.path.dirname(__file__)]))
    proc = subprocess.run(
        [sys.executable, str(script), backend, str(cache),
         str(out_npz)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(proc.stdout.strip().splitlines()[-1])
    assert st["hits"] > 0, f"restart never hit the disk cache: {st}"

    got = np.load(out_npz)
    for name, leaves in ref.items():
        for i, lf in enumerate(leaves):
            other = got[f"{name}.{i}"]
            assert np.array_equal(lf, other), \
                f"{backend}/{name} leaf {i} drifted across restart"


# -- corruption / mismatch fallback -----------------------------------

def test_corrupt_entry_falls_back_and_heals(tmp_path, built):
    x, y, part, index = built
    cache = tmp_path / "cache"
    ex1 = Executor(index, config=EngineConfig(
        backend="xla", compile_cache_dir=str(cache)))
    ref = _run_all(ex1, x, y, part)
    entries = sorted((cache / "entries").glob("*.bin"))
    assert entries, "write-through store produced no entries"
    for p in entries:
        p.write_bytes(b"not a serialized program")

    ex2 = Executor(index, config=EngineConfig(
        backend="xla", compile_cache_dir=str(cache)))
    got = _run_all(ex2, x, y, part)
    st = ex2.stats()
    # every corrupt load was re-billed hit -> miss, results unharmed
    assert st["disk_cache_hits"] == 0
    assert st["disk_cache_misses"] >= len(entries)
    for name in ref:
        for a, b in zip(ref[name], got[name]):
            assert np.array_equal(a, b)
    # the fresh compiles re-stored good entries: a third executor hits
    ex3 = Executor(index, config=EngineConfig(
        backend="xla", compile_cache_dir=str(cache)))
    _run_all(ex3, x, y, part)
    assert ex3.stats()["disk_cache_hits"] > 0


def test_schema_mismatch_misses_cleanly(tmp_path, built, monkeypatch):
    """A cache written under a different schema/context version is
    unreachable — fresh compile, correct results, no crash."""
    x, y, part, index = built
    cache = tmp_path / "cache"
    ex1 = Executor(index, config=EngineConfig(
        backend="xla", compile_cache_dir=str(cache)))
    ref = _run_all(ex1, x, y, part)
    assert ex1.stats()["disk_cache_misses"] > 0   # cold dir

    import repro.core.plan as plan
    monkeypatch.setattr(plan, "CACHE_SCHEMA", plan.CACHE_SCHEMA + 1)
    ex2 = Executor(index, config=EngineConfig(
        backend="xla", compile_cache_dir=str(cache)))
    got = _run_all(ex2, x, y, part)
    st = ex2.stats()
    assert st["disk_cache_hits"] == 0
    assert st["disk_cache_misses"] > 0
    for name in ref:
        for a, b in zip(ref[name], got[name]):
            assert np.array_equal(a, b)


# -- size-capped LRU eviction -----------------------------------------

def test_lru_eviction_by_mtime(tmp_path):
    cc = CompileCache(tmp_path / "cc", max_bytes=1 << 30)
    blob = b"x" * 300
    for i, t in enumerate((100.0, 200.0, 300.0)):
        fp = f"{i:064d}"
        assert cc.store(fp, blob)
        os.utime(cc._path(fp), (t, t))
    # oldest-first: cap 650 keeps the two newest
    assert cc.evict(max_bytes=650) == 1
    assert cc.load("0" * 64) is None               # evicted -> miss
    assert cc.load(f"{1:064d}") == blob            # bumps mtime (LRU)
    # entry 1 was just USED, so the next sweep drops entry 2 instead
    assert cc.evict(max_bytes=350) == 1
    assert cc.load(f"{1:064d}") == blob
    assert cc.load(f"{2:064d}") is None
    assert cc.size_bytes() <= 350 and len(cc) == 1


def test_executor_respects_size_cap(tmp_path, built):
    x, y, part, index = built
    cap = 4096
    ex = Executor(index, config=EngineConfig(
        backend="xla", compile_cache_dir=str(tmp_path / "cache"),
        compile_cache_bytes=cap))
    _run_all(ex, x, y, part)
    assert ex._disk.size_bytes() <= cap


# -- manifest prewarm + async precompile neutrality -------------------

def test_prewarm_compiles_nothing_new_for_recorded_traffic(tmp_path,
                                                           built):
    x, y, part, index = built
    cache = str(tmp_path / "cache")
    reqs = [(spec, *args) for _, spec, args in _workload(x, y, part)]

    a = SpatialServeSession(index, config=EngineConfig(
        backend="xla", compile_cache_dir=cache))
    for spec, *args in reqs:            # settle sticky tiers the same
        a.submit(spec, *args)           # way live traffic would
    a.maintain()
    for spec, *args in reqs:
        a.submit(spec, *args)
    man = a.manifest()
    assert man["programs"], "manifest recorded no programs"
    mpath = tmp_path / "prewarm.json"
    save_manifest(mpath, man)
    man = load_manifest(mpath)          # across the JSON round-trip

    b = SpatialServeSession(index, config=EngineConfig(
        backend="xla", compile_cache_dir=cache))
    res = b.prewarm(man)
    assert res["compiled"] > 0
    st0 = b.stats()
    assert st0["disk_cache_hits"] > 0   # prewarm loaded, not compiled
    for spec, *args in reqs:
        jax.block_until_ready(b.submit(spec, *args))
    st1 = b.stats()
    # recorded traffic must ride entirely on prewarmed executables
    assert st1["cache_size"] == st0["cache_size"]
    assert st1["compile_ms_total"] == st0["compile_ms_total"]


def test_async_precompile_is_bitwise_neutral(built):
    x, y, part, index = built
    sess = SpatialServeSession(index, config=EngineConfig(
        backend="xla", serve_async_precompile=True))
    rng = np.random.default_rng(21)
    ix = rng.integers(0, len(x), 12)
    reqs = [(PointQuery(), x[i:i + 1], y[i:i + 1]) for i in ix]
    ref = [sess.submit(spec, *args) for spec, *args in reqs]
    jax.block_until_ready(ref)

    with sess.scheduler(start=True) as live:
        tickets = [None] * len(reqs)

        def client(k):
            for i in range(k, len(reqs), 3):
                spec, *args = reqs[i]
                tickets[i] = live.submit(spec, *args)

        ths = [threading.Thread(target=client, args=(k,))
               for k in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        got = [t.result(120.0) for t in tickets]
        live.ex.precompile_quiesce(120.0)
        st = live.stats()
    assert st["maintain_busy"] == 0
    for r, g in zip(ref, got):
        for a, b in zip(_leaves(r), _leaves(g)):
            assert np.array_equal(a, b), \
                "async precompilation changed a result bit"
    # the worker never runs afterwards: close() stopped it
    assert not sess.executor.precompiling


# -- where the cache lives ---------------------------------------------

def test_default_cache_root_is_fixed(monkeypatch, tmp_path):
    """The root a program picks for itself is fixed: the ``lilis``
    subdirectory of JAX_COMPILATION_CACHE_DIR when that is set, else
    ``.jax_cache`` at the top of the checkout."""
    from repro.core.compile_cache import default_cache_root
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert default_cache_root() == str(tmp_path / "lilis")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = default_cache_root()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert root == os.path.join(checkout, ".jax_cache")
    assert root == default_cache_root()


_ENV_CACHE = """
import sys
import jax
from test_compile_cache import _build, _run_all
from repro.core import EngineConfig, Executor

x, y, part, index = _build()
ex = Executor(index, config=EngineConfig(compile_cache_dir=sys.argv[1]))
_run_all(ex, x, y, part)
print(jax.config.jax_compilation_cache_dir)
"""


def test_env_cache_dir_is_not_overridden(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, jax's cache stays there: no
    code points it elsewhere, and the export layer keeps its entries
    under the configured root."""
    env_dir, root = tmp_path / "jaxcache", tmp_path / "root"
    script = tmp_path / "env_cache.py"
    script.write_text(_ENV_CACHE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(env_dir),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.path.dirname(__file__)]))
    proc = subprocess.run([sys.executable, str(script), str(root)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == str(env_dir)
    assert any(env_dir.iterdir()), "jax wrote nothing to its env cache"
    assert not (root / "xla").exists()
    assert any((root / "entries").glob("*.bin"))


def test_code_digest_tracks_the_sources(tmp_path):
    """The fingerprint context carries a digest of the package sources,
    so an artifact exported by other code is never replayed."""
    from repro.core.compile_cache import code_digest, process_context
    pkg = tmp_path / "pkg"
    (pkg / "core").mkdir(parents=True)
    (pkg / "core" / "a.py").write_text("X = 1\n")
    before = code_digest(pkg)
    assert code_digest(pkg) == before
    (pkg / "core" / "a.py").write_text("X = 2\n")
    assert code_digest(pkg) != before
    assert process_context()["code"] == code_digest()
