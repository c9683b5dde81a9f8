"""CPU tests of the benchmark's parts: discovery by name, seeded
traffic, the copied reference, pinned shapes, the no-chip exit and the
trace reduction."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import deploy, gen, trace
from bench.conftest import ROOT, small_cell
from bench.oracle import Oracle

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cells_found_by_name(cell):
    from bench.run import Cell, reader
    c = Cell(cell)
    assert c.cfg["name"] == c.entry["config"]
    assert c.traffic["mix"]
    assert {m["name"] for m in c.e2e} >= {"setup_s", "queries_per_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(reader(m["name"]))
    g = gen.Generator(c.traffic, *_pts(c))
    assert set(g.families) == {f["family"] for f in c.traffic["mix"]}


def test_new_files_found_by_name(tmp_path):
    """A later PR adds a configuration with its data generator, a mix
    with a new arrival process and a new query family, and a metric, as
    files and entries only; the harness finds each by name."""
    from bench.run import Cell, reader, streams
    for d in ("configs", "traffic", "metrics", "data", "loops",
              "families"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    cfg = json.loads((ROOT / f"bench/configs/{CONFIGS[0]}.json")
                     .read_text())
    cfg.update(name="tiny-town", generator={"kind": "grid_town"})
    (tmp_path / "bench/configs/tiny-town.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/data/grid_town.py").write_text(
        "import numpy as np\n"
        "def points(n, seed):\n"
        "    v = np.linspace(0.1, 0.9, n, dtype=np.float32)\n"
        "    return v, v[::-1].copy()\n")
    (tmp_path / "bench/loops/bursts.py").write_text(
        "import numpy as np\n"
        "def requests(g, seconds, seed):\n"
        "    n = int(g.t['rate'] * seconds)\n"
        "    return g.batch(n, seed), np.zeros(n)\n")
    (tmp_path / "bench/families/corner.py").write_text(
        "import numpy as np\n"
        "def requests(g, f, n, rng):\n"
        "    z = np.zeros(1, np.float32)\n"
        "    return [g.Request('corner', g.core.PointQuery(), (z, z), 1)\n"
        "            for _ in range(n)]\n")
    (tmp_path / "bench/traffic/lookups.json").write_text(json.dumps(
        {"loop": "bursts", "rate": 5,
         "mix": [{"family": "corner", "share": 1},
                 {"family": "point", "share": 1}]}))
    shutil.copy(ROOT / "bench/families/point.py",
                tmp_path / "bench/families/point.py")
    (tmp_path / "bench/metrics/answers.new.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = dict(BENCH)
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "tiny-town.lookups", "config": "tiny-town",
         "traffic": "lookups", "chips": 1, "why": "test"}]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "answers.new", "unit": "queries", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "read_p50_ms", "workloads": ["tiny-town.lookups"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = Cell("tiny-town.lookups", root=tmp_path)
    assert c.cfg["name"] == "tiny-town"
    assert [m["name"] for m in c.per_layer] == ["answers.new"]
    assert reader("answers.new", tmp_path)({}) == 42.0
    x, y = deploy.points(c.cfg, 10, 1, str(tmp_path))
    assert x[0] == np.float32(0.1) and y[0] == np.float32(0.9)
    g = gen.Generator(c.traffic, x, y, str(tmp_path))
    reqs, due = streams(g, c.traffic, 3, 2.0, False)["main"]
    assert len(reqs) == 10 and not due.any()
    assert sorted(r.family for r in reqs) == ["corner"] * 5 + ["point"] * 5


@pytest.mark.parametrize("kind", ["loop", "family"])
def test_unknown_loop_or_family_fails(kind):
    """A mix that names a loop or a family with no file is refused,
    never run as another."""
    c = small_cell(CELLS[0])
    t = dict(c.traffic, loop="bursty") if kind == "loop" else dict(
        c.traffic, mix=[{"family": "zones", "share": 1}])
    with pytest.raises(ValueError, match="no (loops|families) named"):
        gen.Generator(t, *_pts(c))


def test_spider_gaussian_points():
    """The configuration's points: Spider's gaussian, inside the unit
    square, the same for a seed and different for another."""
    x, y = deploy.points({"generator": {"kind": "spider_gaussian"}},
                         200_000, 2**31 + 9)
    assert x.dtype == y.dtype == np.float32
    assert 0 <= min(x.min(), y.min()) and max(x.max(), y.max()) <= 1
    assert abs(x.mean() - 0.5) < 2e-3 and abs(y.std() - 0.1) < 2e-3
    x2, _ = deploy.points({"generator": {"kind": "spider_gaussian"}},
                          200_000, 2**31 + 9)
    x3, _ = deploy.points({"generator": {"kind": "spider_gaussian"}},
                          200_000, 2**31 + 10)
    assert np.array_equal(x, x2) and not np.array_equal(x, x3)


def _pts(cell):
    return deploy.points(cell.cfg, 4000, 3)


def _key(reqs):
    return [(r.family, r.spec, tuple(np.asarray(a).tobytes()
                                     for a in r.args)) for r in reqs]


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_deterministic_per_seed(cell):
    """The same seed gives the same requests; another seed gives the
    same work (family counts, sizes, arrival gaps) in another order."""
    c = small_cell(cell)
    x, y = _pts(c)

    def make(seed):
        return gen.Generator(c.traffic, x, y).stream(3.0, seed)

    (a, da), (b, db), (o, do) = make(11), make(11), make(2**31 + 5)
    assert _key(a) == _key(b)
    assert np.array_equal(da, db)
    gaps = lambda d: np.sort(np.diff(d, prepend=0.0))  # noqa: E731
    assert np.allclose(gaps(da), gaps(do), rtol=1e-9, atol=1e-12)
    assert da[-1] <= 3.0
    assert _key(a) != _key(o)
    count = lambda rs: sorted((r.family, r.queries) for r in rs)  # noqa
    assert count(a) == count(o)


def test_oracle_agrees_with_chip_smoke():
    """The copied reference answers as ``chip_smoke.Oracle`` does."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    rng = np.random.default_rng(0)
    x, y = deploy.points({"generator": {"kind": "spider_gaussian"}},
                         20000, 1)
    mine, theirs = Oracle(x, y), chip_smoke.Oracle(x, y)
    for _ in range(20):
        i = rng.integers(len(x))
        cx, cy = float(x[i]), float(y[i])
        r = float(rng.uniform(0.003, 0.02))
        rect = (cx - r, cy - r, cx + r, cy + r)
        assert np.array_equal(np.sort(mine.rect_ids(rect)),
                              np.sort(theirs.rect_ids(rect)))
        assert np.array_equal(np.sort(mine.circle_ids(cx, cy, r)),
                              np.sort(theirs.circle_ids(cx, cy, r)))
        assert np.array_equal(mine.knn_d2(cx, cy, 10),
                              theirs.knn_d2(cx, cy, 10))
    px = np.concatenate([x[:50], rng.random(50, dtype=np.float32)])
    py = np.concatenate([y[:50], rng.random(50, dtype=np.float32)])
    assert np.array_equal(mine.point(px, py), theirs.point(px, py))
    q = np.arange(150, 160)
    assert np.array_equal(mine.vid_d2(q, 0.5, 0.5),
                          theirs.vid_d2(q, 0.5, 0.5))


@pytest.mark.parametrize("config", CONFIGS)
def test_two_seeds_build_identical_shapes(config):
    """Under the pinned sizes every seed's index has the same static
    shapes, so every seed's programs are the same programs."""
    import jax
    name = [w["name"] for w in BENCH["workloads"]
            if w["config"] == config][0]
    cell = small_cell(name)
    got = []
    for seed in (5, 2**31 + 77):
        x, y = deploy.points(cell.cfg, cell.cfg["points"], seed)
        index, _ = deploy.build(cell.cfg, x, y, seed)
        leaves = [(a.shape, str(a.dtype))
                  for a in jax.tree_util.tree_leaves(index)]
        got.append((deploy.shapes(index), leaves,
                    jax.tree_util.tree_structure(index)))
    assert got[0] == got[1]
    assert got[0][0]["n_pad"] == cell.cfg["pinned"]["n_pad"]
    assert got[0][0]["probe"] == cell.cfg["pinned"]["probe"]
    assert got[0][0]["knots"] == cell.cfg["pinned"]["knots"]


def test_pin_exceeded_fails():
    cell = small_cell(CELLS[0])
    cell.cfg["pinned"]["n_pad"] = 256
    x, y = deploy.points(cell.cfg, cell.cfg["points"], 1)
    with pytest.raises(deploy.PinExceeded):
        deploy.build(cell.cfg, x, y, 1)


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_fails_without_tpu():
    p = _run_py(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ is refused."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert not p.stdout.strip()


FIXTURE = Path(__file__).parent / "fixtures" / "interactive.xplane.pb.gz"


def test_trace_reduction_on_a_chip_trace(tmp_path):
    """The committed trace was recorded on a TPU v5e by a traced run of
    the interactive mix over 10^7 points (slimmed by
    fixtures/slim_trace.py)."""
    import gzip
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    red = trace.reduce(trace.load(str(path)))
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["kernel_s"] <= red["busy_s"]
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6, abs=1e-9)
    assert all(t >= 0 for _, t in red["idle_gaps"])


def test_trace_reduction_arithmetic():
    """Union of overlapping device ops, clipping to the window, and gap
    labels from the harness's host spans, on a hand-made trace."""
    tr = {"device": {"/device:TPU:0": [
        (0.0, 30.0, "fusion.1", False), (20.0, 50.0, "k", True),
        (70.0, 90.0, "fusion.1", False), (95.0, 130.0, "k", True)]},
        "host": [(10.0, 110.0, "bench.window"),
                 (50.0, 60.0, "bench.wait"), (55.0, 70.0, "bench.sleep"),
                 (90.0, 95.0, "bench.submit")]}
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((40 + 20 + 15) * 1e-9)
    assert red["kernel_s"] == pytest.approx((30 + 15) * 1e-9)
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"ticket in flight": 20e-9, "submitting": 5e-9})
