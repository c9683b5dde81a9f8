"""The harness over four virtual CPU devices: a cell whose ``chips`` is 4
is served over a four-device ``("data",)`` mesh, its points drawn on the
device from the seed, and checked by the reference over candidate
windows (``bench/windows.py``).

Each test runs a script in a subprocess, since the device count has to
be set before JAX starts (this process keeps one device). The cells
live under a ``tmp_path`` root: the committed ``bench/`` with one more
configuration, the committed ``spider-gaussian`` drawn on the device,
and a four-chip entry in ``BENCHMARK.json``.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.conftest import ROOT

DEVICE_CELL = "spider-gaussian-device.interactive-counts"

PRELUDE = r"""
import json, sys
import numpy as np
import jax
root = sys.argv[1]
from bench import check, deploy, gen, windows
from bench.conftest import ENGINE, small_cell
from bench.oracle import Oracle
"""


def device_root(tmp_path):
    """A checkout whose BENCHMARK.json has a four-chip cell over the
    committed configuration, drawn on the device."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/spider-gaussian.json")
                     .read_text())
    cfg.update(name="spider-gaussian-device",
               generator=dict(cfg["generator"],
                              kind="spider_gaussian_device"))
    (tmp_path / "bench/configs/spider-gaussian-device.json").write_text(
        json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": DEVICE_CELL, "config": "spider-gaussian-device",
         "traffic": "interactive-counts", "chips": 4, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def run_script(body: str, root, timeout=600):
    """Run PRELUDE + body on four virtual CPU devices; its last stdout
    line is JSON."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"
                          ).strip(),
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", PRELUDE + body, str(root)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_device_points_independent_of_shards(tmp_path):
    """One seed gives bitwise the same points on 1, 2 and 4 shards, a
    point's id is its position, another seed gives other points, and
    the points are Spider's gaussian inside the unit square."""
    got = run_script(r"""
cfg = {"generator": {"kind": "spider_gaussian_device"}}
pts = {}
for s in (1, 2, 4):
    mesh = jax.make_mesh((s,), ("data",), devices=jax.devices()[:s])
    x, y = deploy.points(cfg, 200_000, 2**31 + 9, mesh=mesh)
    assert x.dtype == y.dtype == np.float32
    assert len(x.sharding.device_set) == s
    pts[s] = (np.asarray(x), np.asarray(y))
same = all(np.array_equal(pts[1][i], pts[s][i]) for s in (2, 4)
           for i in (0, 1))
ids = np.array([0, 49_999, 50_000, 123_457, 199_999])
tx, ty = windows.take(x, y, ids)
by_id = (np.array_equal(tx, pts[1][0][ids])
         and np.array_equal(ty, pts[1][1][ids]))
other, _ = deploy.points(cfg, 200_000, 2**31 + 10, mesh=mesh)
X, Y = pts[1]
print(json.dumps({"same": same, "by_id": by_id,
                  "other": bool(np.array_equal(np.asarray(other), X)),
                  "lo": float(min(X.min(), Y.min())),
                  "hi": float(max(X.max(), Y.max())),
                  "mean": [float(X.mean()), float(Y.mean())],
                  "sd": [float(X.std()), float(Y.std())]}))
""", tmp_path)
    assert got["same"] and got["by_id"] and not got["other"]
    assert 0 <= got["lo"] and got["hi"] <= 1
    assert got["mean"] == pytest.approx([0.5, 0.5], abs=2e-3)
    assert got["sd"] == pytest.approx([0.1, 0.1], abs=2e-3)


def test_window_reference_equals_the_oracle(tmp_path):
    """On every family the reference over candidate windows answers as
    the whole-set Oracle does and judges as it does, on a set with
    duplicates and a lattice: points on rect edges, circles that touch
    points, kNN ties and point misses; a wrong kNN id far outside every
    window is judged on its true distance."""
    got = run_script(r"""
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import core
mesh = jax.make_mesh((4,), ("data",))
g = np.arange(16, dtype=np.float32) / 64 + np.float32(0.25)
gx, gy = (a.ravel() for a in np.meshgrid(g, g))
cfg = {"generator": {"kind": "spider_gaussian_device"}}
dx, dy = (np.asarray(a) for a in deploy.points(cfg, 3744, 3, mesh=mesh))
X = np.concatenate([dx, gx, gx[:64]])         # the lattice's first row twice
Y = np.concatenate([dy, gy, gy[:64]])
put = lambda a: jax.device_put(a, NamedSharding(mesh, P("data")))
x, y = put(X), put(Y)
R = gen.Request
f = lambda *v: np.array(v, np.float32)
reqs = gen.Generator({"loop": "open", "rate": 1, "mix": [
    {"family": "point", "share": 1, "miss_share": 0.5},
    {"family": "rect", "share": 1, "selectivity": 1e-3},
    {"family": "rect", "share": 1, "selectivity": 1e-3, "materialize": True},
    {"family": "circle", "share": 1, "r": [0.005, 0.03]},
    {"family": "circle", "share": 1, "r": [0.005, 0.03],
     "materialize": True},
    {"family": "knn", "share": 1, "k": 10, "jitter": 0.01}]},
    X, Y).batch(120, 9)
lat = [float(v) for v in g[[3, 5, 9]]]
for a, b in zip(lat, lat[1:]):
    reqs += [R("rect", core.RangeCount(), (f([a, a, b, b]).reshape(1, 4),), 1),
             R("rect", core.RangeQuery(), (f([a, b, b, b]).reshape(1, 4),), 1),
             R("circle", core.CircleQuery(), (f(a), f(b), f(1 / 64)), 1),
             R("circle", core.CircleQuery(materialize=True),
               (f(a), f(a), f(2 / 64)), 1),
             R("knn", core.Knn(k=4), (f(a), f(b)), 1),
             R("knn", core.Knn(k=9), (f(a + 1 / 128), f(b + 1 / 128)), 1),
             R("point", core.PointQuery(), (f(a, 0.9), f(b, 0.9)), 1)]
whole = Oracle(X, Y)
ref = windows.Reference(x, y, reqs)
same = [check.judge(ref, r, check.answer(whole, r))
        and check.judge(whole, r, check.answer(ref, r))
        for r in reqs]
fams = sorted({r.spec.kind for r in reqs})
knn = [r for r in reqs if r.spec.kind == "knn"]
far = int(np.argmax((X - 0.9) ** 2 + (Y - 0.9) ** 2))
wrong = []
for r in knn:
    _, v = check.answer(whole, r)
    v = v.copy()
    v[0, -1] = far
    wrong.append(check.judge(ref, r, (None, v)) or
                 check.judge(whole, r, (None, v)))
print(json.dumps({"n": len(reqs), "same": sum(same), "fams": fams,
                  "candidates": int(len(ref.ids)),
                  "wrong_passed": sum(wrong)}))
""", tmp_path)
    assert got["same"] == got["n"]
    assert got["fams"] == ["circle", "knn", "point", "range",
                           "range_count"]
    assert 0 < got["candidates"] < 3744 + 320
    assert got["wrong_passed"] == 0


def test_window_reference_rejects_the_bf16_control(tmp_path):
    """The control (``bench/control.py``), on a cell drawn on the
    device, is judged through the window reference and found wrong;
    the reference in its place is found right."""
    root = device_root(tmp_path)
    got = run_script(r"""
from bench import control
cell = small_cell(%r, root=root, points=20000)
ctl = control.readings(cell, 2**31 + 17, 3.0)
x, y = deploy.points(cell.cfg, 20000, 5,
                     mesh=jax.make_mesh((4,), ("data",)))
reqs = gen.Generator(cell.traffic,
                     *deploy.centres(x, y, 12)).batch(60, 5)
ref = windows.Reference(x, y, reqs)
sound = check.compare([(r, None, True) for r in reqs], ref, control=ref)
print(json.dumps({"control": ctl, "sound": sound.counts}))
""" % DEVICE_CELL, root)
    assert got["control"]["compared"] > 0
    assert got["control"]["wrong_answers"] > 0
    assert got["sound"]["compared"] == 60
    assert got["sound"]["wrong_answers"] == 0


FOUR_CHIPS = r"""
from bench import run as R
cell = small_cell(%r, root=root, points=8000)
# 31 partitions and the overflow one: the executor lays rows out over
# 4 shards in steps of 8, so every shard holds 8, and points
cell.cfg["pinned"]["partitions"] = 31
seen = {}

def probe(session):
    ex = session.executor
    seen["mesh"] = dict(ex.mesh.shape)
    seen["points_by_shard"] = sorted(
        int(np.asarray(s.data).sum()) for s in
        ex.parts["count"].addressable_shards)

if %r:
    from repro.core import local_ops
    local_ops._psum = lambda v, axis: v     # the exchange left out
out = R.run(cell, seed=2**31 + 3, seconds=1.5, trace=False,
            require_tpu=False, cache=False, fault=probe, engine=ENGINE)
out["seen"] = seen
print(json.dumps(out))
"""


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "exchange-left-out"])
def test_four_chip_cell_end_to_end(tmp_path, fault):
    """A four-chip cell drawn on the device runs end to end through
    ``run.run``: served over a four-device ``("data",)`` mesh, correct,
    and its peak memory the fullest device's. With the exchange between
    the chips left out of the program, the check finds it wrong."""
    root = device_root(tmp_path)
    out = run_script(FOUR_CHIPS % (DEVICE_CELL, fault), root, timeout=900)
    assert out["seen"]["mesh"] == {"data": 4}
    by_shard = out["seen"]["points_by_shard"]
    assert len(by_shard) == 4 and min(by_shard) > 0
    assert sum(by_shard) == 8000
    assert out["attempted"] > 0
    assert out["correct"] is (not fault), out["checks"]
    dev = out["device"]
    assert dev["count"] == 4
    assert len(dev["memory_peak_bytes_per_device"]) == 4
    assert dev["memory_peak_bytes"] == max(
        dev["memory_peak_bytes_per_device"])


class _Dev:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_is_the_fullest_device():
    from bench.run import memory_peaks
    devs = [_Dev({"peak_bytes_in_use": 5}), _Dev(None),
            _Dev({"peak_bytes_in_use": 9}), _Dev({})]
    assert memory_peaks(devs) == [5, 0, 9, 0]
