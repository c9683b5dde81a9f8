"""The comparison that decides ``correct`` has to fail what is wrong.

The control: the reference itself, with points and query arguments in
bfloat16 (the precision below the configurations' float32), put in the
program's place; every cell's check must reject it. Then whole runs of
the harness, with the look for a chip skipped, on cells cut to a CPU
size: a sound run is correct, and a run whose timed path is broken
underneath is not, for each fault a one-chip read-only cell can have
(an answer altered where it is produced; half of each coalesced batch
left out), and for windows the program flags as cut: the client asks
again and accepts only a whole one.
"""
import json

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from bench import check, deploy, drive, gen
from bench.conftest import ENGINE, ROOT, small_cell
from bench.oracle import Oracle

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_reference_passes_and_bf16_control_fails(cell):
    c = small_cell(cell, points=20000)
    x, y = deploy.points(c.cfg, c.cfg["points"], 7)
    reqs = gen.Generator(c.traffic, x, y).batch(60, 7)
    answers = [(r, None, True) for r in reqs]
    sound = check.compare(answers, Oracle(x, y), control=Oracle(x, y))
    assert sound.counts["compared"] == 60
    assert sound.counts["wrong_answers"] == 0
    ctl = check.compare(answers, Oracle(x, y),
                        control=Oracle(x, y, dtype=ml_dtypes.bfloat16))
    assert ctl.counts["wrong_answers"] > 0


# -- faults planted under the harness --------------------------------------

def alter_answers(session):
    """Row 0 of every read dispatch comes back wrong."""
    from repro.core import UpdateSpec
    ex = session.executor
    run = ex.run

    def bad(spec, *args, strict=False):
        out = run(spec, *args, strict=strict)
        if isinstance(spec, UpdateSpec):
            return out
        if spec.kind == "point":
            return jnp.asarray(out).at[0].set(~jnp.asarray(out)[0])
        if spec.kind == "knn":
            return out[0], jnp.asarray(out[1]).at[0].add(1)
        if isinstance(out, tuple):              # materialised (cnt, ids, ok)
            return (jnp.asarray(out[0]).at[0].add(1),) + tuple(out[1:])
        return jnp.asarray(out).at[0].add(1)

    ex.run = bad


def drop_half(session):
    """Each read dispatch computes its first half of rows only; the rest
    are answered with the first row's answer."""
    import jax
    from repro.core import UpdateSpec
    ex = session.executor
    run = ex.run

    def bad(spec, *args, strict=False):
        w = int(args[0].shape[0]) if args else 0
        if isinstance(spec, UpdateSpec) or w < 2:
            return run(spec, *args, strict=strict)
        h = (w + 1) // 2
        out = run(spec, *(a[:h] for a in args), strict=strict)
        return jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [jnp.asarray(a), jnp.repeat(jnp.asarray(a)[:1], w - h, 0)]),
            out)

    ex.run = bad


def cut_windows(session, first_only=False):
    """Every materialised window comes back cut: its ok flag false and
    its ids past the first dropped. With ``first_only``, only the first
    time each query is answered; asked again, it comes back whole."""
    ex = session.executor
    run = ex.run
    seen = set()
    session.cut = 0

    def bad(spec, *args, strict=False):
        out = run(spec, *args, strict=strict)
        if not drive.materialised(spec):
            return out
        cnt, ids, ok = out
        rows = [b"".join(np.asarray(a)[i].tobytes() for a in args)
                for i in range(len(args[0]))]
        cut = np.array([not (first_only and k in seen) for k in rows])
        seen.update(rows)
        session.cut += int(cut.sum())
        ids = jnp.where(jnp.asarray(cut)[:, None]
                        & (jnp.arange(ids.shape[1]) > 0)[None, :], -1, ids)
        return cnt, ids, jnp.asarray(ok) & ~jnp.asarray(cut)

    ex.run = bad


def cut_once(session):
    cut_windows(session, first_only=True)


def _run(fault, rate=30.0, windows=False):
    """A whole run of the first cell (no look for a chip) at a CPU size,
    with ``fault`` planted under the timed path; with ``windows``, its
    rects are materialised (RangeQuery windows of ids)."""
    from bench.run import run
    cell = small_cell(CELLS[0], rate=rate)
    if windows:
        for f in cell.traffic["mix"]:
            if f["family"] == "rect":
                f["materialize"] = True
    return run(cell, seed=2**31 + 3, seconds=1.5, trace=False,
               require_tpu=False, cache=False, fault=fault, engine=ENGINE)


@pytest.mark.parametrize("fault", [None, alter_answers],
                         ids=["sound", "answer-altered"])
def test_harness_judges_the_timed_path(fault):
    """Correct only without a fault."""
    judged(fault)


def judged(fault, rate=30.0, windows=False):
    out = _run(fault, rate, windows)
    assert out["attempted"] > 0
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
