"""The one traffic generator: a traffic file's parameters -> requests.

A traffic mix is a JSON file, ``bench/traffic/<mix>.json``, found by
the name a cell gives it. It names its ``loop`` (the arrival process,
``bench/loops/<loop>.py``) with that loop's parameters, and its ``mix``:
query families, each ``bench/families/<family>.py``, with a ``share``
and the family's own sizes. ``check_per_family`` is how many answers of
each family the check compares; ``warm_seconds`` and ``warm_rounds``
how much of the mix set-up sends before the window.

Every seed gets the same work in another order: family counts are
exact shares of the requests, each family draws its sizes as evenly
spaced quantiles in the seed's order, and the loop's gaps likewise;
only the places (data points) are drawn.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Request:
    family: str
    spec: object            # the QuerySpec submitted
    args: tuple             # numpy arrays, query axis first
    queries: int            # queries answered (queries_per_s counts them)


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def module(kind: str, name: str, root: str = None):
    """``bench/<kind>/<name>.py`` under ``root`` (default: this
    checkout's), imported by its path; an unknown name is an error."""
    path = os.path.join(root or os.path.dirname(HERE), "bench", kind,
                        name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"no {kind} named {name!r} ({path})")
    tag = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts(shares, n: int):
    """Exact integer split of n by shares (largest remainder)."""
    s = np.asarray(shares, np.float64)
    raw = s / s.sum() * n
    c = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - c), kind="stable")[: n - int(c.sum())]:
        c[i] += 1
    return c


def spread(lo: float, hi: float, n: int, rng):
    """n values evenly spaced over [lo, hi], in the seed's order."""
    v = lo + (hi - lo) * (np.arange(n) + 0.5) / max(n, 1)
    return rng.permutation(v).astype(np.float32)


class Generator:
    """Requests of one traffic mix over one point set, from a seed.
    Families and the loop are found by name under ``root``."""

    Request = Request
    spread = staticmethod(spread)

    def __init__(self, traffic: dict, x, y, root: str = None):
        from repro import core
        self.core = core
        self.t = traffic
        self.x, self.y = x, y
        self.loop = module("loops", traffic["loop"], root)
        self.families = {f["family"]: module("families", f["family"], root)
                         for f in traffic["mix"]}

    def family(self, f: dict, n: int, rng):
        """n requests of the mix entry ``f``."""
        return self.families[f["family"]].requests(self, f, n, rng)

    def batch(self, n: int, seed: int):
        """n requests in the mix's shares, in the seed's order."""
        rng = np.random.default_rng(seed)
        mix = self.t["mix"]
        per = [self.family(f, int(c), rng)
               for f, c in zip(mix, counts([f["share"] for f in mix], n))]
        order = rng.permutation(np.concatenate(
            [np.full(len(p), i, np.int64) for i, p in enumerate(per)]))
        taken = [0] * len(per)
        out = []
        for i in order.tolist():
            out.append(per[i][taken[i]])
            taken[i] += 1
        return out

    def stream(self, seconds: float, seed: int):
        """(requests, due times in seconds from the window's start) of
        one window, as the loop sends them."""
        return self.loop.requests(self, seconds, seed)
