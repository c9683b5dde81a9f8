"""A deployment: its configuration file, its points, and its index
built at the sizes the configuration pins.

The program sizes several static shapes from the data: the partition
width ``n_pad`` from the largest kd-tree partition, the knot width from
the most knots any partition's spline needs, the probe window from the
longest run of equal keys, and the key space from the data's bounds.
Each of these is part of every compiled program, so a seed that moved
one would recompile everything. The configuration pins them: the build
is given ``n_pad`` and the key bounds, and the knot and probe widths
are widened to the pinned values (knot rows padded the way the spline
pads them, a wider probe window only searches more). A seed whose data
needs more than a pinned size fails the run rather than change it.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

KNOT_PAD = np.float32(3.4e38)   # the spline's padding key (core/spline.POS)


class PinExceeded(RuntimeError):
    """The seed's data needs more than a size the configuration pins."""


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def points(cfg: dict, n: int, seed: int, root: str = None):
    """``n`` points of the configuration's generator for ``seed``: the
    ``points`` of ``bench/data/<generator.kind>.py`` with its
    ``params``."""
    from bench.gen import module
    g = cfg["generator"]
    return module("data", g["kind"], root).points(n, seed,
                                                  **g.get("params", {}))


def build(cfg: dict, x, y, seed: int):
    """(index, partitioner) at the pinned static shapes."""
    import jax
    import jax.numpy as jnp
    from repro.core import build_index, fit
    from repro.core.keys import KeySpec

    pin = cfg["pinned"]
    part = fit(cfg["partitioner"], x, y, int(pin["partitions"]), seed=seed)
    index = build_index(x, y, part,
                        key_spec=KeySpec(bounds=tuple(pin["key_bounds"])),
                        n_pad=int(pin["n_pad"]),
                        radix_bits=int(pin["radix_bits"]))
    biggest = int(jnp.max(index.count))
    if biggest > index.n_pad:
        raise PinExceeded(f"largest partition holds {biggest} points, "
                          f"pinned n_pad is {index.n_pad}")
    if index.probe > int(pin["probe"]):
        raise PinExceeded(f"data needs probe {index.probe}, pinned "
                          f"{pin['probe']}")
    knots = int(index.knot_keys.shape[1])
    if knots > int(pin["knots"]):
        raise PinExceeded(f"data needs {knots} knots, pinned "
                          f"{pin['knots']}")
    extra = int(pin["knots"]) - knots
    if extra:
        rows = index.knot_keys.shape[0]
        index = dataclasses.replace(
            index,
            knot_keys=jnp.concatenate(
                [index.knot_keys,
                 jnp.full((rows, extra), KNOT_PAD, jnp.float32)], axis=1),
            knot_pos=jnp.concatenate(
                [index.knot_pos, jnp.zeros((rows, extra), jnp.float32)],
                axis=1))
    index = dataclasses.replace(index, probe=int(pin["probe"]))
    jax.block_until_ready(index)
    return index, part


def shapes(index) -> dict:
    """The static shapes every compiled program depends on."""
    return {"partitions": int(index.num_partitions),
            "n_pad": int(index.n_pad),
            "knots": int(index.knot_keys.shape[1]),
            "radix": int(index.radix_table.shape[1]),
            "probe": int(index.probe),
            "key_spec": dataclasses.asdict(index.key_spec)}
