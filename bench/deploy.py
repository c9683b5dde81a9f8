"""A deployment: its configuration file, its points, and its index
built at the sizes the configuration pins. The points come from
``bench/data/<generator.kind>.py`` (on the host, or drawn on the
device where the module can), the index from the build the
configuration names, ``bench/builds/<build>.py``.

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
CENTRES = 1 << 20               # query centres sampled from device points


class PinExceeded(RuntimeError):
    """The seed's data needs more than a size the configuration pins."""


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def points(cfg: dict, n: int, seed: int, root: str = None, mesh=None):
    """``n`` points of the configuration's generator for ``seed``, from
    ``bench/data/<generator.kind>.py`` with its ``params``: drawn on the
    device and sharded over ``mesh`` (default: the first device) where
    the module has ``device_points``, else its host ``points``."""
    from bench.gen import module
    g = cfg["generator"]
    mod = module("data", g["kind"], root)
    if not hasattr(mod, "device_points"):
        return mod.points(n, seed, **g.get("params", {}))
    if mesh is None:
        import jax
        mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    return mod.device_points(n, seed, mesh, **g.get("params", {}))


def on_device(x) -> bool:
    """Whether the points live on the device."""
    return not isinstance(x, np.ndarray)


def centres(x, y, seed: int):
    """The points the traffic centres its queries on, on the host: the
    points themselves where they live there, else a uniform sample of
    ``CENTRES`` of them drawn from ``seed`` (all of them where there
    are no more)."""
    if not on_device(x):
        return x, y
    from bench import windows
    n = int(x.shape[0])
    idx = (np.arange(n) if n <= CENTRES
           else np.random.default_rng(seed).integers(0, n, CENTRES))
    return windows.take(x, y, idx)


def build(cfg: dict, x, y, seed: int, mesh=None, root: str = None):
    """(index, partitioner) from the configuration's build,
    ``bench/builds/<build>.py``, held to the pinned static shapes."""
    import jax
    import jax.numpy as jnp
    from bench.gen import module

    pin = cfg["pinned"]
    index, part = module("builds", cfg["build"], root).build(
        cfg, x, y, seed, mesh)
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
