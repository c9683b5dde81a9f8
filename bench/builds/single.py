"""The index built on one device: the kd-tree fitted on the host, then
``repro.core.build_index`` at the configuration's pinned sizes. With a
mesh, the executor shards the result over it."""
import numpy as np


def build(cfg: dict, x, y, seed: int, mesh):
    """(index, partitioner). Points drawn on the device are gathered to
    the host first, so every build starts as one from host points."""
    from repro.core import build_index, fit
    from repro.core.keys import KeySpec

    if not isinstance(x, np.ndarray):
        x, y = np.asarray(x), np.asarray(y)
    pin = cfg["pinned"]
    part = fit(cfg["partitioner"], x, y, int(pin["partitions"]), seed=seed)
    index = build_index(x, y, part,
                        key_spec=KeySpec(bounds=tuple(pin["key_bounds"])),
                        n_pad=int(pin["n_pad"]),
                        radix_bits=int(pin["radix_bits"]))
    return index, part
