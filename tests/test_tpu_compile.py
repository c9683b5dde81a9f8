"""Compile every Pallas kernel of the serve path for a TPU v5e.

Interpret-mode parity (tests/test_kernels.py, tests/test_backends.py)
cannot catch what only the chip's compiler refuses: unaligned dynamic
lane slices, unsupported primitives, loop carries Mosaic cannot
legalize, VMEM or SMEM overruns. These tests lower each kernel with
interpret mode off, for one chip of a described (not attached)
``v5e:2x2`` topology, at the widths ``chip_smoke.py`` builds and runs
for a 10^7-point index (``taxi``, kd-tree of 256 partitions): n_pad
45,056, 256 knots, a radix table of 1,026 entries, a probe window of
256, 256 queries. spline_search's vector load width and SMEM sizes
follow these. Each compiled program must hold the kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

N_PAD = 45056         # partition row of the 10^7-point index
M_KNOTS = 256         # spline knots per partition
RADIX = (1 << 10) + 2  # radix table entries (10 bits)
Q = 256               # query batch
PROBE = 256           # eps-bounded probe window
EDGES = 12            # polygon vertices (random_polygons' max_edges)
K = 10


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    "spline_search": (
        lambda q, kk, kp, rt, keys, kmin, scale, nk, cnt: ops.spline_search(
            q, kk, kp, rt, keys, kmin, scale, nk, cnt, probe=PROBE,
            radix_bits=10, interpret=False),
        lambda s: (_spec(s, (Q,)), _spec(s, (M_KNOTS,)),
                   _spec(s, (M_KNOTS,)), _spec(s, (RADIX,), jnp.int32),
                   _spec(s, (N_PAD,)), _spec(s, ()), _spec(s, ()),
                   _spec(s, (), jnp.int32), _spec(s, (), jnp.int32))),
    "range_count": (
        lambda r, se, cnt, x, y: ops.range_count(r, se, cnt, x, y,
                                                 interpret=False),
        lambda s: (_spec(s, (Q, 4)), _spec(s, (Q, 2)),
                   _spec(s, (), jnp.int32), _spec(s, (N_PAD,)),
                   _spec(s, (N_PAD,)))),
    "circle_count": (
        lambda r, se, c, cnt, x, y: ops.circle_count(r, se, c, cnt, x, y,
                                                     interpret=False),
        lambda s: (_spec(s, (Q, 4)), _spec(s, (Q, 2)), _spec(s, (Q, 3)),
                   _spec(s, (), jnp.int32), _spec(s, (N_PAD,)),
                   _spec(s, (N_PAD,)))),
    "point_probe": (
        lambda k, x, y, wk, wx, wy: ops.point_probe(
            k, x, y, wk, wx, wy, probe=PROBE, interpret=False),
        lambda s: (_spec(s, (Q,)),) * 3 + (_spec(s, (Q, PROBE)),) * 3),
    "knn_topk": (
        lambda q, cnt, x, y: ops.knn_topk(q, cnt, x, y, k=K,
                                          interpret=False),
        lambda s: (_spec(s, (Q, 2)), _spec(s, (), jnp.int32),
                   _spec(s, (N_PAD,)), _spec(s, (N_PAD,)))),
    "point_in_polygon": (
        lambda p, ne, x, y: ops.point_in_polygon(p, ne, x, y,
                                                 interpret=False),
        lambda s: (_spec(s, (EDGES, 2)), _spec(s, (), jnp.int32),
                   _spec(s, (N_PAD,)), _spec(s, (N_PAD,)))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    compiled = jax.jit(fn).lower(*specs(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
