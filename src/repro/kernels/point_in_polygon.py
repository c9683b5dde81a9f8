"""Pallas TPU kernel: ray-casting point-in-polygon (join refine phase).

Grid over point blocks; the (small, broadcast) polygon vertex list stays
whole in SMEM — the paper's broadcast-join structure. A fori_loop walks
edges, reading each edge's end points as scalars; each edge updates the
crossing parity of the whole (1, NB) lane vector (VPU). Cost: E vector
ops per block, E <= a few dozen.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NB = 512


def _kernel(ne_ref, poly_ref, x_ref, y_ref, out_ref):
    n_edges = ne_ref[0]
    e_max = poly_ref.shape[0] // 2
    px = x_ref[...]
    py = y_ref[...]

    def body(i, parity):
        nxt = jnp.where(i + 1 >= n_edges, 0, i + 1)
        x1, y1 = poly_ref[2 * i], poly_ref[2 * i + 1]
        x2, y2 = poly_ref[2 * nxt], poly_ref[2 * nxt + 1]
        cond = (y1 > py) != (y2 > py)
        t = (py - y1) / jnp.where(y2 == y1, 1e-30, y2 - y1)
        xin = x1 + t * (x2 - x1)
        crosses = cond & (px < xin) & (i < n_edges)
        return parity ^ crosses.astype(jnp.int32)

    # int32 parity carry: Mosaic cannot carry a mask through a loop
    out_ref[...] = jax.lax.fori_loop(
        0, e_max, body, jnp.zeros(px.shape, dtype=jnp.int32))


@partial(jax.jit, static_argnames=("interpret",))
def point_in_polygon(poly, n_edges, x, y, *, interpret: bool):
    """poly: (E, 2) f32 ; n_edges: (1,) int32 ; x, y: (N,) f32.

    Returns (N,) int32 inside flags.
    """
    n = x.shape[0]
    assert n % NB == 0
    smem = partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _kernel,
        grid=(n // NB,),
        in_specs=[
            smem(),
            smem(),
            pl.BlockSpec((1, NB), lambda i: (0, i)),
            pl.BlockSpec((1, NB), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, NB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(n_edges, poly.reshape(-1), x.reshape(1, -1), y.reshape(1, -1))
    return out.reshape(-1)
