"""Per-kind read preparation (DESIGN.md §9): the argument tuple each read
family's local programs take, built from the query arrays a caller
passes.

Every function here is traceable and takes ``(parts, bounds, *query)``
like the local programs (core/local_ops.py), but reads no partition
shard of ``parts``: the executor compiles each one as one program per
(kind, batch width), a plain jit even on a mesh — never wrapped in
``shard_map``, since kNN's seed radius reads the count of any partition
(the whole count row is one of its inputs). Run eagerly, the same code
is the op-by-op host prep it replaced, and its outputs are bitwise equal
to it: ``tests/test_prep.py`` holds that reference.
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp

from repro.core import keys as K
from repro.core import local_ops as L
from repro.core import queries as Q


def rect_keys(rects, spec: K.KeySpec):
    """(klo, khi) of rects, as the exact f32 image the programs read."""
    klo, khi = K.rect_key_range(rects, spec)
    return K.keys_to_f32(klo), K.keys_to_f32(khi)


def point(spec, parts, bounds, qx, qy):
    """(qx, qy) -> (qx, qy, qk): the query points' sort keys."""
    return qx, qy, K.keys_to_f32(K.make_keys(qx, qy, spec))


def rect(spec, parts, bounds, rects):
    """rects -> (rects, klo, khi), for range counts and range queries."""
    return (rects,) + rect_keys(rects, spec)


def circle(spec, parts, bounds, cx, cy, r):
    """(cx, cy, r) -> (rects, klo, khi, circ): the circle's bounding
    rect, its key range and the (cx, cy, r) stack."""
    rects = jnp.stack([cx - r, cy - r, cx + r, cy + r], axis=-1)
    circ = jnp.stack([cx, cy, r], axis=-1)
    return (rects,) + rect_keys(rects, spec) + (circ,)


def knn(k, parts, bounds, qx, qy, r0g, count):
    """(qx, qy, r0g, count) -> (qx, qy, r0): each query's seed radius.

    Paper Eq. (1): r = sqrt(k / (pi * d)) — refined with the LOCAL
    density of each query's nearest partition (beyond-paper: the
    global-density estimate ``r0g`` needs many expansion rounds in
    sparse regions; the per-partition counts are free in the global
    index). ``r0g`` is a float32 scalar input, so a write that moves
    the global density compiles nothing; ``count`` is the index's
    whole (P,) count row, not the partition shards of ``parts``."""
    bd2 = Q.box_min_dist2(qx, qy, bounds)
    pid0 = jnp.argmin(bd2, axis=1)
    b0 = bounds[pid0]
    area0 = jnp.maximum((b0[:, 2] - b0[:, 0]) *
                        (b0[:, 3] - b0[:, 1]), 1e-30)
    d0 = jnp.maximum(count[pid0] / area0, 1e-30)
    r0 = jnp.sqrt(k / (jnp.pi * d0)).astype(jnp.float32)
    return qx, qy, jnp.maximum(r0, r0g)


def join(spec, parts, bounds, polys, n_edges):
    """(polys, n_edges) -> (polys, n_edges, mbr_k): each polygon's MBR
    over its real edges, with the MBR's key range appended."""
    em = L._edge_mask(polys, n_edges)
    mbrs = jnp.concatenate([
        jnp.min(jnp.where(em, polys, 3e38), axis=1),
        jnp.max(jnp.where(em, polys, -3e38), axis=1)], axis=-1)
    klo, khi = rect_keys(mbrs, spec)
    mbr_k = jnp.concatenate([mbrs, klo[:, None], khi[:, None]], axis=-1)
    return polys, n_edges, mbr_k


def program(base, spec: K.KeySpec):
    """The prep function of an exec-key base ``("prep", kind[, k])``."""
    kind = base[1]
    if kind == "knn":
        return partial(knn, int(base[2]))
    return partial({"point": point, "rect": rect, "circle": circle,
                    "join": join}[kind], spec)
