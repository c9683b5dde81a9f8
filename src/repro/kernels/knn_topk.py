"""Pallas TPU kernel: batched kNN distance + streaming top-k merge.

Grid (query blocks x point blocks). Each step computes the (QB, NB)
squared-distance tile on the VPU and merges it into the running per-query
top-k held in the output blocks (resident in VMEM across the point axis).

Merge strategy: k rounds of (max, mask) selection over the concatenated
candidate row — k is small (paper: k <= 100, default 10), so k*(NB+k)
compares per tile beat a full sort. Index tracking uses the
iota-equality-select idiom (no gather needed on the lane axis).

Note on the MXU: for 2-D spatial coords the classic
||q-p||^2 = ||q||^2 + ||p||^2 - 2 q.p matmul trick degenerates to a
(QB x 2 x NB) contraction — too thin to feed the 128x128 systolic array,
so the VPU broadcast form is used; the matmul form wins only for
high-dimensional points (documented in DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import iota2

QB = 128
NB = 512
NEG = -3.0e38  # python float: avoids captured-const tracing in the kernel


def _kernel(q_ref, cnt_ref, px_ref, py_ref, outv_ref, outi_ref, *, k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        outv_ref[...] = jnp.full_like(outv_ref, NEG)
        outi_ref[...] = jnp.full_like(outi_ref, -1)

    count = cnt_ref[0, 0].astype(jnp.int32)
    pos = j * NB + iota2((1, NB), 1)
    qx = q_ref[:, 0:1]
    qy = q_ref[:, 1:2]
    dx = px_ref[...] - qx                               # (QB, NB)
    dy = py_ref[...] - qy
    negd = jnp.where(pos < count, -(dx * dx + dy * dy), NEG)

    cand_v = jnp.concatenate([outv_ref[...], negd], axis=1)
    cand_i = jnp.concatenate(
        [outi_ref[...], jnp.broadcast_to(pos, negd.shape)], axis=1)
    width = cand_v.shape[1]
    lane = iota2((1, width), 1)

    best_v = []
    best_i = []
    for _ in range(k):                                   # static unroll
        m = jnp.max(cand_v, axis=1, keepdims=True)       # (QB, 1)
        # first lane holding the max: lowest-index tie order, as
        # lax.top_k (the carry precedes the tile, so earlier positions
        # win ties)
        first = jnp.min(jnp.where(cand_v == m, lane, width), axis=1,
                        keepdims=True)
        hit = lane == first
        sel_i = jnp.sum(jnp.where(hit, cand_i, 0), axis=1, keepdims=True)
        best_v.append(m)
        best_i.append(sel_i)
        cand_v = jnp.where(hit, NEG, cand_v)
    outv_ref[...] = jnp.concatenate(best_v, axis=1)
    outi_ref[...] = jnp.concatenate(best_i, axis=1)


@partial(jax.jit, static_argnames=("k", "interpret"))
def knn_topk(qxy, cnt_scalar, px, py, *, k: int, interpret: bool):
    """Top-k nearest points per query on ONE partition.

    qxy: (Q, 2) f32 ; cnt_scalar: (1, 1) f32 ; px, py: (N,) f32
    Returns (neg_d2 (Q, k) f32, idx (Q, k) int32) — idx are positions in
    the partition row (map through vid outside).
    """
    nq = qxy.shape[0]
    n = px.shape[0]
    assert nq % QB == 0 and n % NB == 0
    grid = (nq // QB, n // NB)
    outv, outi = pl.pallas_call(
        partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((QB, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, NB), lambda i, j: (0, j)),
            pl.BlockSpec((1, NB), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((QB, k), lambda i, j: (i, 0)),
            pl.BlockSpec((QB, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(qxy, cnt_scalar, px.reshape(1, -1), py.reshape(1, -1))
    return outv, outi
