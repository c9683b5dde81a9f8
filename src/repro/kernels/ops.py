"""Public jit'd wrappers around the Pallas kernels.

Each op pads inputs to kernel block multiples, dispatches interpret mode
automatically on non-TPU backends, and strips padding from outputs, so
callers (engine / benchmarks / tests) see clean shapes.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels import circle_filter as _cf
from repro.kernels import knn_topk as _knn
from repro.kernels import morton as _morton
from repro.kernels import point_in_polygon as _pip
from repro.kernels import point_probe as _pp
from repro.kernels import range_filter as _rf
from repro.kernels import spline_search as _ss
from repro.kernels.common import interpret_default, pad_to, cdiv


def _interp(flag: Optional[bool]) -> bool:
    return interpret_default() if flag is None else flag


def morton_encode(qx, qy, interpret: Optional[bool] = None):
    """(N,) uint32 quantized coords -> (N,) uint32 morton keys."""
    n = qx.shape[0]
    row = _morton.LANE
    rows = cdiv(n, row * _morton.BLOCK_ROWS) * _morton.BLOCK_ROWS
    qx2 = pad_to(qx, rows * row, 0, 0).reshape(rows, row)
    qy2 = pad_to(qy, rows * row, 0, 0).reshape(rows, row)
    out = _morton.morton_encode_2d(qx2, qy2, interpret=_interp(interpret))
    return out.reshape(-1)[:n]


def spline_search(queries, knot_keys, knot_pos, radix_table, keys_f,
                  kmin, scale, n_knots, count, *, probe: int,
                  radix_bits: int, interpret: Optional[bool] = None):
    """Exact learned lower-bound positions for (Q,) query keys."""
    nq = queries.shape[0]
    qpad = cdiv(nq, _ss.QBLOCK) * _ss.QBLOCK
    q = pad_to(jnp.asarray(queries, jnp.float32), qpad, 0, 0.0)
    fscal = jnp.stack([jnp.asarray(kmin, jnp.float32),
                       jnp.asarray(scale, jnp.float32)])
    iscal = jnp.stack([jnp.asarray(n_knots, jnp.int32),
                       jnp.asarray(count, jnp.int32)])
    pos = _ss.spline_search(q, knot_keys, knot_pos, radix_table, keys_f,
                            fscal, iscal, probe=probe,
                            radix_bits=radix_bits,
                            interpret=_interp(interpret))
    return pos[:nq]


def range_count(rects, se, count, x, y, interpret: Optional[bool] = None):
    """(Q,) in-rect counts within learned [s, e) intervals."""
    nq = rects.shape[0]
    n = x.shape[0]
    qpad = cdiv(nq, _rf.QB) * _rf.QB
    npad = cdiv(n, _rf.NB) * _rf.NB
    rects_p = pad_to(jnp.asarray(rects, jnp.float32), qpad, 0, 0.0)
    se_p = pad_to(jnp.asarray(se, jnp.float32), qpad, 0, 0.0)
    x_p = pad_to(jnp.asarray(x, jnp.float32), npad, 0, 3e38)
    y_p = pad_to(jnp.asarray(y, jnp.float32), npad, 0, 3e38)
    cnt = jnp.asarray([[np.float32(0)]], jnp.float32).at[0, 0].set(
        jnp.asarray(count, jnp.float32))
    out = _rf.range_count(rects_p, se_p, cnt, x_p, y_p,
                          interpret=_interp(interpret))
    return out[:nq]


def circle_count(rects, se, circ, count, x, y,
                 interpret: Optional[bool] = None):
    """(Q,) in-circle counts within learned [s, e) intervals (fused
    MBR filter + distance refine in one kernel pass)."""
    nq = rects.shape[0]
    n = x.shape[0]
    qpad = cdiv(nq, _cf.QB) * _cf.QB
    npad = cdiv(n, _cf.NB) * _cf.NB
    rects_p = pad_to(jnp.asarray(rects, jnp.float32), qpad, 0, 0.0)
    se_p = pad_to(jnp.asarray(se, jnp.float32), qpad, 0, 0.0)
    circ_p = pad_to(jnp.asarray(circ, jnp.float32), qpad, 0, 0.0)
    x_p = pad_to(jnp.asarray(x, jnp.float32), npad, 0, 3e38)
    y_p = pad_to(jnp.asarray(y, jnp.float32), npad, 0, 3e38)
    cnt = jnp.zeros((1, 1), jnp.float32).at[0, 0].set(
        jnp.asarray(count, jnp.float32))
    out = _cf.circle_count(rects_p, se_p, circ_p, cnt, x_p, y_p,
                           interpret=_interp(interpret))
    return out[:nq]


def point_probe(qkf, qx, qy, wk, wx, wy, *, probe: int,
                interpret: Optional[bool] = None):
    """(Q,) exact-match counts in each query's gathered probe window
    (wk/wx/wy: (Q, W >= probe) f32; lanes >= probe are ignored)."""
    nq = qkf.shape[0]
    w = wk.shape[1]
    qpad = cdiv(nq, _pp.QB) * _pp.QB
    wpad = cdiv(w, 128) * 128
    q3 = jnp.stack([jnp.asarray(qkf, jnp.float32),
                    jnp.asarray(qx, jnp.float32),
                    jnp.asarray(qy, jnp.float32),
                    jnp.zeros(nq, jnp.float32)], axis=1)
    q3 = pad_to(q3, qpad, 0, 0.0)
    # window padding uses -3e38 (query pad rows are 0.0, so padding can
    # never fabricate a match before the [:nq] slice anyway)
    wk_p = pad_to(pad_to(jnp.asarray(wk, jnp.float32), wpad, 1, -3e38),
                  qpad, 0, -3e38)
    wx_p = pad_to(pad_to(jnp.asarray(wx, jnp.float32), wpad, 1, -3e38),
                  qpad, 0, -3e38)
    wy_p = pad_to(pad_to(jnp.asarray(wy, jnp.float32), wpad, 1, -3e38),
                  qpad, 0, -3e38)
    out = _pp.point_probe(q3, wk_p, wx_p, wy_p, probe=probe,
                          interpret=_interp(interpret))
    return out[:nq]


def knn_topk(qxy, count, px, py, *, k: int,
             interpret: Optional[bool] = None):
    """Per-query top-k (neg_d2, idx) over one partition's points."""
    nq = qxy.shape[0]
    n = px.shape[0]
    qpad = cdiv(nq, _knn.QB) * _knn.QB
    npad = cdiv(n, _knn.NB) * _knn.NB
    qxy_p = pad_to(jnp.asarray(qxy, jnp.float32), qpad, 0, 0.0)
    px_p = pad_to(jnp.asarray(px, jnp.float32), npad, 0, 3e38)
    py_p = pad_to(jnp.asarray(py, jnp.float32), npad, 0, 3e38)
    cnt = jnp.zeros((1, 1), jnp.float32).at[0, 0].set(
        jnp.asarray(count, jnp.float32))
    negd, idx = _knn.knn_topk(qxy_p, cnt, px_p, py_p, k=k,
                              interpret=_interp(interpret))
    return negd[:nq], idx[:nq]


def point_in_polygon(poly, n_edges, x, y, interpret: Optional[bool] = None):
    """(N,) int32 ray-casting containment flags."""
    n = x.shape[0]
    npad = cdiv(n, _pip.NB) * _pip.NB
    x_p = pad_to(jnp.asarray(x, jnp.float32), npad, 0, 3e38)
    y_p = pad_to(jnp.asarray(y, jnp.float32), npad, 0, 3e38)
    ne = jnp.asarray(n_edges, jnp.int32).reshape(1)
    out = _pip.point_in_polygon(jnp.asarray(poly, jnp.float32), ne,
                                x_p, y_p, interpret=_interp(interpret))
    return out[:n]
