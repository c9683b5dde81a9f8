"""Pluggable kernel backends for the local SPMD programs (DESIGN.md §10).

Every local program in ``core/local_ops.py`` is structured as three
stages:

  lookup   learned key search — spline/radix lower bounds ([s, e)
           intervals or probe positions) against one partition;
  scan     the per-partition point work inside those bounds (masked
           range counts, kNN distance tiles, ray-casting refine);
  merge    cross-partition / cross-shard reduction (psum, all_gather,
           top-k merge) — owned by the program, never by a backend.

A ``Backend`` supplies the lookup + scan stages. Two implementations:

  xla      the pure-jnp reference (bitwise the seed engine's math; the
           golden parity fixture pins it).
  pallas   routes the scan stage onto the purpose-built TPU kernels in
           ``repro/kernels`` (range_filter, knn_topk, spline_search,
           point_in_polygon). On CPU the kernels run in interpret mode
           (kernels/ops.py auto-detects), so both backends are testable
           everywhere; on TPU they compile to real Mosaic kernels.

Dispatch rules (also DESIGN.md §10):

  - Only the FULL-REFINE scan programs dispatch to kernels: range/circle
    exact counts, the point probe, exact kNN, join refine. They scan
    whole partitions — exactly the tile shape the kernels implement —
    and they are the serving fallback half of every fused (windowed +
    lax.cond) program. Circle counts use the fused circle_filter kernel
    (range filter + distance test in ONE pass); the point probe uses the
    point_probe kernel (window equality scan after the learned lookup).
  - The windowed fast paths gather <= cap candidates via dynamic slices;
    their work is proportional to the learned interval, not to the
    partition, so there is nothing for a scan kernel to win — they stay
    on the XLA gather path under both backends.
  - ``vectorize`` tells the chunk loops how to span partitions: the XLA
    stages vmap cleanly; ``pallas_call`` is dispatched per partition via
    ``lax.map`` (one kernel launch per partition row — the grid already
    parallelizes queries x points inside).

Selection: ``EngineConfig.backend`` is "auto" | "xla" | "pallas";
"auto" picks pallas on TPU and the XLA reference elsewhere. The backend
name is part of every executable-cache key (core/plan.py exec_key), so
one executor never mixes compiled programs across backends.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import queries as Q

BACKENDS = ("auto", "xla", "pallas")


class XlaBackend:
    """Reference lookup/scan stages in plain jnp (CPU/GPU/TPU)."""

    name = "xla"
    vectorize = True      # stages are safe under vmap over partitions

    # -- lookup stage -----------------------------------------------------

    def lower_bound(self, part, qkf, *, radix_bits: int, probe: int):
        """Exact learned lower_bound positions for (Q,) keys, one part."""
        return Q.learned_lower_bound(part, qkf, radix_bits=radix_bits,
                                     probe=probe)

    def bounds(self, part, klo_f, khi_f, *, radix_bits: int, probe: int):
        """[s, e) covering all keys in [klo, khi] (one kernel-sized
        batch: both ends share one lookup dispatch)."""
        qn = klo_f.shape[0]
        pos = self.lower_bound(part,
                               jnp.concatenate([klo_f, khi_f + 1.0]),
                               radix_bits=radix_bits, probe=probe)
        return pos[:qn], pos[qn:]

    # -- scan stage -------------------------------------------------------

    def filter_mask(self, part, rects, s, e, active=None):
        """(Q, n_pad) bool — in-[s,e) AND in-rect AND valid (the paper's
        filter phase as a mask, for scans that refine further)."""
        n_pad = part["keys_f"].shape[0]
        posn = jnp.arange(n_pad, dtype=jnp.int32)
        valid = posn < part["count"]
        inpos = ((posn[None, :] >= s[:, None]) &
                 (posn[None, :] < e[:, None]))
        xl, yl, xh, yh = (rects[:, 0:1], rects[:, 1:2], rects[:, 2:3],
                          rects[:, 3:4])
        inrect = ((part["x"][None, :] >= xl) &
                  (part["x"][None, :] <= xh) &
                  (part["y"][None, :] >= yl) &
                  (part["y"][None, :] <= yh))
        m = valid[None, :] & inpos & inrect
        if active is not None:
            m = m & active[:, None]
        return m

    def range_scan(self, part, rects, s, e, active=None):
        """(Q,) exact in-rect counts within learned [s, e) intervals."""
        m = self.filter_mask(part, rects, s, e, active)
        return jnp.sum(m.astype(jnp.int32), axis=1)

    def circle_scan(self, part, rects, s, e, circ, active=None):
        """(Q,) exact in-circle counts (MBR filter + distance refine)."""
        m = self.filter_mask(part, rects, s, e, active)
        dx = part["x"][None, :] - circ[:, 0:1]
        dy = part["y"][None, :] - circ[:, 1:2]
        inc = (dx * dx + dy * dy) <= circ[:, 2:3] ** 2
        return jnp.sum((m & inc).astype(jnp.int32), axis=1)

    def point_windows(self, parts, pid, start, probe: int):
        """Gather each query's (probe,) key/x/y window from ITS
        candidate partition (query-centric — ``parts`` is the full
        (P, ...) dict). Shared by both backends: the gather path is
        dynamic slices, nothing for a partition-resident kernel to
        win."""

        def win(arr):
            return jax.vmap(
                lambda p, s: jax.lax.dynamic_slice(arr, (p, s),
                                                   (1, probe))[0]
            )(pid, start)

        return win(parts["keys_f"]), win(parts["x"]), win(parts["y"])

    def point_scan(self, parts, pid, start, qkf, qx, qy, *,
                   probe: int):
        """(Q,) exact membership flags: equality probe of the window
        [start, start+probe) around the learned position in each
        query's candidate partition (paper Alg. 3 collapsed into one
        masked window reduction)."""
        wk, wx, wy = self.point_windows(parts, pid, start, probe)
        return jnp.any((wk == qkf[:, None]) & (wx == qx[:, None]) &
                       (wy == qy[:, None]), axis=1)

    def knn_scan(self, part, qx, qy, k: int):
        """Per-partition kNN candidates: (neg_d2 (Q, W), vid (Q, W)).

        W is backend-defined — the merge stage only concatenates and
        top-ks. The reference returns the full masked distance row
        (W = n_pad), preserving the seed engine's merge order bitwise.
        """
        del k
        n_pad = part["keys_f"].shape[0]
        dx = part["x"][None, :] - qx[:, None]
        dy = part["y"][None, :] - qy[:, None]
        valid = jnp.arange(n_pad)[None, :] < part["count"]
        d2 = jnp.where(valid, dx * dx + dy * dy, 3e38)
        return -d2, jnp.broadcast_to(part["vid"][None, :], d2.shape)

    def join_scan(self, part, polys, n_edges, mbrs, s, e, active=None):
        """(PG,) per-polygon contained-point counts (filter + ray cast)."""
        m = self.filter_mask(part, mbrs, s, e, active)

        def pip(poly, ne, mask):
            inside = Q.point_in_polygon(part["x"], part["y"], poly, ne)
            return jnp.sum((mask & inside).astype(jnp.int32))

        return jax.vmap(pip)(polys, n_edges, m)

    # -- merge stage helper ----------------------------------------------

    def topk_merge(self, carry_n, carry_v, chunk_n, chunk_v, k: int):
        """Streaming top-k merge: fold one candidate chunk into the
        running (Q, k) best set.

        Shared by both backends (PallasBackend inherits): the kNN scan
        drivers in local_ops consume candidate planes CHUNK BY CHUNK
        instead of materializing the full (Q, C*S*cap) plane, so memory
        per query is O(k + chunk) at any escalation tier. Bitwise
        contract (DESIGN.md §13): lax.top_k breaks value ties by lowest
        index, and the carry — holding survivors of earlier (lower
        plane-index) chunks in their original relative order — is
        concatenated BEFORE the chunk, so the streamed result equals the
        monolithic top_k over the whole plane, tie order included.
        """
        cn = jnp.concatenate([carry_n, chunk_n], axis=1)
        cv = jnp.concatenate([carry_v, chunk_v], axis=1)
        bn, ix = jax.lax.top_k(cn, k)
        return bn, jnp.take_along_axis(cv, ix, axis=1)

    # -- delta stage ------------------------------------------------------
    # Live delta-buffer probes (DESIGN.md §11). Buffers hold <= d_cap
    # points per partition, so a full masked scan IS the optimal plan —
    # like the windowed gathers, there is nothing for a partition-
    # resident kernel to win, and both backends share this jnp path
    # (PallasBackend inherits).

    def delta_live(self, part):
        """(d_cap,) live-slot mask of one partition's delta buffer
        (the per-row form of queries.gather_delta's liveness rule —
        change both together)."""
        slot = jnp.arange(part["dvid"].shape[0], dtype=jnp.int32)
        return (slot < part["dcount"]) & (part["dvid"] >= 0)

    def delta_scan(self, part, rects, circ=None, active=None):
        """(Q,) live buffered points in each rect (and circle)."""
        live = self.delta_live(part)
        xl, yl, xh, yh = (rects[:, 0:1], rects[:, 1:2], rects[:, 2:3],
                          rects[:, 3:4])
        m = (live[None, :] &
             (part["dx"][None, :] >= xl) & (part["dx"][None, :] <= xh) &
             (part["dy"][None, :] >= yl) & (part["dy"][None, :] <= yh))
        if circ is not None:
            dx = part["dx"][None, :] - circ[:, 0:1]
            dy = part["dy"][None, :] - circ[:, 1:2]
            m = m & (dx * dx + dy * dy <= circ[:, 2:3] ** 2)
        if active is not None:
            m = m & active[:, None]
        return jnp.sum(m.astype(jnp.int32), axis=1)

    def delta_join_scan(self, part, polys, n_edges, mbrs, active=None):
        """(PG,) buffered points contained in each polygon."""
        live = self.delta_live(part)
        xl, yl, xh, yh = (mbrs[:, 0:1], mbrs[:, 1:2], mbrs[:, 2:3],
                          mbrs[:, 3:4])
        m = (live[None, :] &
             (part["dx"][None, :] >= xl) & (part["dx"][None, :] <= xh) &
             (part["dy"][None, :] >= yl) & (part["dy"][None, :] <= yh))
        if active is not None:
            m = m & active[:, None]

        def pip(poly, ne, mask):
            inside = Q.point_in_polygon(part["dx"], part["dy"], poly, ne)
            return jnp.sum((mask & inside).astype(jnp.int32))

        return jax.vmap(pip)(polys, n_edges, m)

    def delta_knn_scan(self, part, qx, qy):
        """Buffered kNN candidates: (neg_d2 (Q, d_cap), vid (Q, d_cap))
        — merged by the program exactly like main-plane candidates."""
        live = self.delta_live(part)
        dx = part["dx"][None, :] - qx[:, None]
        dy = part["dy"][None, :] - qy[:, None]
        d2 = jnp.where(live[None, :], dx * dx + dy * dy, 3e38)
        vid = jnp.where(live, part["dvid"], -1)
        return -d2, jnp.broadcast_to(vid[None, :], d2.shape)


class PallasBackend(XlaBackend):
    """Scan stages on the Pallas TPU kernels (interpret mode off-TPU).

    Every full-refine scan stage has a dedicated kernel, and every one
    of them compiles for the TPU (tests/test_tpu_compile.py): the
    learned lookup (spline_search), range, fused circle, point probe,
    kNN and join refine. Only ``filter_mask`` (the join's MBR
    pre-filter) stays the inherited XLA stage: it feeds a mask, not a
    count, to the join refine. ``interpret=None`` resolves once, here:
    interpret mode unless running on a real TPU. ``self.interpret`` is
    the flag every kernel call of this backend uses.
    """

    name = "pallas"
    vectorize = False     # one pallas_call per partition row (lax.map)

    def __init__(self, interpret: Optional[bool] = None):
        from repro.kernels.common import interpret_default
        self.interpret = (interpret_default() if interpret is None
                          else bool(interpret))

    def lower_bound(self, part, qkf, *, radix_bits: int, probe: int):
        from repro.kernels import ops
        return ops.spline_search(
            qkf, part["knot_keys"], part["knot_pos"],
            part["radix_table"], part["keys_f"], part["radix_kmin"],
            part["radix_scale"], part["n_knots"], part["count"],
            probe=probe, radix_bits=radix_bits, interpret=self.interpret)

    def circle_scan(self, part, rects, s, e, circ, active=None):
        from repro.kernels import ops
        se = jnp.stack([s, e], axis=1).astype(jnp.float32)
        cnt = ops.circle_count(rects, se, circ, part["count"],
                               part["x"], part["y"],
                               interpret=self.interpret)
        if active is not None:
            cnt = jnp.where(active, cnt, 0)
        return cnt

    def point_scan(self, parts, pid, start, qkf, qx, qy, *,
                   probe: int):
        from repro.kernels import ops
        wk, wx, wy = self.point_windows(parts, pid, start, probe)
        hits = ops.point_probe(qkf, qx, qy, wk, wx, wy, probe=probe,
                               interpret=self.interpret)
        return hits > 0

    def range_scan(self, part, rects, s, e, active=None):
        from repro.kernels import ops
        se = jnp.stack([s, e], axis=1).astype(jnp.float32)
        cnt = ops.range_count(rects, se, part["count"], part["x"],
                              part["y"], interpret=self.interpret)
        if active is not None:
            # inactive queries cannot count points here (their rect does
            # not overlap this partition's box) — masking matches the
            # reference's in-mask AND exactly
            cnt = jnp.where(active, cnt, 0)
        return cnt

    def knn_scan(self, part, qx, qy, k: int):
        from repro.kernels import knn_topk as _knn
        from repro.kernels import ops
        qxy = jnp.stack([qx, qy], axis=1)
        negd, idx = ops.knn_topk(qxy, part["count"], part["x"],
                                 part["y"], k=k, interpret=self.interpret)
        # kernel idx are partition positions; map through vid, keeping
        # the reference's -1 for sub-k partitions (NEG-valued slots)
        vid = part["vid"][jnp.clip(idx, 0, part["vid"].shape[0] - 1)]
        vid = jnp.where((idx >= 0) & (negd > _knn.NEG), vid, -1)
        return negd, vid

    def join_scan(self, part, polys, n_edges, mbrs, s, e, active=None):
        from repro.kernels import ops
        m = self.filter_mask(part, mbrs, s, e, active)

        def pip(args):
            poly, ne, mask = args
            inside = ops.point_in_polygon(poly, ne, part["x"],
                                          part["y"],
                                          interpret=self.interpret)
            return jnp.sum((mask & (inside > 0)).astype(jnp.int32))

        return jax.lax.map(pip, (polys, n_edges, m))


def resolve_backend(name: str = "auto",
                    interpret: Optional[bool] = None):
    """Backend instance from an EngineConfig.backend string.

    "auto" picks the Pallas kernels when running on real TPU hardware
    and the XLA reference elsewhere; "pallas" forces the kernels (they
    run in interpret mode off-TPU, so this is valid — just slow — on
    CPU, which is exactly what the parity suite exercises).
    """
    if name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}: expected one of {BACKENDS}")
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "xla"
    if name == "pallas":
        return PallasBackend(interpret=interpret)
    return XlaBackend()
