"""Per-shard local query programs (paper §3-4, DESIGN.md §2/§9/§10).

Each class below is a local SPMD program: a callable
``fn(parts, bounds, *query_args, axis=...)`` with attribute
``n_query_args`` so the executor knows its signature. ``bounds`` is the
REPLICATED global index; ``parts`` leaves are LOCAL partition shards.
The executor (core/executor.py) owns jit + shard_map wrapping, the
executable cache, and the adaptive-cap policy; nothing here retries or
synchronizes with the host.

Every program is staged lookup -> scan -> merge (DESIGN.md §10): the
lookup (learned bounds) and scan (per-partition point work) stages come
from the pluggable kernel backend (core/backends.py — XLA reference or
the Pallas TPU kernels); the merge stage (collectives) stays here:

  point  -> psum (boolean OR as integer sum)
  range  -> psum of counts / all_gather of windowed candidate ids
  kNN    -> per-shard top-k, all_gather, merge top-k
  join   -> psum of per-polygon counts
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as K
from repro.core import queries as Q
from repro.core.build import LearnedSpatialIndex
from repro.core.plan import EngineConfig

EMPTY_BOX = np.asarray([3e38, 3e38, -3e38, -3e38], np.float32)


def pad_partitions(index: LearnedSpatialIndex, multiple: int
                   ) -> LearnedSpatialIndex:
    """Pad the partition axis with empty partitions (never match queries)."""
    p = index.num_partitions
    p_pad = int(np.ceil(p / multiple) * multiple)
    if p_pad == p:
        return index
    extra = p_pad - p

    def pad(a, fill):
        pad_block = jnp.full((extra,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, pad_block], axis=0)

    def pad_opt(a, fill):
        return None if a is None else pad(a, fill)

    return dataclasses.replace(
        index,
        key=pad(index.key, index.key_spec.sentinel),
        x=pad(index.x, 3e38), y=pad(index.y, 3e38), vid=pad(index.vid, -1),
        count=pad(index.count, 0),
        knot_keys=pad(index.knot_keys, 3e38),
        knot_pos=pad(index.knot_pos, 0.0),
        n_knots=pad(index.n_knots, 0),
        radix_table=pad(index.radix_table, 0),
        radix_kmin=pad(index.radix_kmin, 0.0),
        radix_scale=pad(index.radix_scale, 0.0),
        part_bounds=jnp.concatenate(
            [index.part_bounds,
             jnp.broadcast_to(jnp.asarray(EMPTY_BOX), (extra, 4))], axis=0),
        delta_key=pad_opt(index.delta_key, index.key_spec.sentinel),
        delta_x=pad_opt(index.delta_x, 3e38),
        delta_y=pad_opt(index.delta_y, 3e38),
        delta_vid=pad_opt(index.delta_vid, -1),
        delta_count=pad_opt(index.delta_count, 0),
        dead=pad_opt(index.dead, 0),
        max_run=pad_opt(index.max_run, 0),
        refit_gen=pad_opt(index.refit_gen, 0),
        # the true overflow grid keeps its pre-padding position
        overflow_pid=index.overflow,
    )


def part_leaf_names(index: LearnedSpatialIndex) -> set:
    """Leaf names part_arrays would produce (no arrays materialized)."""
    names = {"keys_f", "x", "y", "vid", "count", "knot_keys",
             "knot_pos", "n_knots", "radix_table", "radix_kmin",
             "radix_scale"}
    if index.delta_cap:
        names |= {"dx", "dy", "dvid", "dcount"}
    return names


def part_arrays(index: LearnedSpatialIndex, leaves=None) -> dict:
    """Shardable dict-of-arrays view (leading axis = partitions).

    The delta-buffer leaves appear only when the index carries a
    non-zero delta capacity, so frozen-index programs (and the dry-run
    harness, which builds this dict by hand) are unchanged. ``leaves``
    restricts the result to the named subset — the executor's update
    path refreshes only the planes a mutation touched, and in
    particular skips the O(N) keys_f cast unless the key plane moved.
    """
    parts = {
        "x": index.x, "y": index.y, "vid": index.vid,
        "count": index.count,
        "knot_keys": index.knot_keys, "knot_pos": index.knot_pos,
        "n_knots": index.n_knots, "radix_table": index.radix_table,
        "radix_kmin": index.radix_kmin, "radix_scale": index.radix_scale,
    }
    if index.delta_cap:
        parts.update({
            "dx": index.delta_x, "dy": index.delta_y,
            "dvid": index.delta_vid, "dcount": index.delta_count,
        })
    if leaves is None or "keys_f" in leaves:
        parts["keys_f"] = K.keys_to_f32(index.key)
    if leaves is not None:
        return {k: parts[k] for k in leaves}
    return parts


def _map_parts(f, parts, chunk: int, init=None):
    """Sequential lax.map over partition chunks (bounds peak memory).

    f(chunk_parts, carry) -> carry ; chunk_parts leaves (C, ...).
    """
    p = parts["count"].shape[0]
    c = min(chunk, p)
    assert p % c == 0, (p, c)
    chunked = jax.tree_util.tree_map(
        lambda a: a.reshape((p // c, c) + a.shape[1:]), parts)

    def step(carry, ch):
        return f(ch, carry), None

    carry, _ = jax.lax.scan(step, init, chunked)
    return carry


def _for_parts(backend, f, xs):
    """Span f over one chunk's partitions, backend-appropriately.

    The XLA stages vectorize (vmap); a pallas_call is dispatched once
    per partition row via lax.map — its grid already parallelizes
    queries x points, and batching rules for kernels are not relied on.
    ``xs`` is a tuple of per-partition-stacked args; returns stacked
    outputs either way.
    """
    if backend.vectorize:
        return jax.vmap(f)(*xs)
    return jax.lax.map(lambda a: f(*a), xs)


def _edge_mask(polys, n_edges):
    e = polys.shape[1]
    return (jnp.arange(e)[None, :, None] < n_edges[:, None, None])


def _axes(axis):
    return axis if isinstance(axis, tuple) else (axis,)


def _psum(x, axis):
    return x if axis is None else jax.lax.psum(x, axis)


def _top_candidates(flags, c: int):
    """First C true columns per row of (Q, P) flags.

    lax.top_k on a descending column-priority score — O(P*C) instead of
    the O(P log P) full argsort it replaces; top_k's lowest-index
    tie-break reproduces the stable sort's layout bitwise (true columns
    ascending, then false columns ascending).

    Returns (pids (Q, C) int32, valid (Q, C), within (Q,) — True when the
    row had <= C candidates, i.e. the result is complete)."""
    qn, p = flags.shape
    c = min(c, p)
    col = jnp.arange(p, dtype=jnp.int32)
    score = jnp.where(flags, p - col, 0)
    _, order = jax.lax.top_k(score, c)
    valid = jnp.take_along_axis(flags, order, axis=1)
    within = jnp.sum(flags.astype(jnp.int32), axis=1) <= c
    return order.astype(jnp.int32), valid, within


def _compact_ids(vids, keep: int):
    """Order-preserving stream compaction of (Q, W) -1-padded ids to the
    first ``keep`` slots (the gather half of ``_keep_window``; also the
    per-chunk merge step of the streaming compaction — concatenating an
    already-compacted carry BEFORE a raw chunk and recompacting keeps
    exactly the first ``keep`` valid ids in global plane order)."""
    qn, w = vids.shape
    keep = min(keep, w)
    cum = jnp.cumsum((vids >= 0).astype(jnp.int32), axis=1)
    tgt = jnp.arange(1, keep + 1, dtype=jnp.int32)
    idx = jax.vmap(lambda c: jnp.searchsorted(c, tgt))(cum)
    kept = jnp.take_along_axis(vids, jnp.minimum(idx, w - 1), axis=1)
    return jnp.where(tgt[None, :] <= cum[:, -1:], kept, -1)


def _keep_window(vids, cnt, cap: int, keep=None):
    """Compact materialized ids to the front, bounded keep width.

    Cumsum stream compaction: the running count of valid ids gives each
    output slot k its source position (the first index whose cumsum
    reaches k+1, found by searchsorted on the monotone cumsum row), so
    the kept window is ONE gather — O(W + keep log W) instead of the
    O(W log W) full-width argsort this replaces, with the identical
    (order-preserving) layout. The gather formulation is deliberate:
    the equivalent scatter (slot per valid id) is scalarized by XLA:CPU
    and measures ~12x slower at serving widths.

    ``keep`` overrides the default width bound — the chunked circle
    compaction passes the MONOLITHIC plane's bound explicitly so its
    final output is bitwise the unchunked one.

    Returns (vids (Q, keep), cap_ok (Q,) — True when no id was dropped).
    """
    qn, w = vids.shape
    if keep is None:
        keep = min(w, max(cap * 8, 256))
    kept = _compact_ids(vids, keep)
    cap_ok = jnp.sum((kept >= 0).astype(jnp.int32), axis=1) == cnt
    return kept, cap_ok


def _chunk_cands(cc: int, *arrays):
    """Split candidate-axis arrays (Q, C, ...) into lax.scan inputs
    (nch, Q, cc, ...), padding the candidate axis with inactive slots
    (EMPTY_BOX boxes / False masks / pid 0) that can never match."""
    c = arrays[0].shape[1]
    nch = -(-c // cc)
    pad = nch * cc - c

    def prep(a):
        if pad:
            if a.dtype == jnp.float32 and a.ndim == 3:   # candidate boxes
                blk = jnp.broadcast_to(jnp.asarray(EMPTY_BOX),
                                       (a.shape[0], pad, 4))
            else:
                blk = jnp.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)
            a = jnp.concatenate([a, blk], axis=1)
        a = a.reshape((a.shape[0], nch, cc) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    return tuple(prep(a) for a in arrays)


def _delta_knn_candidates(parts, pid, valid, qx, qy, r):
    """Live buffered candidates within radius r of (Q, C) candidate
    partitions (the kNN delta probe, DESIGN.md §11; liveness comes
    from the shared Q.gather_delta rule).

    Returns (counts (Q,), vids (Q, C*d_cap), neg_d2 (Q, C*d_cap)).
    """
    qn = pid.shape[0]
    dx, dy, dv, live = Q.gather_delta(parts, pid, valid)
    d2 = ((dx - qx[:, None, None]) ** 2 + (dy - qy[:, None, None]) ** 2)
    inc = live & (d2 <= (r * r)[:, None, None])
    return (jnp.sum(inc.astype(jnp.int32), axis=(1, 2)),
            jnp.where(inc, dv, -1).reshape(qn, -1),
            jnp.where(inc, -d2, -3e38).reshape(qn, -1))


# ---------------------------------------------------------------------------
# local programs
# ---------------------------------------------------------------------------

class _LocalFn:
    def __init__(self, index: LearnedSpatialIndex, cfg: EngineConfig,
                 backend):
        self.kw = dict(radix_bits=index.radix_bits, probe=index.probe)
        self.cfg = cfg
        self.backend = backend
        self.p_total = index.num_partitions
        self.n_pad = index.n_pad
        self.spec = index.key_spec
        # static: d_cap == 0 compiles the delta probes away entirely,
        # keeping frozen-index programs bitwise the pre-update ones
        self.d_cap = index.delta_cap
        self.overflow = index.overflow

    def _local_offset(self, axis, p_loc):
        if axis is None:
            return jnp.int32(0)
        idx = jnp.int32(0)
        mul = jnp.int32(1)
        for a in reversed(axis):
            idx = idx + jax.lax.axis_index(a) * mul
            mul = mul * jax.lax.axis_size(a)
        return idx * p_loc


class _PointLocal(_LocalFn):
    """Staged point probe, query-centric: each query touches only its
    first-match grid partition and the overflow grid (paper Alg. 1) —
    never a partition sweep. The lookup is the shared query-centric
    learned search (Q.lower_bound_at, one knot-row gather per query);
    the scan is the backend's point_scan stage over the gathered probe
    windows (the pallas backend reduces the whole batch in ONE
    point_probe kernel launch)."""

    n_query_args = 3

    def __call__(self, parts, bounds, qx, qy, qk, *, axis):
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        bk = self.backend
        probe = self.kw["probe"]
        n_pad = parts["keys_f"].shape[1]
        # global filter: first-match grid (paper Alg. 1 semantics) and the
        # overflow grid are the only partitions that can contain the point.
        inb = Q.point_in_box(qx, qy, bounds[:self.overflow])  # (Q, G)
        hit = jnp.any(inb, axis=1)
        pid1 = jnp.where(hit, jnp.argmax(inb, axis=1).astype(jnp.int32),
                         self.overflow)
        pid2 = jnp.full_like(pid1, self.overflow)         # overflow grid

        def probe_pid(pid):
            lid = pid - off
            mine = (lid >= 0) & (lid < p_loc)
            lid = jnp.clip(lid, 0, p_loc - 1)
            pos = Q.lower_bound_at(parts, lid, qk, **self.kw)  # lookup
            start = jnp.clip(pos - probe // 2, 0, n_pad - probe)
            f = bk.point_scan(parts, lid, start, qk, qx, qy,   # scan
                              probe=probe)
            if self.d_cap:                                 # delta probe
                ddx, ddy, _, live = Q.gather_delta(
                    parts, lid[:, None], mine[:, None])
                f = f | jnp.any(live[:, 0] &
                                (ddx[:, 0] == qx[:, None]) &
                                (ddy[:, 0] == qy[:, None]), axis=1)
            return f & mine

        found = probe_pid(pid1) | probe_pid(pid2)
        return _psum(found.astype(jnp.int32), axis)           # merge


class _RangeCountLocal(_LocalFn):
    n_query_args = 3

    def __call__(self, parts, bounds, rects, klo, khi, *, axis):
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        bk = self.backend
        overlap = Q.rect_overlaps_box(rects, bounds)      # (Q, P_total)

        def chunk_fn(ch, carry):
            c = ch["count"].shape[0]
            base = carry["i"] * c + off

            def one(j, part):
                act = jax.lax.dynamic_index_in_dim(
                    overlap, base + j, axis=1, keepdims=False)
                s, e = bk.bounds(part, klo, khi, **self.kw)   # lookup
                cnt = bk.range_scan(part, rects, s, e,        # scan
                                    active=act)
                if self.d_cap:
                    cnt = cnt + bk.delta_scan(part, rects, active=act)
                return cnt

            cnts = _for_parts(bk, one, (jnp.arange(c), ch))   # (C, Q)
            return {"i": carry["i"] + 1,
                    "acc": carry["acc"] + jnp.sum(cnts, axis=0)}

        out = _map_parts(chunk_fn, parts, self.cfg.part_chunk,
                         init={"i": jnp.int32(0),
                               "acc": jnp.zeros(rects.shape[0], jnp.int32)})
        return _psum(out["acc"], axis)                        # merge


class _CircleCountLocal(_LocalFn):
    """Exact full-refine circle count (fallback / gridonly baseline)."""

    n_query_args = 4

    def __call__(self, parts, bounds, rects, klo, khi, circ, *, axis):
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        bk = self.backend
        overlap = Q.rect_overlaps_box(rects, bounds)

        def chunk_fn(ch, carry):
            c = ch["count"].shape[0]
            base = carry["i"] * c + off

            def one(j, part):
                act = jax.lax.dynamic_index_in_dim(
                    overlap, base + j, axis=1, keepdims=False)
                s, e = bk.bounds(part, klo, khi, **self.kw)   # lookup
                cnt = bk.circle_scan(part, rects, s, e, circ,  # scan
                                     active=act)
                if self.d_cap:
                    cnt = cnt + bk.delta_scan(part, rects, circ=circ,
                                              active=act)
                return cnt

            cnts = _for_parts(bk, one, (jnp.arange(c), ch))
            return {"i": carry["i"] + 1,
                    "acc": carry["acc"] + jnp.sum(cnts, axis=0)}

        out = _map_parts(chunk_fn, parts, self.cfg.part_chunk,
                         init={"i": jnp.int32(0),
                               "acc": jnp.zeros(rects.shape[0], jnp.int32)})
        return _psum(out["acc"], axis)                        # merge


class _RangeWindowLocal(_LocalFn):
    """Query-centric windowed range query (the paper's two-phase shape):
    phase 1 selects the <=C candidate partitions per query from the
    replicated global index; phase 2 gathers ONLY each candidate's
    learned key interval (cap slots). Work ~ Q x C x cap, independent of
    the total partition count and of partition size."""

    n_query_args = 3

    def __init__(self, index, cfg, backend, cap, cand):
        super().__init__(index, cfg, backend)
        self.cap = min(cap, index.n_pad)
        self.cand = cand

    def __call__(self, parts, bounds, rects, klo, khi, *, axis):
        del klo, khi   # recomputed per-candidate with clipping
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        qn = rects.shape[0]
        overlap = Q.rect_overlaps_box(rects, bounds)       # (Q, P_total)
        pids, valid, within = _top_candidates(overlap, self.cand)
        boxes = bounds[pids.reshape(-1)].reshape(qn, self.cand, 4)
        local = pids - off
        mine = valid & (local >= 0) & (local < p_loc)
        local = jnp.clip(local, 0, p_loc - 1)
        cnts, vids, ok, _, _ = Q.range_window_at(
            parts, boxes, local, mine, rects, self.spec, cap=self.cap,
            **self.kw)
        if self.d_cap:
            dcnts, dvids = Q.delta_window_at(parts, local, mine, rects)
            cnts = cnts + dcnts
            vids = jnp.concatenate([vids, dvids], axis=-1)
        cnt = _psum(jnp.sum(cnts, axis=1), axis)
        vids = vids.reshape(qn, -1)
        okq = jnp.all(ok | ~mine, axis=1)
        if axis is not None:
            vids = jax.lax.all_gather(vids, axis, axis=1, tiled=True)
            shards = jax.lax.axis_size(axis)
            okq = jax.lax.psum(okq.astype(jnp.int32), axis) == shards
        vids, cap_ok = _keep_window(vids, cnt, self.cap)
        return cnt, vids, okq & within & cap_ok


class _CircleWindowLocal(_LocalFn):
    """Adaptive windowed circle query: the distance refine (paper
    Remark 2) is FUSED into the per-subinterval window gather
    (Q.circle_window_at), so this program receives pre-refined in-circle
    counts plus compacted ids and never materializes the (Q, C, S*cap)
    wx/wy coordinate planes. Exact when ok; the executor escalates /
    falls back to the full-refine _CircleCountLocal otherwise."""

    n_query_args = 4

    def __init__(self, index, cfg, backend, cap, cand,
                 materialize: bool):
        super().__init__(index, cfg, backend)
        self.cap = min(cap, index.n_pad)
        self.cand = cand
        self.materialize = materialize

    def __call__(self, parts, bounds, rects, klo, khi, circ, *, axis):
        del klo, khi   # recomputed per-candidate with clipping
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        qn = rects.shape[0]
        overlap = Q.rect_overlaps_box(rects, bounds)
        pids, valid, within = _top_candidates(overlap, self.cand)
        boxes = bounds[pids.reshape(-1)].reshape(qn, self.cand, 4)
        local = pids - off
        mine = valid & (local >= 0) & (local < p_loc)
        local = jnp.clip(local, 0, p_loc - 1)
        c = pids.shape[1]
        cc = max(1, self.cfg.scan_chunk_elems //
                 max(1, qn * (4 * self.cap + self.d_cap)))
        if self.materialize and cc < c:
            return self._chunked(parts, rects, circ, boxes, local, mine,
                                 within, cc, axis)
        cnts, vids, ok = Q.circle_window_at(
            parts, boxes, local, mine, rects, circ, self.spec,
            cap=self.cap, materialize=self.materialize, **self.kw)
        if self.d_cap:
            dcnts, dvids = Q.delta_window_at(parts, local, mine, rects,
                                             circ=circ)
            cnts = cnts + dcnts
            if self.materialize:
                vids = jnp.concatenate([vids, dvids], axis=-1)
        cnt = _psum(jnp.sum(cnts, axis=1), axis)
        okq = jnp.all(ok | ~mine, axis=1)
        if axis is not None:
            shards = jax.lax.axis_size(axis)
            okq = jax.lax.psum(okq.astype(jnp.int32), axis) == shards
        if not self.materialize:
            return cnt, okq & within
        vids = vids.reshape(qn, -1)
        if axis is not None:
            vids = jax.lax.all_gather(vids, axis, axis=1, tiled=True)
        vids, cap_ok = _keep_window(vids, cnt, self.cap)
        return cnt, vids, okq & within & cap_ok

    def _chunked(self, parts, rects, circ, boxes, local, mine, within,
                 cc: int, axis):
        """Streaming chunked compaction of the materializing gather
        (DESIGN.md §13): lax.scan over candidate-axis chunks keeps a
        front-compacted (Q, keep) id carry, so the materialized plane
        never exceeds O(keep + chunk) per query regardless of the
        sticky tier. Bitwise the monolithic path: compaction is order-
        preserving, the carry precedes each chunk in plane order, and
        the final width bound is the MONOLITHIC plane's bound (passed
        explicitly to _keep_window), so the kept ids — the first
        ``keep`` valid ids in candidate-major order — are identical.
        """
        qn = rects.shape[0]
        c = boxes.shape[1]
        w_loc = c * (4 * self.cap + self.d_cap)   # monolithic local width
        keep_loc = min(w_loc, max(self.cap * 8, 256))
        xs = _chunk_cands(cc, boxes, local, mine)

        def step(carry, ch):
            bx, lc, mn = ch
            cnts, vids, ok = Q.circle_window_at(
                parts, bx, lc, mn, rects, circ, self.spec,
                cap=self.cap, materialize=True, **self.kw)
            if self.d_cap:
                dcnts, dvids = Q.delta_window_at(parts, lc, mn, rects,
                                                 circ=circ)
                cnts = cnts + dcnts
                vids = jnp.concatenate([vids, dvids], axis=-1)
            both = jnp.concatenate([carry["v"], vids.reshape(qn, -1)],
                                   axis=1)
            return {"v": _compact_ids(both, keep_loc),
                    "c": carry["c"] + jnp.sum(cnts, axis=1),
                    "ok": carry["ok"] & jnp.all(ok | ~mn, axis=1)}, None

        init = {"v": jnp.full((qn, keep_loc), -1, jnp.int32),
                "c": jnp.zeros(qn, jnp.int32),
                "ok": jnp.ones(qn, bool)}
        out, _ = jax.lax.scan(step, init, xs)
        cnt = _psum(out["c"], axis)
        okq = out["ok"]
        kept = out["v"]
        if axis is not None:
            shards = jax.lax.axis_size(axis)
            okq = jax.lax.psum(okq.astype(jnp.int32), axis) == shards
            # per-shard carries are already compacted losslessly up to
            # keep_loc >= the final bound, so gathering them shard-major
            # and recompacting keeps exactly the ids the monolithic
            # all_gather + _keep_window kept
            kept = jax.lax.all_gather(kept, axis, axis=1, tiled=True)
            n_sh = kept.shape[1] // keep_loc
            keep_fin = min(n_sh * w_loc, max(self.cap * 8, 256))
        else:
            keep_fin = keep_loc
        kept, cap_ok = _keep_window(kept, cnt, self.cap, keep=keep_fin)
        return cnt, kept, okq & within & cap_ok


class _KnnExactLocal(_LocalFn):
    n_query_args = 2

    def __init__(self, index, cfg, backend, k):
        super().__init__(index, cfg, backend)
        self.k = k

    def __call__(self, parts, bounds, qx, qy, *, axis):
        qn = qx.shape[0]
        k = self.k
        bk = self.backend

        def chunk_fn(ch, carry):
            def one(part):
                # scan stage: (Q, W) per-partition candidates — W is the
                # full row for xla, the kernel's top-k for pallas; the
                # delta probe appends its (tiny) buffered candidates
                neg, vid = bk.knn_scan(part, qx, qy, k)
                if self.d_cap:
                    dneg, dvid = bk.delta_knn_scan(part, qx, qy)
                    neg = jnp.concatenate([neg, dneg], axis=1)
                    vid = jnp.concatenate([vid, dvid], axis=1)
                return neg, vid

            neg, vid = _for_parts(bk, one, (ch,))          # (C, Q, W)
            neg = jnp.swapaxes(neg, 0, 1).reshape(qn, -1)
            vid = jnp.swapaxes(vid, 0, 1).reshape(qn, -1)
            return bk.topk_merge(carry[0], carry[1], neg, vid, k)

        init = (jnp.full((qn, k), -3e38, jnp.float32),
                jnp.full((qn, k), -1, jnp.int32))
        neg, vid = _map_parts(chunk_fn, parts, self.cfg.part_chunk, init)
        if axis is not None:
            neg = jax.lax.all_gather(neg, axis, axis=1, tiled=True)
            vid = jax.lax.all_gather(vid, axis, axis=1, tiled=True)
            best_n, ix = jax.lax.top_k(neg, k)
            vid = jnp.take_along_axis(vid, ix, axis=1)
            neg = best_n
        return neg, vid


class _KnnPrunedLocal(_LocalFn):
    """Paper §4.3, query-centric: density-estimated radius, windowed
    range gather over the <=C nearest candidate partitions, geometric
    expansion until >=k verified in-circle candidates. Exact when ok;
    the executor falls back to the full scan per unresolved query."""

    n_query_args = 3

    def __init__(self, index, cfg, backend, k, spec, cand, cap):
        super().__init__(index, cfg, backend)
        self.k = k
        self.spec2 = spec
        self.cand = cand
        self.cap = min(cap, index.n_pad)

    def __call__(self, parts, bounds, qx, qy, r0, *, axis):
        qn = qx.shape[0]
        k = self.k
        cap = self.cap
        cand = self.cand
        bk = self.backend
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        boxd2 = Q.box_min_dist2(qx, qy, bounds)            # (Q, P_total)
        # C nearest partitions by box distance (static per query batch):
        # lax.top_k on negated distances — O(P*C) vs the full argsort,
        # identical order (top_k's lowest-index tie-break matches the
        # stable ascending sort)
        negd2, order = jax.lax.top_k(-boxd2, cand)
        cand_d2 = -negd2
        boxes = bounds[order.reshape(-1)].reshape(qn, cand, 4)
        local = order - off
        inshard = (local >= 0) & (local < p_loc)
        local = jnp.clip(local, 0, p_loc - 1)

        # streaming chunk size: per-chunk candidate plane is
        # (Q, cc * 4*cap); when the full (Q, cand * 4*cap) plane fits
        # the budget the monolithic single-top_k path compiles instead
        cc = max(1, self.cfg.scan_chunk_elems // max(1, qn * 4 * cap))

        def round_chunked(r, rects, active):
            """Streaming chunked top-k (DESIGN.md §13): fold candidate
            chunks into a running (Q, k) best set via bk.topk_merge.
            Bitwise the monolithic top_k: the carry precedes each chunk
            (global plane order preserved), init-carry slots are
            payload-identical to masked plane slots ((-3e38, -1)), and
            the delta plane is merged LAST, matching the monolithic
            [main | delta] concat order."""
            xs = _chunk_cands(cc, boxes, local, active)

            def step(carry, ch):
                bx, lc, ac = ch
                _, vids, ok, wx, wy = Q.range_window_at(
                    parts, bx, lc, ac, rects, self.spec2,
                    cap=cap, **self.kw)
                d2 = ((wx - qx[:, None, None]) ** 2 +
                      (wy - qy[:, None, None]) ** 2)
                inc = (vids >= 0) & (d2 <= (r * r)[:, None, None])
                negd = jnp.where(inc, -d2, -3e38).reshape(qn, -1)
                wv = jnp.where(inc, vids, -1).reshape(qn, -1)
                bn2, bv2 = bk.topk_merge(carry[0], carry[1], negd, wv, k)
                return (bn2, bv2,
                        carry[2] + jnp.sum(inc.astype(jnp.int32),
                                           axis=(1, 2)),
                        carry[3] & jnp.all(ok | ~ac, axis=1)), None

            init = (jnp.full((qn, k), -3e38, jnp.float32),
                    jnp.full((qn, k), -1, jnp.int32),
                    jnp.zeros(qn, jnp.int32), jnp.ones(qn, bool))
            (bn, bv, cnt, okl), _ = jax.lax.scan(step, init, xs)
            if self.d_cap:
                dcnts, dvids, dd2 = _delta_knn_candidates(
                    parts, local, active, qx, qy, r)
                bn, bv = bk.topk_merge(bn, bv, dd2, dvids, k)
                cnt = cnt + dcnts
            return bn, bv, cnt, okl

        def round_monolithic(r, rects, active):
            cnts, vids, ok, wx, wy = Q.range_window_at(
                parts, boxes, local, active, rects, self.spec2,
                cap=cap, **self.kw)
            d2 = ((wx - qx[:, None, None]) ** 2 +
                  (wy - qy[:, None, None]) ** 2)
            inc = (vids >= 0) & (d2 <= (r * r)[:, None, None])
            negd = jnp.where(inc, -d2, -3e38).reshape(qn, -1)
            wv = jnp.where(inc, vids, -1).reshape(qn, -1)
            cnt = jnp.sum(inc.astype(jnp.int32), axis=(1, 2))
            if self.d_cap:
                # buffered candidates of the same candidate partitions:
                # an insert is in-circle iff within r (coverage already
                # guarantees every in-range partition is a candidate)
                dcnts, dvids, dd2 = _delta_knn_candidates(
                    parts, local, active, qx, qy, r)
                negd = jnp.concatenate([negd, dd2], axis=1)
                wv = jnp.concatenate([wv, dvids], axis=1)
                cnt = cnt + dcnts
            bn, ix = jax.lax.top_k(negd, k)
            bv = jnp.take_along_axis(wv, ix, axis=1)
            return bn, bv, cnt, jnp.all(ok | ~active, axis=1)

        def gather_round(r):
            rects = jnp.stack([qx - r, qy - r, qx + r, qy + r], axis=-1)
            active = inshard & (cand_d2 <= (r * r)[:, None])
            # coverage: every partition within r must be a candidate
            covered = jnp.sum((boxd2 <= (r * r)[:, None]).astype(
                jnp.int32), axis=1) <= cand
            rnd = round_chunked if cc < cand else round_monolithic
            bn, bv, cnt, okl = rnd(r, rects, active)
            okq = okl & covered
            if axis is not None:
                bn_g = jax.lax.all_gather(bn, axis, axis=1, tiled=True)
                bv_g = jax.lax.all_gather(bv, axis, axis=1, tiled=True)
                bn, ix = jax.lax.top_k(bn_g, k)
                bv = jnp.take_along_axis(bv_g, ix, axis=1)
                cnt = jax.lax.psum(cnt, axis)
                okq = jax.lax.psum(okq.astype(jnp.int32), axis) == \
                    jax.lax.axis_size(axis)
            return bn, bv, okq, cnt

        def cond(state):
            rounds, r, done, *_ = state
            return (rounds < self.cfg.knn_max_rounds) & ~jnp.all(done)

        def body(state):
            rounds, r, done, bn, bv, okc = state
            bn2, bv2, ok2, cnt2 = gather_round(r)
            newly = (cnt2 >= k) & ok2 & ~done
            bn = jnp.where(newly[:, None], bn2, bn)
            bv = jnp.where(newly[:, None], bv2, bv)
            okc = okc | newly
            done2 = done | newly | ~ok2        # overflow -> fallback
            r2 = jnp.where(done2, r, r * 2.0)
            return rounds + 1, r2, done2, bn, bv, okc

        state = (jnp.int32(0), r0, jnp.zeros(qn, bool),
                 jnp.full((qn, k), -3e38, jnp.float32),
                 jnp.full((qn, k), -1, jnp.int32), jnp.zeros(qn, bool))
        _, _, done, bn, bv, okc = jax.lax.while_loop(cond, body, state)
        return bn, bv, okc & done


class _JoinLocal(_LocalFn):
    """Query-centric windowed broadcast join: per polygon, gather only
    the learned MBR interval of its <=C candidate partitions, refine by
    ray casting on those <= C*cap points."""

    n_query_args = 3

    def __init__(self, index, cfg, backend, cap, cand):
        super().__init__(index, cfg, backend)
        self.cap = min(cap, index.n_pad)
        self.cand = cand

    def __call__(self, parts, bounds, polys, n_edges, mbr_k, *, axis):
        pg = polys.shape[0]
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        mbrs = mbr_k[:, :4]
        overlap = Q.rect_overlaps_box(mbrs, bounds)
        pids, valid, within = _top_candidates(overlap, self.cand)
        boxes = bounds[pids.reshape(-1)].reshape(pg, self.cand, 4)
        local = pids - off
        mine = valid & (local >= 0) & (local < p_loc)
        local = jnp.clip(local, 0, p_loc - 1)
        cnts, vids, ok, wx, wy = Q.range_window_at(
            parts, boxes, local, mine, mbrs, self.spec, cap=self.cap,
            z_depth=3, **self.kw)
        if self.d_cap:
            dxw, dyw, dvw, live = Q.gather_delta(parts, local, mine)
            r = mbrs[:, None, None, :]
            inm = (live & (dxw >= r[..., 0]) & (dxw <= r[..., 2]) &
                   (dyw >= r[..., 1]) & (dyw <= r[..., 3]))
            wx = jnp.concatenate([wx, dxw], axis=-1)
            wy = jnp.concatenate([wy, dyw], axis=-1)
            vids = jnp.concatenate([vids, jnp.where(inm, dvw, -1)],
                                   axis=-1)

        def pip(poly, ne, wxq, wyq, vq):
            inside = Q.point_in_polygon(wxq.reshape(-1),
                                        wyq.reshape(-1), poly, ne)
            return jnp.sum(((vq.reshape(-1) >= 0) & inside
                            ).astype(jnp.int32))

        cnt = jax.vmap(pip)(polys, n_edges, wx, wy, vids)
        cnt = _psum(cnt, axis)
        okq = jnp.all(ok | ~mine, axis=1)
        if axis is not None:
            shards = jax.lax.axis_size(axis)
            okq = jax.lax.psum(okq.astype(jnp.int32), axis) == shards
        return cnt, okq & within


class _JoinFullLocal(_LocalFn):
    """Exact full-refine join (fallback / gridonly baseline)."""

    n_query_args = 3

    def __call__(self, parts, bounds, polys, n_edges, mbr_k, *, axis):
        pg = polys.shape[0]
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        bk = self.backend
        mbrs, klo, khi = mbr_k[:, :4], mbr_k[:, 4], mbr_k[:, 5]
        overlap = Q.rect_overlaps_box(mbrs, bounds)

        def chunk_fn(ch, carry):
            c = ch["count"].shape[0]
            base = carry["i"] * c + off

            def one(j, part):
                act = jax.lax.dynamic_index_in_dim(
                    overlap, base + j, axis=1, keepdims=False)
                s, e = bk.bounds(part, klo, khi, **self.kw)   # lookup
                cnt = bk.join_scan(part, polys, n_edges, mbrs,  # scan
                                   s, e, active=act)
                if self.d_cap:
                    cnt = cnt + bk.delta_join_scan(part, polys, n_edges,
                                                   mbrs, active=act)
                return cnt

            cnts = _for_parts(bk, one, (jnp.arange(c), ch))   # (C, PG)
            return {"i": carry["i"] + 1,
                    "acc": carry["acc"] + jnp.sum(cnts, axis=0)}

        out = _map_parts(chunk_fn, parts, self.cfg.part_chunk,
                         init={"i": jnp.int32(0),
                               "acc": jnp.zeros(pg, jnp.int32)})
        return _psum(out["acc"], axis)                        # merge


class _WindowNeedLocal(_LocalFn):
    """Wide-batch feasibility probe for the rect family (tag "p",
    DESIGN.md §13): per query, the candidate-partition count and the
    learned-interval DEMAND of its windowed gather — no data gathers,
    no refine, one tiny (Q, 3) replicated output the executor reads
    back ONCE per wide batch to assign per-tier buckets.

    Output (Q, 3) int32 columns: [ncand, need, needsum] where ncand is
    the number of overlapping partitions (feasible needs ncand <=
    cand_t), need the max per-subinterval window width over candidates
    (needs need <= cap_t), and needsum the total window mass (bounds
    the materialized plane for the keep-width check).
    """

    def __init__(self, index, cfg, backend, cand, rect_of,
                 n_query_args, z_depth: int = 2):
        super().__init__(index, cfg, backend)
        self.cand = cand
        self.rect_of = rect_of
        self.n_query_args = n_query_args
        self.z_depth = z_depth

    def __call__(self, parts, bounds, *q, axis):
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        rects = self.rect_of(*q)
        qn = rects.shape[0]
        overlap = Q.rect_overlaps_box(rects, bounds)     # replicated
        ncand = jnp.sum(overlap.astype(jnp.int32), axis=1)
        pids, valid, _ = _top_candidates(overlap, self.cand)
        boxes = bounds[pids.reshape(-1)].reshape(qn, self.cand, 4)
        local = pids - off
        mine = valid & (local >= 0) & (local < p_loc)
        local = jnp.clip(local, 0, p_loc - 1)
        width, total = Q.window_need_at(
            parts, boxes, local, mine, rects, self.spec,
            z_depth=self.z_depth, **self.kw)
        need = jnp.max(width, axis=1)
        tot = jnp.sum(total, axis=1)
        if axis is not None:
            need = jax.lax.pmax(need, axis)
            tot = jax.lax.psum(tot, axis)
        return jnp.stack([ncand, need, tot], axis=1)


class _KnnNeedLocal(_LocalFn):
    """kNN resolution-round probe (tag "p", DESIGN.md §13): for J=2
    doubling radii r0*2^j report per query [need, tot, nin] — the max
    window width demanded, the total candidate mass in the ring, and
    the number of partitions within the radius. The executor's
    bucketer predicts the resolving round from the first j whose mass
    reaches 2k; ALL kNN buckets still run at the same sticky tier
    (only padded widths differ), so the prediction affects cost, never
    values. J stays small on purpose: each probed round costs about as
    much as a real query round, and rows past round J-1 just join the
    hard bucket, whose fused ladder absorbs them on device.
    """

    n_query_args = 3
    J = 2

    def __init__(self, index, cfg, backend, cand):
        super().__init__(index, cfg, backend)
        self.cand = cand

    def __call__(self, parts, bounds, qx, qy, r0, *, axis):
        p_loc = parts["count"].shape[0]
        off = self._local_offset(axis, p_loc)
        qn = qx.shape[0]
        boxd2 = Q.box_min_dist2(qx, qy, bounds)          # (Q, P_total)
        negd2, order = jax.lax.top_k(-boxd2, self.cand)
        cand_d2 = -negd2
        boxes = bounds[order.reshape(-1)].reshape(qn, self.cand, 4)
        local = order - off
        inshard = (local >= 0) & (local < p_loc)
        local = jnp.clip(local, 0, p_loc - 1)
        cols = []
        for j in range(self.J):
            r = r0 * float(2 ** j)
            rects = jnp.stack([qx - r, qy - r, qx + r, qy + r], axis=-1)
            active = inshard & (cand_d2 <= (r * r)[:, None])
            width, total = Q.window_need_at(
                parts, boxes, local, active, rects, self.spec,
                **self.kw)
            need = jnp.max(width, axis=1)
            tot = jnp.sum(total, axis=1)
            if axis is not None:
                need = jax.lax.pmax(need, axis)
                tot = jax.lax.psum(tot, axis)
            nin = jnp.sum((boxd2 <= (r * r)[:, None]).astype(jnp.int32),
                          axis=1)
            cols.append(jnp.stack([need, tot, nin], axis=1))
        return jnp.stack(cols, axis=1)                   # (Q, J, 3)


class _KnnLadderLocal(_LocalFn):
    """On-device kNN escalation stage (DESIGN.md §13): used as a
    _CondFusedLocal fallback, so it only executes when some row
    overflowed the sticky cap. It re-runs the pruned rounds at the
    NEXT ladder cap and resolves any still-overflowing rows with the
    exact full scan, merged per row — a row's answer stays a pure
    function of the row: sticky result if ok there (outer merge),
    else escalated result if ok there, else exact. This keeps the
    expensive exact scan off batches whose stragglers merely need a
    wider window (the common wide-batch case), on every dispatch path
    identically (serial and tier-bucketed)."""
    n_query_args = 3

    def __init__(self, index, cfg, backend, primary, exact):
        super().__init__(index, cfg, backend)
        self.primary = primary       # pruned rounds at the escalated cap
        self.exact = exact

    def __call__(self, parts, bounds, qx, qy, r0, *, axis):
        neg, vid, ok = self.primary(parts, bounds, qx, qy, r0,
                                    axis=axis)

        def on_ok(_):
            return neg, vid

        def on_overflow(_):
            nege, vide = self.exact(parts, bounds, qx, qy, axis=axis)
            okc = ok[:, None]
            return (jnp.where(okc, neg, nege),
                    jnp.where(okc, vid, vide))

        return jax.lax.cond(jnp.all(ok), on_ok, on_overflow, None)


class _CondFusedLocal(_LocalFn):
    """Windowed primary + lax.cond exact fallback, fused in ONE program.

    The steady-state zero-host-sync path (DESIGN.md §9): the primary
    windowed attempt runs at the sticky (cap, cand); when any query
    overflowed, lax.cond dispatches the exact fallback ON DEVICE — the
    host never inspects ``ok``. The cond predicate is replicated (ok is
    psum-merged in the primary), so all shards take the same branch.

    primary(parts, bounds, *q)              -> pytree containing ok
    fallback(parts, bounds, *q[fb_args])    -> exact pytree
    merge_ok(pri) / merge_fb(pri, fb)       -> SAME output structure

    Returns (merged_result, ok): the replicated per-query ok flags ride
    along so the executor can stash them for a DEFERRED host check
    (Executor.maintain) without syncing on the dispatch path.
    """

    def __init__(self, index, cfg, backend, primary, fallback, fb_args,
                 get_ok, merge_ok, merge_fb):
        super().__init__(index, cfg, backend)
        self.primary = primary
        self.fallback = fallback
        self.fb_args = fb_args
        self.get_ok = get_ok
        self.merge_ok = merge_ok
        self.merge_fb = merge_fb
        self.n_query_args = primary.n_query_args

    def __call__(self, parts, bounds, *q, axis):
        # named scopes: a device op's metadata tells the two stages apart
        with jax.named_scope("window"):
            pri = self.primary(parts, bounds, *q, axis=axis)
            ok = self.get_ok(pri)

        def on_ok(_):
            return self.merge_ok(pri)

        def on_overflow(_):
            with jax.named_scope("fallback"):
                fb = self.fallback(parts, bounds,
                                   *[q[i] for i in self.fb_args],
                                   axis=axis)
            return self.merge_fb(pri, fb)

        return jax.lax.cond(jnp.all(ok), on_ok, on_overflow, None), ok
