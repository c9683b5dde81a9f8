"""Batched index mutations: insert/delete absorption + per-partition
spline re-fit (paper's update story; DESIGN.md §11).

The mutable-index contract (``build.LearnedSpatialIndex``):

  insert   append to the target partition's DELTA BUFFER (capacity-
           padded slots; host grows the capacity when a batch would
           overflow — a static-shape change, so the executor bumps
           ``shape_epoch`` and evicts stale executables).
  delete   tombstone in place: the sorted key row is untouched (the
           fitted spline stays valid), coordinates are poisoned to
           ``PAD_COORD`` and the vid to -1 — every coordinate-refine
           scan on either kernel backend then excludes the slot with no
           extra masking. Deletes of still-buffered inserts poison the
           delta slot the same way.
  refit    ``refit_partitions(idx, touched)``: merge delta + drop
           tombstones and re-run the error-bounded spline fit (the
           scalar-carry scan, ``build.fit_partitions``) over ONLY the
           touched partition rows; untouched partitions keep their
           arrays bit-for-bit. After a full refit the index answers
           every query bitwise-identically to a fresh ``build_index``
           on the surviving point set (tests/test_updates.py).

All entry points are host-driven (like ``build_index``): shapes become
static per (batch size, capacity) so the jitted kernels cache like
query executables; the executor routes them through its executable
cache via ``plan.exec_key``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as K
from repro.core.build import (LearnedSpatialIndex, PAD_COORD,
                              assign_partitions, fit_partitions,
                              probe_for)


def _pow2_at_least(n: int, floor: int) -> int:
    if max(n, floor) <= 0:
        return 0        # zero-capacity request: aux state only
    return max(floor, int(2 ** np.ceil(np.log2(max(n, 1)))))


@jax.jit
def row_max_runs(key_g, counts):
    """(P,) longest duplicate-key run per row (valid prefix only) —
    recovers the probe-sizing statistic for indexes that predate the
    mutable-state split (build_index stores it directly)."""
    p, n_pad = key_g.shape
    keys_f = K.keys_to_f32(key_g)
    idx = jnp.arange(n_pad, dtype=jnp.int32)
    valid = idx[None, :] < counts[:, None]
    prev = jnp.concatenate(
        [jnp.full((p, 1), -1.0, jnp.float32), keys_f[:, :-1]], axis=1)
    first = valid & (keys_f != prev)
    start = jnp.where(first, idx[None, :], -1)
    last_start = jax.lax.cummax(start, axis=1)
    runlen = jnp.where(valid, idx[None, :] - last_start + 1, 0)
    return jnp.max(runlen, axis=1).astype(jnp.int32)


def with_delta_capacity(index: LearnedSpatialIndex, cap: int,
                        floor: int = 64) -> LearnedSpatialIndex:
    """Grow the per-partition delta buffer to hold >= ``cap`` slots.

    Returns the index unchanged when it already fits; otherwise pads
    the delta planes to the next power of two and bumps ``shape_epoch``
    (compiled programs bake the capacity into their shapes).
    """
    cur = index.delta_cap
    if index.delta_key is not None and cur >= cap:
        return index
    new_cap = _pow2_at_least(cap, floor)
    p = index.num_partitions
    sentinel = jnp.uint32(index.key_spec.sentinel)

    def grow(a, fill, dtype):
        fresh = jnp.full((p, new_cap), fill, dtype)
        if a is None or a.shape[1] == 0:
            return fresh
        return fresh.at[:, :a.shape[1]].set(a)

    return dataclasses.replace(
        index,
        delta_key=grow(index.delta_key, sentinel, jnp.uint32),
        delta_x=grow(index.delta_x, PAD_COORD, jnp.float32),
        delta_y=grow(index.delta_y, PAD_COORD, jnp.float32),
        delta_vid=grow(index.delta_vid, -1, jnp.int32),
        delta_count=(index.delta_count if index.delta_count is not None
                     else jnp.zeros((p,), jnp.int32)),
        dead=(index.dead if index.dead is not None
              else jnp.zeros((p,), jnp.int32)),
        max_run=(index.max_run if index.max_run is not None
                 else row_max_runs(index.key, index.count)),
        refit_gen=(index.refit_gen if index.refit_gen is not None
                   else jnp.zeros((p,), jnp.int32)),
        shape_epoch=index.shape_epoch + 1,
    )


def shrink_delta_capacity(index: LearnedSpatialIndex,
                          cap: int) -> LearnedSpatialIndex:
    """Inverse of ``with_delta_capacity`` for burst-grown buffers:
    slice the delta planes back down after compaction has emptied
    them, so one skewed insert burst does not tax every later query
    (and the index footprint) forever. The caller must have re-fit
    first — every buffered entry must fit the new capacity."""
    new_cap = _pow2_at_least(cap, 0)
    if new_cap >= index.delta_cap:
        return index
    if int(jnp.max(index.delta_count)) > new_cap:
        raise ValueError("shrink below live delta occupancy")
    return dataclasses.replace(
        index,
        delta_key=index.delta_key[:, :new_cap],
        delta_x=index.delta_x[:, :new_cap],
        delta_y=index.delta_y[:, :new_cap],
        delta_vid=index.delta_vid[:, :new_cap],
        shape_epoch=index.shape_epoch + 1,
    )


def assign_insert(index: LearnedSpatialIndex, xs, ys):
    """Partition ids for new points: first-match grid, miss -> overflow
    (identical semantics to the build-time assignment)."""
    boxes = index.part_bounds[:index.overflow]
    pid = assign_partitions(xs, ys, boxes)
    # assign_partitions returns boxes.shape[0] (== overflow) for misses
    return pid


# ---------------------------------------------------------------------------
# jitted mutation kernels (shapes static per batch size / capacity)
# ---------------------------------------------------------------------------

def scatter_inserts(dkey, dx, dy, dvid, dcount, pid, key, xs, ys, vids):
    """Append a batch into the delta planes. Caller guarantees capacity.

    The within-batch slot of each insert is its rank among same-
    partition predecessors (O(B^2) mask — update batches are small
    relative to the data plane), preserving arrival (= vid) order so a
    later stable merge reproduces the fresh-build tie order.
    """
    b = pid.shape[0]
    same = pid[None, :] == pid[:, None]                     # (B, B)
    before = jnp.tril(same, -1)
    rank = jnp.sum(before.astype(jnp.int32), axis=1)
    slot = dcount[pid] + rank
    return (dkey.at[pid, slot].set(key),
            dx.at[pid, slot].set(xs),
            dy.at[pid, slot].set(ys),
            dvid.at[pid, slot].set(vids),
            dcount.at[pid].add(1))


def _row_hits(pids, m, n_rows: int):
    """(n_rows, W) bool: slot s of row r is hit when some batch entry b
    with ``pids[b] == r`` has ``m[b, s]``.

    A 0/1 contraction over the batch (sums of at most ``len(pids)``
    ones are exact in f32), not a scatter-max with repeated row ids. On
    a TPU v5e the scatter form dropped every delta-plane update of
    ``apply_deletes`` while the same scatter compiled alone was right;
    the cause is unconfirmed, so both planes use this form, which
    ``chip_smoke.py``'s delete check covers on the chip.
    """
    own = pids[None, :] == jnp.arange(n_rows, dtype=pids.dtype)[:, None]
    return jnp.dot(own.astype(jnp.bfloat16), m.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) > 0


def apply_deletes(xp, yp, vidp, count, dxp, dyp, dvidp, dcount, dead,
                  qx, qy, pid1, pid2):
    """Tombstone every live copy of each (x, y) in its two candidate
    partitions (first-match grid + overflow), main plane AND delta.

    Returns the poisoned planes, the updated per-partition dead count,
    and the total number of removed points (a (,) int32).
    """
    n_pad = xp.shape[1]
    pids = jnp.stack([pid1, pid2], axis=1).reshape(-1)      # (2B,)
    qx2 = jnp.repeat(qx, 2)
    qy2 = jnp.repeat(qy, 2)
    posn = jnp.arange(n_pad, dtype=jnp.int32)

    rows_x = xp[pids]
    rows_y = yp[pids]
    rows_v = vidp[pids]
    m = ((rows_x == qx2[:, None]) & (rows_y == qy2[:, None]) &
         (rows_v >= 0) & (posn[None, :] < count[pids][:, None]))
    hit = _row_hits(pids, m, xp.shape[0])
    newly = hit & (vidp >= 0)
    new_x = jnp.where(hit, PAD_COORD, xp)
    new_y = jnp.where(hit, PAD_COORD, yp)
    new_v = jnp.where(hit, -1, vidp)
    dead2 = dead + jnp.sum(newly.astype(jnp.int32), axis=1)
    removed = jnp.sum(newly.astype(jnp.int32))

    d_cap = dxp.shape[1]
    if d_cap:
        slot = jnp.arange(d_cap, dtype=jnp.int32)
        drx = dxp[pids]
        dry = dyp[pids]
        drv = dvidp[pids]
        dm = ((drx == qx2[:, None]) & (dry == qy2[:, None]) &
              (drv >= 0) & (slot[None, :] < dcount[pids][:, None]))
        dhit = _row_hits(pids, dm, dxp.shape[0])
        dnew = dhit & (dvidp >= 0)
        dxp = jnp.where(dhit, PAD_COORD, dxp)
        dyp = jnp.where(dhit, PAD_COORD, dyp)
        dvidp = jnp.where(dhit, -1, dvidp)
        removed = removed + jnp.sum(dnew.astype(jnp.int32))

    return new_x, new_y, new_v, dxp, dyp, dvidp, dead2, removed


@partial(jax.jit, static_argnames=("sentinel",))
def merge_rows(key_r, x_r, y_r, vid_r, count_r,
               dkey_r, dx_r, dy_r, dvid_r, dcount_r, *, sentinel: int):
    """Compact k gathered partition rows: drop tombstones, merge delta.

    A stable sort over (main row ++ delta row) keys — tombstones and
    padding mapped to the sentinel so they sink to the tail — yields
    rows sorted by (key asc, vid asc): the main row already holds equal
    keys in vid order and delta vids are strictly newer, so stability
    reproduces the fresh-build layout bitwise.
    """
    n_pad = key_r.shape[1]
    sent = jnp.uint32(sentinel)
    posn = jnp.arange(n_pad, dtype=jnp.int32)
    alive_m = (vid_r >= 0) & (posn[None, :] < count_r[:, None])
    keym = jnp.where(alive_m, key_r, sent)
    xm = jnp.where(alive_m, x_r, PAD_COORD)
    ym = jnp.where(alive_m, y_r, PAD_COORD)
    vm = jnp.where(alive_m, vid_r, -1)

    d_cap = dkey_r.shape[1]
    if d_cap:
        slot = jnp.arange(d_cap, dtype=jnp.int32)
        alive_d = (dvid_r >= 0) & (slot[None, :] < dcount_r[:, None])
        keyc = jnp.concatenate(
            [keym, jnp.where(alive_d, dkey_r, sent)], axis=1)
        xc = jnp.concatenate(
            [xm, jnp.where(alive_d, dx_r, PAD_COORD)], axis=1)
        yc = jnp.concatenate(
            [ym, jnp.where(alive_d, dy_r, PAD_COORD)], axis=1)
        vc = jnp.concatenate([vm, jnp.where(alive_d, dvid_r, -1)], axis=1)
        n_alive = (jnp.sum(alive_m.astype(jnp.int32), axis=1) +
                   jnp.sum(alive_d.astype(jnp.int32), axis=1))
    else:
        keyc, xc, yc, vc = keym, xm, ym, vm
        n_alive = jnp.sum(alive_m.astype(jnp.int32), axis=1)

    order = jnp.argsort(keyc, axis=1, stable=True)
    new_key = jnp.take_along_axis(keyc, order, axis=1)[:, :n_pad]
    new_x = jnp.take_along_axis(xc, order, axis=1)[:, :n_pad]
    new_y = jnp.take_along_axis(yc, order, axis=1)[:, :n_pad]
    new_v = jnp.take_along_axis(vc, order, axis=1)[:, :n_pad]
    return new_key, new_x, new_y, new_v, n_alive.astype(jnp.int32)


# ---------------------------------------------------------------------------
# per-partition re-fit (host entry point, like build_index)
# ---------------------------------------------------------------------------

def grow_n_pad(index: LearnedSpatialIndex,
               new_n_pad: int) -> LearnedSpatialIndex:
    """Widen the data plane (rare: merged rows outgrew n_pad)."""
    new_n_pad = int(np.ceil(new_n_pad / 128) * 128)
    if new_n_pad <= index.n_pad:
        return index
    p = index.num_partitions
    extra = new_n_pad - index.n_pad

    def widen(a, fill, dtype):
        pad = jnp.full((p, extra), fill, dtype)
        return jnp.concatenate([a, pad], axis=1)

    return dataclasses.replace(
        index,
        key=widen(index.key, jnp.uint32(index.key_spec.sentinel),
                  jnp.uint32),
        x=widen(index.x, PAD_COORD, jnp.float32),
        y=widen(index.y, PAD_COORD, jnp.float32),
        vid=widen(index.vid, -1, jnp.int32),
        shape_epoch=index.shape_epoch + 1,
    )


def dirty_partitions(index: LearnedSpatialIndex) -> np.ndarray:
    """Partition ids with buffered inserts or tombstones (host view)."""
    if index.delta_count is None:
        return np.zeros((0,), np.int32)
    dirty = (np.asarray(index.delta_count) > 0)
    if index.dead is not None:
        dirty |= np.asarray(index.dead) > 0
    return np.nonzero(dirty)[0].astype(np.int32)


def delta_occupancy(index: LearnedSpatialIndex) -> np.ndarray:
    """Per-partition dirtiness fraction: (buffered + tombstoned) over
    live points — the executor's compaction/re-fit trigger."""
    p = index.num_partitions
    if index.delta_count is None:
        return np.zeros((p,), np.float64)
    dcount = np.asarray(index.delta_count, np.int64)
    dead = (np.asarray(index.dead, np.int64) if index.dead is not None
            else np.zeros((p,), np.int64))
    count = np.asarray(index.count, np.int64)
    live = np.maximum(count - dead + dcount, 1)
    return (dcount + dead) / live


def refit_partitions(index: LearnedSpatialIndex, touched):
    """Merge delta + drop tombstones + re-fit the spline for ONLY the
    ``touched`` partitions. Bumps ``epoch`` and the touched rows'
    ``refit_gen``; untouched partition arrays are preserved bitwise.

    Returns the new index. Capacity growth (n_pad, knot width, probe)
    happens here when the merged rows outgrow the current statics, each
    bumping ``shape_epoch``.
    """
    touched = np.unique(np.asarray(touched, np.int32))
    if touched.size == 0:
        return index
    if index.delta_key is None:
        index = with_delta_capacity(index, 0, floor=0)
    t = jnp.asarray(touched)

    # -- host sizing: merged rows must fit the data plane -------------
    dcountv = np.asarray(index.delta_count)
    deadv = np.asarray(index.dead)
    alive_delta = np.asarray(
        jnp.sum((index.delta_vid >= 0).astype(jnp.int32), axis=1)
        if index.delta_cap else jnp.zeros_like(index.delta_count))
    new_counts = (np.asarray(index.count) - deadv + alive_delta)[touched]
    if new_counts.max(initial=0) > index.n_pad:
        index = grow_n_pad(index, int(new_counts.max()))

    key_r, x_r, y_r, vid_r, cnt = merge_rows(
        index.key[t], index.x[t], index.y[t], index.vid[t],
        index.count[t], index.delta_key[t], index.delta_x[t],
        index.delta_y[t], index.delta_vid[t], index.delta_count[t],
        sentinel=index.key_spec.sentinel)

    # -- re-fit: the same scalar-carry scan the build uses ------------
    m = index.knot_keys.shape[1]
    while True:
        fit = fit_partitions(key_r, cnt, eps=index.eps, m_pad=m,
                             radix_bits=index.radix_bits)
        if not bool(jnp.any(fit["overflow"])):
            break
        if m >= index.n_pad:
            raise RuntimeError("spline knot capacity exceeded at n_pad")
        m = min(m * 2, index.n_pad)
    if m != index.knot_keys.shape[1]:
        extra = m - index.knot_keys.shape[1]
        p = index.num_partitions
        index = dataclasses.replace(
            index,
            knot_keys=jnp.concatenate(
                [index.knot_keys,
                 jnp.full((p, extra), 3.4e38, jnp.float32)], axis=1),
            knot_pos=jnp.concatenate(
                [index.knot_pos, jnp.zeros((p, extra), jnp.float32)],
                axis=1),
            shape_epoch=index.shape_epoch + 1)

    # -- scatter the compacted rows + fresh fit back ------------------
    sentinel = jnp.uint32(index.key_spec.sentinel)
    d_cap = index.delta_cap
    new = dataclasses.replace(
        index,
        key=index.key.at[t].set(key_r),
        x=index.x.at[t].set(x_r),
        y=index.y.at[t].set(y_r),
        vid=index.vid.at[t].set(vid_r),
        count=index.count.at[t].set(cnt),
        knot_keys=index.knot_keys.at[t].set(fit["knot_keys"]),
        knot_pos=index.knot_pos.at[t].set(fit["knot_pos"]),
        n_knots=index.n_knots.at[t].set(fit["n_knots"]),
        radix_table=index.radix_table.at[t].set(fit["radix_table"]),
        radix_kmin=index.radix_kmin.at[t].set(fit["radix_kmin"]),
        radix_scale=index.radix_scale.at[t].set(fit["radix_scale"]),
        delta_key=index.delta_key.at[t].set(
            jnp.full((t.shape[0], d_cap), sentinel, jnp.uint32)),
        delta_x=index.delta_x.at[t].set(
            jnp.full((t.shape[0], d_cap), PAD_COORD, jnp.float32)),
        delta_y=index.delta_y.at[t].set(
            jnp.full((t.shape[0], d_cap), PAD_COORD, jnp.float32)),
        delta_vid=index.delta_vid.at[t].set(
            jnp.full((t.shape[0], d_cap), -1, jnp.int32)),
        delta_count=index.delta_count.at[t].set(0),
        dead=index.dead.at[t].set(0),
        max_run=index.max_run.at[t].set(fit["max_run"].astype(jnp.int32))
        if index.max_run is not None
        else None,
        refit_gen=index.refit_gen.at[t].add(1),
        epoch=index.epoch + 1,
    )

    # -- probe refresh: duplicate runs may have grown ------------------
    # Same sizing rule the build uses (probe_for over the GLOBAL max
    # run), so a fully-refit index carries exactly the probe a fresh
    # build of the surviving points would: inserts that lengthen a
    # duplicate run widen the window (a static shape change — exact
    # results are probe-independent, so only compile caches notice).
    if new.max_run is not None:
        need = probe_for(new.eps, int(jnp.max(new.max_run)), new.n_pad)
        if need > new.probe:
            new = dataclasses.replace(
                new, probe=need, shape_epoch=new.shape_epoch + 1)
    return new


def verify_eps(index: LearnedSpatialIndex, pid: int) -> float:
    """Max |S(key) - first_occurrence_rank| over one partition's keys.

    The greedy corridor guarantees <= 2*eps at interpolation (a
    corridor restart anchors at the PREVIOUS data point, itself up to
    eps off the fitted line — the same bound a fresh build exhibits).
    Host-side diagnostic; tests re-verify it per touched partition
    after every re-fit, pinning that updates never degrade the fit
    below what ``build_index`` would produce."""
    from repro.core import spline as S
    cnt = int(index.count[pid])
    if cnt == 0:
        return 0.0
    keys_f = K.keys_to_f32(index.key[pid, :cnt])
    first = np.concatenate([[True], np.asarray(keys_f[1:] != keys_f[:-1])])
    pred = S.spline_predict(index.knot_keys[pid], index.knot_pos[pid],
                            index.n_knots[pid], keys_f)
    pos = np.arange(cnt, dtype=np.float32)
    return float(np.max(np.abs(np.asarray(pred)[first] - pos[first])))
