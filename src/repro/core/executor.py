"""Unified adaptive query executor (DESIGN.md §9).

ONE place owns what the six SpatialEngine methods used to hand-roll
separately:

  (a) compilation — jit + shard_map wrapping of the local SPMD programs
      (core/local_ops.py), with an executable cache that EVICTS a
      spec's superseded cap-variants (keeps the sticky tier + the
      initial-config tier) so escalation cannot leak compiled programs
      in long-running serving;
  (b) the adaptive-cap policy — sticky last-successful (cap, cand) per
      ``spec.sticky_key()``, geometric escalation schedule, and an
      exactness-preserving final fallback;
  (c) dispatch — ``run(spec, *args)`` / ``run_batch([...])`` so mixed
      workloads enter through one door.

Two execution modes for adaptive specs:

  strict=True   the backward-compatible facade mode: host-checked
                escalation loop, identical control flow (and bitwise
                results) to the pre-plan engine. One host sync per
                attempt.
  strict=False  the serving mode: once a sticky (cap, cand) exists the
                compiled program FUSES the windowed attempt with a
                lax.cond exact fallback, so a steady-state ``run`` with
                a sticky hit performs ZERO host-side bool(jnp.all(...))
                syncs while counts stay exact. The ``ok`` flags of
                materializing specs still report window completeness.

Every QUERY-path host synchronization goes through ``_all_ok`` and is
counted in ``host_syncs`` — asserted by the dispatch-count test.
Mutations (InsertBatch/DeleteBatch/Refit, DESIGN.md §11) are
host-driven like ``build_index`` and block deliberately; they never
ride the zero-sync steady path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
import time
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import export as _jax_export
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import keys as K
from repro.core import mutate as M
from repro.core import obs
from repro.core import prep as R
from repro.core.backends import resolve_backend
from repro.core.build import LearnedSpatialIndex
from repro.core.plan import (CircleQuery, DeleteBatch, EngineConfig,
                             InsertBatch, Knn, PointQuery, QuerySpec,
                             RangeCount, RangeQuery, Refit, SpatialJoin,
                             exec_key)
from repro.core import local_ops as L
from repro.core.local_ops import _axes


@dataclasses.dataclass
class _AdaptiveOp:
    """Descriptor binding one query family to the shared policy loop."""
    base: Tuple                       # sticky/cache key
    initial: Tuple[int, int]          # starting (cap, cand)
    window: Callable                  # (cap, cand) -> local program
    get_ok: Callable                  # raw result -> ok array
    finalize: Callable                # raw result -> public result
    escalate: Callable                # (cap, cand) -> (cap, cand)
    maxed: Callable                   # (cap, cand) -> bool
    sticky_on_maxed: bool             # seed semantics differ per op
    fallback: Optional[Callable]      # (pargs, raw) -> exact result
    fused: Optional[Callable]         # (cap, cand) -> fused local program
    post: Callable = lambda r: r      # fused/public result adapter
    demote: Optional[Callable] = None  # (cap, cand) -> lower tier
    # -- wide-batch tier bucketing (DESIGN.md §13) --------------------
    probe: Optional[Callable] = None   # cand -> feasibility probe program
    feasible: Optional[Callable] = None  # (probe_np, cap, cand) -> mask
    owidth: Optional[Callable] = None  # (cap, cand) -> materialized width
    bucketer: Optional[Callable] = None  # (probe_np, cap, cand) -> ranks


def _struct(a):
    return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)


def program_name(key, sig=None) -> str:
    """``lilis_<base>_<tag>[_<cap>x<cand>][_q][_w<width>]``: the name a
    compiled program carries (as ``jit_<name>``) on a trace's ``XLA
    Modules`` line. A pure function of the exec key (plan.exec_key; the
    shape epoch left out) and the signature's width, the leading
    dimension of the first query argument; update programs ("u") carry
    their batch size in the variant instead."""
    _bk, qshard, base, tag, variant, _epoch = key
    parts = ["lilis", *(str(b).lower() for b in base), tag]
    if variant:
        parts.append("x".join(str(v) for v in variant))
    if qshard:
        parts.append("q")
    if sig and sig[0][0] and tag != "u":
        parts.append(f"w{sig[0][0][0]}")
    return re.sub(r"[^0-9A-Za-z_]", "_", "_".join(parts))


def _named(fn, name: str):
    """``fn`` under ``name``, so ``jax.jit`` names its program
    ``jit_<name>`` instead of after ``fn``."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


class _Dispatch:
    """Per-exec_key compiling dispatcher (DESIGN.md §14).

    Wraps one traceable local program and realizes an AOT-compiled
    executable per argument SIGNATURE — exported programs are
    shape-specialized, so the signature is part of a compiled program's
    identity. Realization prefers the persistent disk cache (a
    ``jax.export`` artifact skips the Python trace + lowering; the XLA
    persistent cache underneath skips the machine-code compile), falls
    back to a fresh trace+compile on ANY failure, and is safe to run
    OUTSIDE the executor lock: the async precompile worker warms
    signatures concurrently with live dispatch, and racing
    realizations of one signature are idempotent (both compute the
    identical program; last write wins).
    """

    __slots__ = ("ex", "key", "fn", "prefix", "span", "_fns", "_names",
                 "_uncacheable")

    def __init__(self, ex, key, fn, prefix: int,
                 span: str = "lilis.exec.launch"):
        self.ex = ex
        self.key = key
        self.fn = fn              # traceable (not yet traced) program
        self.prefix = prefix      # 2: called as fn(parts, bounds, *q)
                                  # (index state, excluded from the
                                  # sig — it is keyed by shape epoch);
                                  # 0: raw-args update kernels
        self.span = span          # the span around each launch
        self._fns = {}            # args sig -> AOT-compiled executable
        self._names = {}          # args sig -> program_name
        self._uncacheable = False

    @staticmethod
    def sig_of(args) -> Tuple:
        return tuple((tuple(int(d) for d in a.shape), str(a.dtype))
                     for a in jax.tree_util.tree_leaves(args))

    def sigs(self) -> list:
        """Signatures realized so far (manifest recording)."""
        return sorted(self._fns)

    def _realize(self, sig, abs_args):
        ex = self.ex
        name = program_name(self.key, sig)
        background = threading.current_thread() is ex._pc_thread
        t0 = time.perf_counter()
        with obs.span("lilis.exec.compile", program=name,
                      thread="precompile" if background else "inline") \
                as sp:
            compiled = self._compile_sig(sig, abs_args, name, sp)
        ms = (time.perf_counter() - t0) * 1e3
        with ex._compile_lock:
            if background:
                ex.compile_ms_background += ms
            else:
                ex.compile_ms_inline += ms
        self._names[sig] = name
        self._fns[sig] = compiled
        return compiled

    def _compile_sig(self, sig, abs_args, name, sp):
        """The executable for one signature: the export's call, or the
        fresh program, jitted under its ``program_name``. The program
        is exported (and stored) unnamed, so the artifacts stay as
        they were."""
        ex = self.ex
        compiled = None
        disk = ex._disk if not self._uncacheable else None
        fp = None
        if disk is not None:
            # epoch stripped from the fingerprint: the context carries
            # the actual static shapes (delta capacity, n_pad, ...) so
            # equal-shape states reuse entries across histories
            from repro.core.plan import cache_fingerprint
            fp = cache_fingerprint(ex._fp_context(), self.key[:5], sig)
            data = disk.load(fp)
            if data is not None:
                try:
                    exp = _jax_export.deserialize(bytearray(data))
                    compiled = jax.jit(_named(exp.call, name)) \
                        .lower(*abs_args).compile()
                except Exception:
                    # corrupt / undeserializable entry: drop it and
                    # recompile fresh — re-bill the hit as a miss
                    disk.invalidate(fp)
                    with disk._lock:
                        disk.hits -= 1
                        disk.misses += 1
                    compiled = None
        sp.set(disk="off" if disk is None
               else "hit" if compiled is not None else "miss")
        if compiled is None:
            if disk is not None:
                try:
                    exp = _jax_export.export(jax.jit(self.fn))(*abs_args)
                    disk.store(fp, exp.serialize(),
                               meta={"key": repr(self.key[:5])})
                    compiled = jax.jit(_named(exp.call, name)) \
                        .lower(*abs_args).compile()
                except Exception:
                    # program not exportable with this jax: remember
                    # and stop paying the export attempt per sig
                    self._uncacheable = True
                    compiled = None
            if compiled is None:
                compiled = jax.jit(_named(self.fn, name)) \
                    .lower(*abs_args).compile()
        return compiled

    def __call__(self, *args):
        sig = self.sig_of(args[self.prefix:])
        fn = self._fns.get(sig)
        if fn is None:
            fn = self._realize(
                sig, jax.tree_util.tree_map(_struct, args))
        with obs.span(self.span, program=self._names[sig]):
            return fn(*args)

    def warm(self, sig: Tuple) -> bool:
        """Realize (trace + compile) one signature WITHOUT executing —
        the async precompile worker and manifest prewarm entry point.
        Returns True when work actually happened."""
        sig = tuple((tuple(s), str(d)) for s, d in sig)
        if sig in self._fns:
            return False
        pre = ()
        if self.prefix:
            pre = (jax.tree_util.tree_map(_struct, self.ex.parts),
                   _struct(self.ex.bounds))
        tail = tuple(jax.ShapeDtypeStruct(s, np.dtype(d))
                     for s, d in sig)
        self._realize(sig, pre + tail)
        return True


class Executor:
    """Compiles and runs QuerySpecs against one LearnedSpatialIndex.

    mesh=None -> single-device; otherwise partitions are sharded over
    ``part_axis``. With ``query_axis`` set, batches of at least
    ``EngineConfig.query_shard_threshold`` queries additionally shard
    over that mesh axis (query args padded/unpadded in-program; each
    query-row subgroup runs the partition collectives independently).
    Local programs pull their lookup/scan stages from the kernel
    backend selected by ``EngineConfig.backend`` (core/backends.py:
    XLA reference or the Pallas TPU kernels).
    """

    def __init__(self, index: LearnedSpatialIndex,
                 mesh: Optional[Mesh] = None, part_axis: str = "data",
                 query_axis: Optional[str] = None,
                 config: Optional[EngineConfig] = None):
        self.mesh = mesh
        self.part_axis = part_axis
        self.query_axis = query_axis
        # None sentinel, not a default EngineConfig() in the signature:
        # a signature default is evaluated ONCE at import and then
        # shared by every caller
        self.cfg = config if config is not None else EngineConfig()
        self.backend = resolve_backend(self.cfg.backend)
        if query_axis is not None:
            if mesh is None:
                raise ValueError("query_axis requires a mesh")
            bad = set(_axes(query_axis)) & set(_axes(part_axis))
            if bad:
                raise ValueError(
                    f"query_axis overlaps part_axis: {sorted(bad)}")
        if mesh is not None:
            shards = int(np.prod([mesh.shape[a] for a in _axes(part_axis)]))
            index = L.pad_partitions(index, shards * self.cfg.part_chunk)
        else:
            index = L.pad_partitions(index, self.cfg.part_chunk)
        self.index = index
        self.parts = L.part_arrays(index)
        self.bounds = index.part_bounds          # (P, 4) replicated
        self.spec = index.key_spec
        b = index.key_spec.bounds
        self.area = max((b[2] - b[0]) * (b[3] - b[1]), 1e-30)
        self._recount()
        self._psharding = None
        if mesh is not None:
            self._psharding = NamedSharding(mesh, P(_axes(part_axis)))
            self.parts = jax.device_put(self.parts, self._psharding)
            self.bounds = jax.device_put(
                self.bounds, NamedSharding(mesh, P()))
        # -- mutable-index state (DESIGN.md §11) -------------------------
        nxt = int(jnp.max(index.vid))
        if index.delta_vid is not None and index.delta_cap:
            nxt = max(nxt, int(jnp.max(index.delta_vid)))
        self.next_vid = nxt + 1
        self._refit_pending = set()  # partition ids awaiting compaction
        self.updates = 0      # applied insert/delete batches
        self.refits = 0       # refit_partitions invocations
        self._cache = {}      # exec_key -> compiled callable
        self._sticky = {}     # sticky_key -> last-successful (cap, cand)
        self._peak = {}       # sticky_key -> highest tier it has held
        self._initial = {}    # sticky_key -> initial-config (cap, cand)
        self._pending = {}    # sticky_key -> (tier, ok device array)
        self._escalators = {}  # sticky_key -> the op's escalate rule
        self._demoters = {}   # sticky_key -> the op's demote rule
        self._ok_streak = {}  # sticky_key -> consecutive clean checks
        self._demoted_from = {}   # sticky_key -> tier last demoted FROM
        self._demote_backoff = {}  # sticky_key -> streak multiplier
        self.host_syncs = 0   # counted bool(jnp.all(...)) blocking reads
        self.probe_syncs = 0  # wide-batch bucketing probe readbacks
        self.dispatches = 0   # compiled-program launches
        self.prep_launches = 0  # read prep-program launches (not
                                # dispatches: one per read run())
        # -- compile pipeline (DESIGN.md §14) ----------------------------
        # wall-clock ms spent realizing executables (trace+lower+compile,
        # or disk load+compile on a hit), on the calling (serving)
        # thread and on the precompile thread; both under _compile_lock
        self.compile_ms_inline = 0.0
        self.compile_ms_background = 0.0
        self._compile_lock = threading.Lock()
        self.async_compiles = 0      # executables realized by the
                                     # background precompile worker
        self._disk = None            # CompileCache when configured
        self._fpc = None             # (shape_epoch, fingerprint context)
        if self.cfg.compile_cache_dir and mesh is None:
            # the export layer engages on unmeshed executors (the
            # serving shape); meshed programs keep plain jit — their
            # in_shardings don't survive an abstract-args lowering
            from repro.core.compile_cache import (CompileCache,
                                                  process_context)
            self._disk = CompileCache(self.cfg.compile_cache_dir,
                                      self.cfg.compile_cache_bytes,
                                      context=process_context())
        # async precompile worker (started by serve; off by default)
        self._pc_thread = None
        self._pc_stop = None
        self._pc_q = None
        self._pc_seen = set()
        self._pc_done = set()
        # serializes run/maintain/refit so the serve scheduler's worker
        # thread and direct session.submit callers can share one
        # executor (executable cache, sticky state, index swap) safely;
        # reentrant because run(Refit) and maintain() call refit()
        self._lock = threading.RLock()

    # -- compilation + executable cache ----------------------------------

    def _key(self, base, tag="x", variant=None, qshard=False):
        """Canonical cache key (plan.exec_key): backend + qshard +
        shape-epoch aware (compiled programs bake the index's static
        shapes; superseded shape epochs are swept by _evict_stale)."""
        return exec_key(self.backend.name, base, tag, variant,
                        qshard=qshard, epoch=self.index.shape_epoch)

    def _query_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a]
                            for a in _axes(self.query_axis)]))

    def _use_qshard(self, qlen: int) -> bool:
        """Shard this batch over the query mesh axis? (DESIGN.md §10)"""
        return (self.mesh is not None and self.query_axis is not None
                and qlen >= self.cfg.query_shard_threshold)

    def _pad_queries(self, fn):
        """Pad query args to a query-axis multiple; unpad all outputs.

        Traced inside the jitted program. Pads by repeating row 0 — a
        real, resolvable query — so padding can never trip the adaptive
        ok flags that fused programs stash for maintain(). Every program
        output leaf carries the query batch as its leading axis and
        comes back replicated (``_compile`` gathers the query shards),
        so unpadding is one tree_map of static slices.
        """
        qsize = self._query_shards()

        def wrapped(parts, bounds, *q):
            qlen = q[0].shape[0]
            pad = (-qlen) % qsize
            if pad:
                q = tuple(jnp.concatenate(
                    [a, jnp.repeat(a[:1], pad, axis=0)], axis=0)
                    for a in q)
            out = fn(parts, bounds, *q)
            if pad:
                out = jax.tree_util.tree_map(lambda a: a[:qlen], out)
            return out

        return wrapped

    def _compile(self, key, make_fn, qshard: bool = False):
        """jit (and shard_map when meshed) a local program, cached.

        qshard=True compiles the query-axis-sharded wrapping: query
        args shard over ``query_axis`` (partitions still shard over
        ``part_axis``; collectives inside the program stay scoped to the
        part axes, so each query-row subgroup reduces independently).
        Each shard's outputs are gathered over the query axis, and the
        pad/unpad is part of the same jitted program.

        A read's prep program (base ``("prep", ...)``, core/prep.py)
        reads no partition shard: a plain jit on a mesh too, its launch
        spanned as ``lilis.exec.prep``.
        """
        if key in self._cache:
            return self._cache[key]
        fn = make_fn()
        if key[2][0] == "prep":
            out = (_Dispatch(self, key, fn, prefix=2,
                             span="lilis.exec.prep")
                   if self.mesh is None
                   else jax.jit(_named(fn, program_name(key))))
        elif self.mesh is None:
            # compiling dispatcher: per-signature AOT executables with
            # the persistent disk cache underneath (DESIGN.md §14) —
            # behaviorally a jit (bitwise-identical programs), plus
            # .warm() for the precompile worker / manifest prewarm
            out = _Dispatch(self, key, partial(fn, axis=None), prefix=2)
        else:
            paxes = _axes(self.part_axis)
            local = partial(fn, axis=paxes)
            body = local
            if qshard:
                qaxes = _axes(self.query_axis)
                in_specs = ((P(paxes), P()) +
                            (P(qaxes),) * fn.n_query_args)

                def body(*a):
                    return jax.tree_util.tree_map(
                        lambda o: jax.lax.all_gather(o, qaxes, axis=0,
                                                     tiled=True),
                        local(*a))
            else:
                in_specs = (P(paxes),) + (P(),) * (fn.n_query_args + 1)
            wrapped = jax.shard_map(body, mesh=self.mesh,
                                    in_specs=in_specs, out_specs=P(),
                                    check_vma=False)
            out = jax.jit(_named(self._pad_queries(wrapped) if qshard
                                 else wrapped, program_name(key)))
        self._cache[key] = out
        return out

    def _fp_context(self) -> dict:
        """Fingerprint context for the disk cache: process invariants +
        every index static a compiled program bakes. Cached per shape
        epoch (the statics only move when the epoch does)."""
        se = self.index.shape_epoch
        if self._fpc is None or self._fpc[0] != se:
            idx = self.index
            cfg = dataclasses.asdict(self.cfg)
            for k in ("compile_cache_dir", "compile_cache_bytes",
                      "serve_async_precompile"):
                cfg.pop(k, None)     # cache plumbing, not program shape
            parts_sig = sorted((k, tuple(int(d) for d in v.shape),
                                str(v.dtype))
                               for k, v in self.parts.items())
            ctx = dict(self._disk.context, cfg=cfg,
                       key_spec=dataclasses.asdict(idx.key_spec),
                       num_partitions=int(idx.num_partitions),
                       n_pad=int(idx.n_pad),
                       delta_cap=int(idx.delta_cap or 0),
                       overflow=int(idx.overflow),
                       parts_sig=parts_sig)
            self._fpc = (se, ctx)
        return self._fpc[1]

    def _call(self, fn, *args):
        self.dispatches += 1
        if isinstance(fn, _Dispatch):    # spans its own launch
            return fn(self.parts, self.bounds, *args)
        with obs.span("lilis.exec.launch"):
            return fn(self.parts, self.bounds, *args)

    def _call_rows(self, fn, args, cw: int):
        """Dispatch ``args`` through ``fn`` in ``cw``-row slices and
        concatenate the outputs. Per-row outputs are pure f(row, tier),
        so the concat is bitwise the single-dispatch result. A short
        tail slice pads with its own row 0 (a real query) up to ``cw``
        and un-pads, keeping the executable count at one."""
        width = args[0].shape[0]
        if cw >= width:
            return self._call(fn, *args)
        outs = []
        for s in range(0, width, cw):
            cargs = tuple(a[s:s + cw] for a in args)
            tail = cw - cargs[0].shape[0]
            if tail > 0:
                cargs = tuple(jnp.concatenate(
                    [a, jnp.repeat(a[:1], tail, axis=0)], axis=0)
                    for a in cargs)
            out = self._call(fn, *cargs)
            if tail > 0:
                out = jax.tree_util.tree_map(lambda a: a[:-tail], out)
            outs.append(out)
        return jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(leaves, axis=0), *outs)

    def _mem_rows(self, tier) -> int:
        """Max rows per dispatch at ``tier`` so the windowed candidate
        plane — rows x cand x 4 subintervals x cap elements — stays
        within ``scan_chunk_elems``. Without it a wide batch of large
        queries asks for more device memory than the chip has (on a
        10^7-point index a 256-row RangeQuery batch at tier (4096, 64)
        wanted 15.25 GB of a v5e's 16 GB). Power of two, at least 1."""
        per_row = 4 * max(1, int(tier[0])) * max(1, int(tier[1]))
        rows = max(1, self.cfg.scan_chunk_elems // per_row)
        return 1 << (rows.bit_length() - 1)

    def _all_ok(self, ok) -> bool:
        """The ONLY host-blocking read on the QUERY path (counted)."""
        self.host_syncs += 1
        with obs.span("lilis.exec.sync"):
            return bool(jnp.all(ok))

    def _set_sticky(self, base, variant):
        old = self._sticky.get(base)
        self._sticky[base] = variant
        if old != variant:
            self._peak[base] = max(t for t in (old, variant,
                                               self._peak.get(base))
                                   if t is not None)
            # a new tier starts its demotion clock from zero — clean
            # checks at the PREVIOUS tier must not count toward
            # demote_after at this one
            self._ok_streak[base] = 0
            self._evict(base)
            if self._pc_thread is not None:
                # hand the adjacent ladder tiers to the precompile
                # worker so the NEXT move finds a warm executable
                self._pc_neighbors(base)

    def _evict(self, base):
        """Drop superseded cap-variants: keep sticky + initial tier.

        Escalated ``(cap, cand)`` executables for smaller caps are dead
        weight once a larger sticky tier is established — without this,
        long-running serving leaks one compiled program per escalation
        step (the seed engine's ``_jits`` bug). Sweeps both the plain
        and query-sharded wrappings (plan.exec_key layout).

        FUSED programs additionally keep every ladder tier from the
        initial tier up to the one above the highest tier the base has
        held (O(log) of them): wide-batch bucketed dispatch (DESIGN.md
        §13) runs light buckets at below-sticky tiers on every batch,
        and a demotion that the next overflow undoes must find the tiers
        it left still compiled — sweeping them recompiled a whole ladder
        of programs, at every warm batch width, inside a serving window
        (PERF.md §6). Probe ("p") executables are
        tier-independent and only swept by shape epoch (_evict_stale).
        """
        sticky = self._sticky.get(base)
        initial = self._initial.get(base)
        keep_w = {sticky, initial}
        keep_f = set(keep_w)
        esc = self._escalators.get(base)
        dem = self._demoters.get(base)
        if sticky is not None:
            # the async precompile worker warms the tier above sticky
            # and the demotion target ahead of need — don't sweep them
            if esc is not None:
                keep_f.add(esc(*sticky))
            if dem is not None:
                keep_f.add(dem(*sticky))
        if esc is not None and sticky is not None and initial is not None:
            top = esc(*max(sticky, self._peak.get(base, sticky)))
            cur = initial
            for _ in range(64):          # ladders are O(log) long
                keep_f.add(cur)
                if cur == top:
                    break
                nxt = esc(*cur)
                if nxt == cur:
                    break
                cur = nxt
        for key in list(self._cache):
            if key[2] != tuple(base):
                continue
            if ((key[3] == "w" and key[4] not in keep_w) or
                    (key[3] == "fused" and key[4] not in keep_f)):
                del self._cache[key]

    def _evict_stale(self):
        """Drop executables whose index shape epoch is superseded.

        Cap-variant eviction (_evict) only sweeps one plan key; without
        this sweep a long-lived serve session leaks every compiled
        program across updates that change a static shape (delta
        capacity growth, n_pad/knot widening, probe refresh).
        """
        cur = self.index.shape_epoch
        for key in list(self._cache):
            if key[5] != cur:
                del self._cache[key]

    def cache_variants(self, base) -> list:
        """Cached (tag, (cap, cand)) window variants for one sticky key."""
        return sorted((k[3], k[4]) for k in self._cache
                      if k[2] == tuple(base) and k[3] in ("w", "fused"))

    def cache_keys(self) -> list:
        """All executable-cache keys (plan.exec_key layout) — used by
        tests/tools to assert backend and query-shard compilation."""
        return list(self._cache)

    @property
    def compile_ms_total(self) -> float:
        """Compile time on every thread: inline + background."""
        with self._compile_lock:
            return self.compile_ms_inline + self.compile_ms_background

    def stats(self) -> dict:
        return {"host_syncs": self.host_syncs,
                "probe_syncs": self.probe_syncs,
                "dispatches": self.dispatches,
                "prep_launches": self.prep_launches,
                "cache_size": len(self._cache),
                "backend": self.backend.name,
                "qshard_executables": sum(1 for k in self._cache if k[1]),
                "compile_ms_total": round(self.compile_ms_total, 1),
                "compile_ms_inline": round(self.compile_ms_inline, 1),
                "compile_ms_background":
                    round(self.compile_ms_background, 1),
                "disk_cache_hits":
                    self._disk.hits if self._disk else 0,
                "disk_cache_misses":
                    self._disk.misses if self._disk else 0,
                "disk_cache_uncacheable": sum(
                    1 for d in self._cache.values()
                    if isinstance(d, _Dispatch) and d._uncacheable),
                "async_compiles": self.async_compiles,
                "sticky": dict(self._sticky),
                "epoch": self.index.epoch,
                "shape_epoch": self.index.shape_epoch,
                "updates": self.updates,
                "refits": self.refits,
                "pending_refit": sorted(self._refit_pending)}

    def compiled_programs(self):
        """(exec_key, args signature, compiled executable) of every
        program this unmeshed executor has realized (meshed executors
        cache plain jitted callables, which have none to list)."""
        for key, d in list(self._cache.items()):
            if isinstance(d, _Dispatch):
                for sig, compiled in list(d._fns.items()):
                    yield key, sig, compiled

    @property
    def epoch(self) -> int:
        """Mutation epoch of the resident index — the read-your-writes
        barrier token the serve scheduler stamps on request tickets
        (a read dispatched after a write sees an epoch >= the write's).
        """
        return self.index.epoch

    def maintenance_due(self) -> bool:
        """Deferred maintain() work waiting? (stashed ok flags from
        zero-sync runs, or occupancy-scheduled compactions) — the serve
        scheduler polls this at queue-idle time so maintenance never
        rides the hot path."""
        return bool(self._pending) or bool(self._refit_pending)

    # -- async precompilation worker (DESIGN.md §14) ---------------------

    @property
    def precompiling(self) -> bool:
        """Whether the async precompile worker is running."""
        return self._pc_thread is not None

    def start_precompiler(self) -> bool:
        """Start the background thread that compiles predictable-next
        executables off the serving thread: the escalation tier above
        sticky, demotion targets, and (spec, width) signatures fed by
        the serve scheduler. Idempotent; returns True when a thread was
        actually started."""
        if self._pc_thread is not None:
            return False
        import queue
        self._pc_q = queue.Queue()
        self._pc_stop = threading.Event()
        t = threading.Thread(target=self._pc_loop, daemon=True,
                             name="executor-precompile")
        self._pc_thread = t
        t.start()
        return True

    def stop_precompiler(self) -> None:
        if self._pc_thread is None:
            return
        self._pc_stop.set()
        self._pc_q.put(None)
        self._pc_thread.join(timeout=60)
        self._pc_thread = None
        self._pc_seen = set()

    def _pc_submit(self, label, thunk):
        if self._pc_thread is None or label in self._pc_seen:
            return None
        self._pc_seen.add(label)
        self._pc_q.put((label, thunk))
        return label

    def precompile_done(self, label) -> bool:
        """Has a precompile_async job (by returned label) finished?"""
        return label in self._pc_done

    def precompile_quiesce(self, timeout: float = 60.0) -> bool:
        """Block until every enqueued precompile job has finished (or
        the timeout passes; returns False then). Bench/test hook: on
        small machines a still-running background compile contends
        with a timed serving window for cores."""
        if self._pc_thread is None:
            return True
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if all(lbl in self._pc_done
                   for lbl in tuple(self._pc_seen)):
                return True
            time.sleep(0.002)
        return False

    def _pc_loop(self):
        while not self._pc_stop.is_set():
            item = self._pc_q.get()
            if item is None or self._pc_stop.is_set():
                break
            label, thunk = item
            try:
                # the thunk compiles OUTSIDE the executor lock (it only
                # takes it briefly to create dispatchers) — a failed
                # speculative compile must never take serving down
                self.async_compiles += int(thunk() or 0)
            except Exception:
                pass
            self._pc_done.add(label)

    def precompile_async(self, spec: QuerySpec, *args):
        """Feed one predicted-next (spec, args-shape) signature to the
        precompile worker. The worker realizes exactly the executables
        the steady path would need for that shape — it never executes
        a query, so results are unaffected; only WHEN compilation
        happens moves. Returns an opaque label to poll with
        precompile_done(), or None (worker off / already enqueued)."""
        if self._pc_thread is None or not isinstance(spec, QuerySpec):
            return None
        sig = _Dispatch.sig_of(args)
        label = ("spec", type(spec).__name__, spec.plan_key(), sig,
                 self.index.shape_epoch)
        args = tuple(np.asarray(a) for a in args)   # detach from caller
        return self._pc_submit(
            label, partial(self._pc_warm_spec, spec, args))

    def _pc_warm_spec(self, spec, args) -> int:
        with self._lock:
            targets = self._warm_targets(spec, args)
        n = 0
        for disp, sig in targets:
            if isinstance(disp, _Dispatch):
                n += bool(disp.warm(sig))
        return n

    def _pc_neighbors(self, base):
        """After a sticky move: warm the adjacent ladder tiers (the
        next escalation and the demotion target) at the signatures
        already seen by this base's fused dispatchers, so the NEXT
        tier change hands off an already-compiled executable."""
        sticky = self._sticky.get(base)
        esc = self._escalators.get(base)
        dem = self._demoters.get(base)
        if sticky is None:
            return
        sigs = set()
        for k, v in self._cache.items():
            if (k[2] == tuple(base) and k[3] == "fused"
                    and isinstance(v, _Dispatch)):
                sigs.update(v.sigs())
        if not sigs:
            return
        for rule in (esc, dem):
            if rule is None:
                continue
            tier = rule(*sticky)
            if tier == sticky:
                continue
            label = ("tier", tuple(base), tier,
                     self.index.shape_epoch, tuple(sorted(sigs)))
            self._pc_submit(label, partial(self._pc_warm_tier,
                                           tuple(base), tier,
                                           sorted(sigs)))

    def _pc_warm_tier(self, base, tier, sigs) -> int:
        with self._lock:
            op = self._op_for(base)
            if op is None or op.fused is None:
                return 0
            disp = self._compile(self._key(base, "fused", tier),
                                 lambda: op.fused(*tier))
        n = 0
        if isinstance(disp, _Dispatch):
            for sig in sigs:
                n += bool(disp.warm(sig))
        return n

    # -- manifest-driven prewarm (DESIGN.md §14) -------------------------

    def _op_for(self, base) -> Optional[_AdaptiveOp]:
        """Rebuild the adaptive-op descriptor for a sticky base — the
        registry that lets a recorded manifest (or a precompile job)
        reconstruct any program family from its exec_key alone."""
        kind = base[0]
        if kind == "range":
            return self._op_range(tuple(base))
        if kind == "circle":
            return self._op_circle(tuple(base), bool(base[1]))
        if kind == "knn":
            return self._op_knn(tuple(base), int(base[1]))
        if kind == "join":
            return self._op_join(tuple(base))
        return None

    def _factory_for(self, base, tag, variant):
        idx, cfg, bk = self.index, self.cfg, self.backend
        kind = base[0]
        if kind == "prep":
            return lambda: R.program(base, self.spec)
        if tag == "u":
            return {"insert": (lambda: M.scatter_inserts),
                    "delete": (lambda: M.apply_deletes)}.get(kind)
        if tag == "x":
            if kind == "point":
                return lambda: L._PointLocal(idx, cfg, bk)
            if kind == "range_count":
                return lambda: L._RangeCountLocal(idx, cfg, bk)
            if kind == "circle_exact":
                return lambda: L._CircleCountLocal(idx, cfg, bk)
            if kind == "join_full":
                return lambda: L._JoinFullLocal(idx, cfg, bk)
            if kind == "knn_exact":
                return lambda: L._KnnExactLocal(idx, cfg, bk,
                                                int(base[1]))
            return None
        op = self._op_for(base)
        if op is None:
            return None
        if tag == "p" and op.probe is not None:
            return lambda: op.probe(int(variant[0]))
        if tag == "w":
            return lambda: op.window(*variant)
        if tag == "fused" and op.fused is not None:
            return lambda: op.fused(*variant)
        return None

    def manifest(self) -> dict:
        """JSON-serializable snapshot of everything compiled + tuned:
        sticky tiers, delta capacity, and every realized (exec_key,
        signature). Replay with ``prewarm()`` — in this process after
        an eviction, or in a future one (compile_cache.save_manifest /
        load_manifest round-trips it through prewarm.json)."""
        with self._lock:
            progs = []
            for key, v in sorted(self._cache.items(), key=repr):
                if not isinstance(v, _Dispatch) or not v.sigs():
                    continue
                if key[5] != self.index.shape_epoch:
                    continue
                variant = (list(key[4]) if isinstance(key[4], tuple)
                           else key[4])
                progs.append({
                    "key": [key[0], bool(key[1]), list(key[2]),
                            key[3], variant],
                    "sigs": [[[list(s), d] for s, d in sig]
                             for sig in v.sigs()],
                })
            return {"version": 1,
                    "backend": self.backend.name,
                    "delta_cap": int(self.index.delta_cap or 0),
                    "sticky": [[list(b), list(t)]
                               for b, t in sorted(self._sticky.items())],
                    "programs": progs}

    def prewarm(self, manifest: dict, exercise: bool = False) -> dict:
        """Replay a recorded manifest: install the recorded delta
        capacity FIRST (so the shape-epoch bump cannot evict what is
        about to be compiled), preset the sticky tiers, then realize
        every recorded (program, signature) without executing anything.
        With a warm disk cache underneath this is a load, not a
        compile. ``exercise=True`` additionally routes one zero-filled
        query batch per recorded READ family through the public ``run``
        path — outputs discarded, adaptive bookkeeping restored — to
        absorb the per-process first use of the few eager ops left on
        the read path (see ``_exercise_families``); update programs are
        never executed. Returns {programs, compiled, skipped} counts."""
        if not isinstance(manifest, dict) or \
                manifest.get("version") != 1:
            return {"programs": 0, "compiled": 0, "skipped": 0}
        with self._lock:
            return self._prewarm_locked(manifest, exercise)

    def _prewarm_locked(self, manifest: dict, exercise: bool = False
                        ) -> dict:
        progs = [p for p in manifest.get("programs", ())
                 if p["key"][0] == self.backend.name]
        # 1. delta capacity before ANY compile: the recorded cap is
        # already pow2-at-least(cfg floor), so this reproduces exactly
        # the shape a first insert would install — and bumping the
        # epoch now means nothing compiled below gets swept later
        dcap = int(manifest.get("delta_cap") or 0)
        has_u = any(p["key"][3] == "u" for p in progs)
        if dcap > 0 or has_u:
            idx = self.index
            need = max(dcap, 1)
            if idx.delta_count is None or idx.delta_cap < need:
                self._install_index(M.with_delta_capacity(
                    idx, need, floor=self.cfg.delta_cap))
        # 2. sticky tiers, so live traffic dispatches fused programs
        # at the recorded tier from the first request
        for b, t in manifest.get("sticky", ()):
            self._sticky[tuple(b)] = tuple(int(v) for v in t)
        # 3. realize the programs
        compiled = skipped = 0
        for p in progs:
            _bk, qs, base, tag, variant = p["key"]
            base = tuple(base)
            if isinstance(variant, list):
                variant = tuple(int(v) for v in variant)
            if qs:
                skipped += 1        # qshard wrappings: mesh-only, not
                continue            # covered by the export layer
            if tag == "u" and variant and \
                    int(variant[1]) != int(self.index.delta_cap or 0):
                skipped += 1        # stale capacity variant
                continue
            make_fn = self._factory_for(base, tag, variant)
            if make_fn is None:
                skipped += 1
                continue
            key = self._key(base, tag, variant)
            if tag == "u":
                if key not in self._cache:
                    self._cache[key] = _Dispatch(self, key, make_fn(),
                                                 prefix=0)
                disp = self._cache[key]
            else:
                disp = self._compile(key, make_fn)
            if not isinstance(disp, _Dispatch):
                skipped += 1
                continue
            for sig in p.get("sigs", ()):
                sig_t = tuple((tuple(int(d) for d in s), str(dt))
                              for s, dt in sig)
                compiled += bool(disp.warm(sig_t))
        if exercise:
            self._exercise_families(progs)
        return {"programs": len(progs), "compiled": compiled,
                "skipped": skipped}

    def _exercise_families(self, progs) -> None:
        """Real ``run()`` calls per recorded read family, on zero-filled
        queries at each recorded narrow batch width, outputs discarded.

        The per-kind prep is a compiled program (core/prep.py), recorded
        in the manifest and realized by the replay itself. What this
        still absorbs is the first use of the few eager ops left around
        the programs — the point lookup's ``hits > 0``, kNN's negation
        in ``post``, the fallbacks' merges — each a tiny kernel traced
        and compiled per batch shape in a fresh process, so every
        recorded narrow width is exercised, not just one. Widths at or
        above ``tier_bucket_min`` are skipped: they dispatch through the
        data-dependent bucketed splitter, whose zero-query buckets could
        realize sub-batch widths the recorded traffic never used.
        Adaptive bookkeeping is snapshotted and restored, so prewarm
        never changes what later traffic computes; update programs are
        never executed."""
        fam = {}
        bmin = self.cfg.tier_bucket_min
        for p in progs:
            _bk, qs, base, tag, _variant = p["key"]
            base = tuple(base)
            want = "x" if base[0] in ("point", "range_count",
                                      "knn_exact", "join_full") \
                else "fused"
            if qs or tag != want:
                continue
            for sig in p.get("sigs", ()):
                if sig[0][0][0] < bmin:
                    fam.setdefault(base, {})[tuple(sig[0][0])] = sig
        pending = dict(self._pending)
        outs = []
        f32 = np.float32
        for base, sigs in sorted(fam.items(), key=repr):
            for sig in sigs.values():
                self._exercise_one(base, sig, outs, f32)
        try:
            jax.block_until_ready(outs)
        except Exception:
            pass
        self._pending = pending

    def _exercise_one(self, base, sig, outs, f32) -> None:
        b = int(sig[0][0][0])
        kind = base[0]
        try:
            if kind == "point":
                req = (PointQuery(), np.zeros(b, f32),
                       np.zeros(b, f32))
            elif kind == "range_count":
                req = (RangeCount(), np.zeros((b, 4), f32))
            elif kind == "range":
                req = (RangeQuery(), np.zeros((b, 4), f32))
            elif kind == "circle":
                req = (CircleQuery(materialize=bool(base[1])),
                       np.zeros(b, f32), np.zeros(b, f32),
                       np.zeros(b, f32))
            elif kind == "knn":
                req = (Knn(k=int(base[1])), np.zeros(b, f32),
                       np.zeros(b, f32))
            elif kind == "knn_exact":
                req = (Knn(k=int(base[1]), mode="exact"),
                       np.zeros(b, f32), np.zeros(b, f32))
            elif kind in ("join", "join_full"):
                v = int(sig[0][0][1])
                req = (SpatialJoin(mode="full" if kind ==
                                   "join_full" else "windowed"),
                       np.zeros((b, v, 2), f32),
                       np.full(b, min(3, v), np.int32))
            else:
                return
            outs.append(self.run(req[0], *req[1:]))
        except Exception:
            pass                        # best-effort, like the cache

    def _warm_targets(self, spec: QuerySpec, args) -> list:
        """(dispatcher, signature) pairs the steady path would need for
        this (spec, args-shape): the spec's prep program and the
        programs it feeds, whose signature ``jax.eval_shape`` derives
        from the prep (nothing runs). Built under the executor lock —
        cheap (one abstract trace of the prep, closure construction; no
        compile); the caller warms them outside the lock."""
        out = []

        def add(key, make_fn, sig):
            out.append((self._compile(key, make_fn), sig))

        idx, cfg, bk = self.index, self.cfg, self.backend
        q = self._prep_inputs(spec, args)
        base = self._prep_base(spec)
        pargs = q
        if base is not None:
            make = self._factory_for(base, "x", None)
            add(self._key(base), make, _Dispatch.sig_of(q))
            pargs = jax.eval_shape(make(), self.parts, self.bounds, *q)
        sig = _Dispatch.sig_of(pargs)
        if isinstance(spec, PointQuery):
            add(self._key(("point",)),
                lambda: L._PointLocal(idx, cfg, bk), sig)
        elif isinstance(spec, RangeCount):
            add(self._key(("range_count",)),
                lambda: L._RangeCountLocal(idx, cfg, bk), sig)
        elif isinstance(spec, RangeQuery):
            self._warm_adaptive(self._op_range(spec.sticky_key()),
                                pargs, add)
        elif isinstance(spec, CircleQuery):
            self._warm_adaptive(
                self._op_circle(spec.sticky_key(), spec.materialize),
                pargs, add)
        elif isinstance(spec, Knn) and spec.mode == "exact":
            add(self._key(("knn_exact", spec.k)),
                lambda: L._KnnExactLocal(idx, cfg, bk, spec.k), sig)
        elif isinstance(spec, Knn):
            self._warm_adaptive(self._op_knn(spec.sticky_key(), spec.k),
                                pargs, add)
        elif isinstance(spec, SpatialJoin) and spec.mode == "full":
            add(self._key(("join_full",)),
                lambda: L._JoinFullLocal(idx, cfg, bk), sig)
        elif isinstance(spec, SpatialJoin):
            self._warm_adaptive(self._op_join(spec.sticky_key()),
                                pargs, add)
        return out

    def _warm_adaptive(self, op: _AdaptiveOp, pargs, add) -> None:
        """Warm targets for one adaptive family at this batch shape:
        mirrors _adaptive's steady dispatch — the sticky fused program
        (at the row-chunked width the bucketed path would use), plus
        the probe when the width crosses the bucketing threshold; the
        initial strict window tier when no sticky exists yet."""
        sticky = self._sticky.get(op.base)
        qn = int(pargs[0].shape[0])
        sig = _Dispatch.sig_of(pargs)
        if sticky is None:
            tier = self._initial.get(op.base, op.initial)
            add(self._key(op.base, "w", tier),
                lambda: op.window(*tier), sig)
            return
        use_bucket = (self.cfg.tier_buckets and op.probe is not None
                      and qn >= self.cfg.tier_bucket_min
                      and (op.feasible is not None
                           or op.bucketer is not None))
        if use_bucket:
            cand_p = (self.cfg.knn_cand if op.bucketer is not None
                      else sticky[1])
            add(self._key(op.base, "p", (cand_p,)),
                lambda: op.probe(cand_p), sig)
        if op.fused is None:
            return
        cw = min(self._row_chunk(sticky, qn), qn) if use_bucket else qn
        csig = tuple(((cw,) + s[1:], d) for s, d in sig)
        add(self._key(op.base, "fused", sticky),
            lambda: op.fused(*sticky), csig)

    # -- mutable-index state management (DESIGN.md §11) ------------------

    def _recount(self):
        """Refresh the live-point total + density (kNN r0 seeding)."""
        idx = self.index
        n = int(jnp.sum(idx.count))
        if idx.dead is not None:
            n -= int(jnp.sum(idx.dead))
        if idx.delta_vid is not None and idx.delta_cap:
            n += int(jnp.sum((idx.delta_vid >= 0).astype(jnp.int32)))
        self.n_total = n
        self.density = max(n / self.area, 1e-30)

    def _install_index(self, new_index, leaves=None):
        """Swap in a mutated index: refresh the (possibly sharded) parts
        leaves and evict executables compiled against superseded static
        shapes. ``leaves`` limits the refresh to the planes a mutation
        actually touched (inserts never re-place the sorted data plane).
        """
        shape_changed = new_index.shape_epoch != self.index.shape_epoch
        self.index = new_index
        names = L.part_leaf_names(new_index)
        if (shape_changed or leaves is None
                or names != set(self.parts)):
            leaves = names
        upd = L.part_arrays(new_index, leaves=leaves)
        if self.mesh is not None:
            upd = {k: jax.device_put(v, self._psharding)
                   for k, v in upd.items()}
        parts = dict(self.parts)
        parts.update(upd)
        self.parts = {k: parts[k] for k in names}
        self.bounds = new_index.part_bounds    # (P, 4): cheap, always
        if self.mesh is not None:
            self.bounds = jax.device_put(
                self.bounds, NamedSharding(self.mesh, P()))
        if shape_changed:
            self._evict_stale()
        self._recount()

    def _update_fn(self, kind: str, b: int, fn):
        """Update executables cache like queries: one jitted instance
        per (batch size, delta capacity) variant, so `_evict_stale`
        sweeping a superseded shape epoch actually frees its compiled
        programs (the mutate kernels are exported unjitted)."""
        key = self._key((kind,), "u", (b, self.index.delta_cap))
        if key not in self._cache:
            if self.mesh is None:
                self._cache[key] = _Dispatch(self, key, fn, prefix=0)
            else:
                self._cache[key] = jax.jit(_named(fn, program_name(key)))
        self.dispatches += 1
        return self._cache[key]

    def _note_occupancy(self, touched):
        """Schedule deferred compaction+re-fit for partitions whose
        delta occupancy crossed the threshold (executed by maintain(),
        off the hot path — exactly like tier demotion)."""
        occ = M.delta_occupancy(self.index)
        for p in np.asarray(touched).tolist():
            if occ[p] > self.cfg.delta_occupancy:
                self._refit_pending.add(int(p))

    def _run_insert(self, args):
        """InsertBatch: append to the target partitions' delta buffers.
        Returns the assigned vids (B,). Host-driven like build_index —
        the capacity check is a blocking read, off the query hot path.
        """
        xs = jnp.asarray(args[0], jnp.float32)
        ys = jnp.asarray(args[1], jnp.float32)
        b = int(xs.shape[0])
        if b == 0:
            return np.zeros((0,), np.int32)
        idx = self.index
        if idx.delta_count is None:      # hand-built index: add aux state
            idx = M.with_delta_capacity(idx, 0, floor=0)
            self._install_index(idx)
        pid = M.assign_insert(idx, xs, ys)
        # out-of-domain inserts land in the overflow grid; widen its box
        # so the global filter (rect/circle/kNN/join candidate
        # selection) can SEE them — otherwise only the point probe,
        # which targets overflow unconditionally, would find them.
        # (Keys still clip to key_spec.bounds; the coordinate refine is
        # exact on the stored f32 coords, so counts stay right.)
        ob = np.asarray(idx.part_bounds[idx.overflow])
        nb = [min(ob[0], float(xs.min())), min(ob[1], float(ys.min())),
              max(ob[2], float(xs.max())), max(ob[3], float(ys.max()))]
        if nb != ob.tolist():
            idx = dataclasses.replace(
                idx, part_bounds=idx.part_bounds.at[idx.overflow].set(
                    jnp.asarray(nb, jnp.float32)))
            self._install_index(idx, leaves=())
        need = np.asarray(idx.delta_count) + np.bincount(
            np.asarray(pid), minlength=idx.num_partitions)
        if int(need.max()) > idx.delta_cap:
            idx = M.with_delta_capacity(idx, int(need.max()),
                                        floor=self.cfg.delta_cap)
            self._install_index(idx)     # shape change: evict + refresh
        key = K.make_keys(xs, ys, self.spec)
        vids = jnp.arange(self.next_vid, self.next_vid + b,
                          dtype=jnp.int32)
        fn = self._update_fn("insert", b, M.scatter_inserts)
        dk, dx, dy, dv, dc = fn(idx.delta_key, idx.delta_x, idx.delta_y,
                                idx.delta_vid, idx.delta_count, pid,
                                key, xs, ys, vids)
        idx = dataclasses.replace(
            idx, delta_key=dk, delta_x=dx, delta_y=dy, delta_vid=dv,
            delta_count=dc, epoch=idx.epoch + 1)
        self.next_vid += b
        self.updates += 1
        self._install_index(idx, leaves=("dx", "dy", "dvid", "dcount"))
        self._note_occupancy(np.unique(np.asarray(pid)))
        return np.arange(self.next_vid - b, self.next_vid, dtype=np.int32)

    def _run_delete(self, args):
        """DeleteBatch: tombstone every live copy of each (x, y) in its
        candidate partitions (main plane + delta). Returns the removed
        count."""
        xs = jnp.asarray(args[0], jnp.float32)
        ys = jnp.asarray(args[1], jnp.float32)
        b = int(xs.shape[0])
        if b == 0:
            return 0
        idx = self.index
        if idx.delta_count is None:      # hand-built index: add aux state
            idx = M.with_delta_capacity(idx, 0, floor=0)
            self._install_index(idx)
        pid1 = M.assign_insert(idx, xs, ys)
        pid2 = jnp.full_like(pid1, idx.overflow)
        fn = self._update_fn("delete", b, M.apply_deletes)
        nx, ny, nv, dx, dy, dv, dead2, removed = fn(
            idx.x, idx.y, idx.vid, idx.count, idx.delta_x, idx.delta_y,
            idx.delta_vid, idx.delta_count, idx.dead, xs, ys, pid1, pid2)
        idx = dataclasses.replace(
            idx, x=nx, y=ny, vid=nv, delta_x=dx, delta_y=dy,
            delta_vid=dv, dead=dead2, epoch=idx.epoch + 1)
        self.updates += 1
        leaves = ("x", "y", "vid")
        if idx.delta_cap:
            leaves = leaves + ("dx", "dy", "dvid")
        self._install_index(idx, leaves=leaves)
        self._note_occupancy(np.unique(np.append(np.asarray(pid1),
                                                 idx.overflow)))
        return int(removed)

    def refit(self, touched=None):
        """Compaction + per-partition spline re-fit (mutate.refit_
        partitions): merge delta buffers, drop tombstones, re-fit ONLY
        the given partitions (default: every dirty one). Returns the
        list of partition ids re-fit. Thread-safe."""
        with self._lock:
            return self._refit_locked(touched)

    def _refit_locked(self, touched=None):
        idx = self.index
        if idx.delta_count is None:
            return []
        if touched is None:
            touched = M.dirty_partitions(idx)
        touched = np.unique(np.asarray(touched, np.int32))
        if touched.size == 0:
            return []
        new = M.refit_partitions(idx, touched)
        self.refits += 1
        self._refit_pending.difference_update(int(t) for t in touched)
        self._install_index(new)         # data plane moved: full refresh
        # shed a burst-grown delta buffer once fully compacted (the 2x
        # floor hysteresis rate-limits grow/shrink compile ping-pong)
        idx2 = self.index
        if (idx2.delta_cap > 2 * max(self.cfg.delta_cap, 1)
                and M.dirty_partitions(idx2).size == 0):
            self._install_index(
                M.shrink_delta_capacity(idx2, self.cfg.delta_cap))
        return [int(t) for t in touched]

    def maintain(self) -> dict:
        """Deferred re-tuning: host-check the stashed ok flags of recent
        zero-sync runs; escalate sticky tiers that overflowed and DEMOTE
        tiers that have been clean for ``EngineConfig.demote_after``
        consecutive checks (the online re-tune loop in both directions —
        a hard burst no longer pins a spec at its peak tier forever).

        Call OFF the serving hot path (between batches, on a timer).
        Counts stay exact either way — overflowed fused runs already
        fell back on device — but escalating restores complete
        materialization windows and stops paying the fallback cost
        every request, while demoting sheds the peak tier's window cost
        once traffic gets easier. A demotion that immediately bounces
        back (the next overflow escalates to the tier it left) DOUBLES
        that base's required clean streak (exponential backoff), so
        steady-state serving rate-limits ping-pong compiles without
        ever disabling downward re-tuning for good. Returns
        {sticky_key: new (cap, cand)} for the tiers that moved.
        Thread-safe (the serve scheduler runs this at queue-idle time).
        """
        with self._locked():
            return self._maintain_locked()

    @contextlib.contextmanager
    def _locked(self):
        """The executor lock, its wait spanned (``lilis.exec.lock``)."""
        with obs.span("lilis.exec.lock"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _maintain_locked(self) -> dict:
        moved = {}
        for base, (tier, ok) in list(self._pending.items()):
            del self._pending[base]
            if self._sticky.get(base) != tier:
                continue   # stale: sticky already moved since the stash
            if self._all_ok(ok):
                streak = self._ok_streak.get(base, 0) + 1
                self._ok_streak[base] = streak
                # the demoted tier survived a clean check: it was a real
                # demotion, not a bounce — forget the provenance so a
                # LATER escalation through this tier is not billed as
                # ping-pong
                self._demoted_from.pop(base, None)
                demote = self._demoters.get(base)
                need = (self.cfg.demote_after *
                        self._demote_backoff.get(base, 1))
                if demote is None or streak < need:
                    continue
                new = demote(*tier)
                if new != tier:
                    self._demoted_from[base] = tier
                    self._set_sticky(base, new)
                    moved[base] = new
                continue
            self._ok_streak[base] = 0
            new = self._escalators[base](*tier)
            if new != tier:
                if self._demoted_from.pop(base, None) == new:
                    # immediate bounce: back off, don't veto forever
                    self._demote_backoff[base] = \
                        self._demote_backoff.get(base, 1) * 2
                self._set_sticky(base, new)
                moved[base] = new
        # deferred compaction + re-fit, scheduled by updates whose delta
        # occupancy crossed the threshold — executed here, off the hot
        # path, exactly like tier re-tuning (DESIGN.md §11)
        if self._refit_pending:
            done = self.refit(sorted(self._refit_pending))
            if done:
                moved["refit"] = done
        return moved

    # -- public entry points ---------------------------------------------

    def run(self, spec: QuerySpec, *args, strict: bool = False):
        """Execute one QuerySpec. See class docstring for ``strict``.

        Thread-safe: the executor lock serializes dispatch (executable
        cache, sticky state, index swap) so the serve scheduler's
        worker and direct callers can share one executor."""
        if not isinstance(spec, QuerySpec):
            raise TypeError(f"expected a QuerySpec, got {spec!r}")
        if len(args) != spec.n_args:
            raise TypeError(f"{type(spec).__name__} takes {spec.n_args} "
                            f"data arguments, got {len(args)}")
        with self._locked():
            if isinstance(spec, InsertBatch):
                return self._run_insert(args)
            if isinstance(spec, DeleteBatch):
                return self._run_delete(args)
            if isinstance(spec, Refit):
                return self.refit()
            if isinstance(spec, PointQuery):
                return self._run_point(spec, args)
            if isinstance(spec, RangeCount):
                return self._run_range_count(spec, args)
            if isinstance(spec, RangeQuery):
                return self._run_range(spec, args, strict)
            if isinstance(spec, CircleQuery):
                return self._run_circle(spec, args, strict)
            if isinstance(spec, Knn):
                return self._run_knn(spec, args, strict)
            if isinstance(spec, SpatialJoin):
                return self._run_join(spec, args, strict)
        raise TypeError(f"unknown QuerySpec: {spec!r}")

    def run_batch(self, requests, strict: bool = False) -> list:
        """Execute a mixed workload: iterable of (spec, *args) tuples.

        Returns results in request order. Steady-state batches (every
        spec sticky-hit) dispatch with zero host syncs.
        """
        return [self.run(req[0], *req[1:], strict=strict)
                for req in requests]

    # -- shared adaptive policy ------------------------------------------

    def _adaptive(self, op: _AdaptiveOp, pargs, strict: bool,
                  start: Optional[Tuple[int, int]] = None):
        """Sticky + geometric escalation + exact fallback — ONCE.

        Replaces the divergent copies the seed engine kept in
        range_query / knn / join_count. ``start`` marks a one-off
        user-tier override: it never UPDATES the shared sticky state,
        so a single cheap capped query cannot downgrade the serving
        tier (and evict its compiled fused executable).
        """
        self._initial.setdefault(op.base, op.initial)
        self._escalators[op.base] = op.escalate
        self._demoters[op.base] = op.demote
        sticky = self._sticky.get(op.base)
        if (sticky is not None and not strict and op.fused is not None
                and start is None):
            if (self.cfg.tier_buckets and op.probe is not None
                    and pargs[0].shape[0] >= self.cfg.tier_bucket_min
                    and (op.feasible is not None
                         or op.bucketer is not None)):
                return self._run_bucketed(op, pargs, sticky)
            # steady state: fused windowed+fallback program, no host
            # sync; the ok flags are stashed (not read) so maintain()
            # can re-tune the sticky tier off the hot path
            out, ok = self._fused_chunked(op, sticky, pargs,
                                          pargs[0].shape[0])
            self._pending[op.base] = (sticky, ok)
            with obs.span("lilis.exec.post"):
                return op.post(out)
        cap, cand = start or sticky or op.initial
        qn = pargs[0].shape[0]
        while True:
            cw = min(qn, self._mem_rows((cap, cand)))
            qs = self._use_qshard(cw)
            fn = self._compile(self._key(op.base, "w", (cap, cand),
                                         qshard=qs),
                               lambda: op.window(cap, cand), qshard=qs)
            res = self._call_rows(fn, pargs, cw)
            hit = self._all_ok(op.get_ok(res))
            maxed = op.maxed(cap, cand)
            if hit or (maxed and op.sticky_on_maxed):
                if start is None:
                    self._set_sticky(op.base, (cap, cand))
                return op.finalize(res)
            if maxed:
                break
            cap, cand = op.escalate(cap, cand)
        return op.fallback(pargs, res)

    # -- wide-batch tier-bucketed dispatch (DESIGN.md §13) ---------------

    def _ladder_tiers(self, op: _AdaptiveOp, sticky) -> list:
        """Escalation-ladder tiers from the initial config up to the
        sticky tier, ascending. A sticky tier off the ladder (never the
        case for tiers set by the policy loop) degrades to the single
        sticky bucket — correctness never depends on the ladder."""
        tiers = [op.initial]
        cur = op.initial
        while cur != sticky:
            nxt = op.escalate(*cur)
            if nxt == cur or len(tiers) > 64:
                return [sticky]
            cur = nxt
            tiers.append(cur)
        return tiers

    def _part_shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a]
                            for a in _axes(self.part_axis)]))

    def _feasible_rect(self, materialize: bool) -> Callable:
        """Host-side feasibility rule against the (Q, 3) probe output
        [ncand, need, needsum]: a feasible row is GUARANTEED ok at the
        tier (candidate set complete, every learned window fits cap,
        and — when materializing — the keep width cannot drop ids), so
        running it below the sticky tier returns bitwise the sticky
        result (modulo -1 width padding, normalized by _norm_width)."""
        n_pad = self.index.n_pad
        p_total = self.index.num_partitions
        d_cap = self.index.delta_cap

        def feasible(probe, cap, cand):
            cap_e = min(cap, n_pad)
            cand_e = min(cand, p_total)
            ok = (probe[:, 0] <= cand_e) & (probe[:, 1] <= cap_e)
            if materialize:
                ok = ok & ((probe[:, 2] + cand_e * d_cap)
                           <= max(cap_e * 8, 256))
            return ok

        return feasible

    def _owidth_rect(self) -> Callable:
        """Materialized vid-plane width of the rect-family programs at a
        tier — must match _keep_window's keep bound exactly so light
        buckets -1-pad to the sticky tier's width bitwise."""
        n_pad = self.index.n_pad
        p_total = self.index.num_partitions
        d_cap = self.index.delta_cap
        shards = self._part_shards()

        def owidth(cap, cand):
            cap_e = min(cap, n_pad)
            cand_e = min(cand, p_total)
            return min(shards * cand_e * (4 * cap_e + d_cap),
                       max(cap_e * 8, 256))

        return owidth

    def _knn_bucketer(self, k: int) -> Callable:
        """Rank kNN rows by PREDICTED resolution round from the (Q, J, 3)
        radius-ladder probe [need, tot, nin]: rows whose candidate mass
        reaches 2k within the probed rounds (and whose window fits the
        sticky cap/cand) rank 0; everything else joins the hard rank,
        where the fused program's on-device cap ladder resolves the
        stragglers without dragging the easy rows along. Every rank
        still runs at the SAME sticky tier — the prediction splits
        padded widths and isolates the escalation cost, it never
        changes per-row values."""
        n_pad = self.index.n_pad
        p_total = self.index.num_partitions
        cand = min(self.cfg.knn_cand, p_total)
        j_max = L._KnnNeedLocal.J

        def bucketer(probe, cap, _cand):
            cap_e = min(cap, n_pad)
            need, tot, nin = probe[..., 0], probe[..., 1], probe[..., 2]
            enough = tot >= 2 * k                       # (Q, J)
            jest = np.where(enough.any(axis=1),
                            enough.argmax(axis=1), j_max)
            jc = np.minimum(jest, j_max - 1)
            rows = np.arange(probe.shape[0])
            hard = ((jest >= j_max) | (need[rows, jc] > cap_e)
                    | (nin[rows, jc] > cand))
            return np.where(hard, 2, np.where(jest <= 1, 0, 1))

        return bucketer

    def _norm_width(self, op: _AdaptiveOp, out, tier, sticky):
        """-1-pad a light bucket's materialized vid plane out to the
        sticky tier's width (both planes are -1 beyond the kept count,
        so padding preserves bitwise equality with the sticky run)."""
        if op.owidth is None or tier == sticky:
            return out
        pad = op.owidth(*sticky) - out[1].shape[1]
        if pad <= 0:
            return out
        vids = jnp.concatenate(
            [out[1], jnp.full((out[1].shape[0], pad), -1,
                              out[1].dtype)], axis=1)
        return (out[0],) + (vids,) + tuple(out[2:])

    def _row_chunk(self, tier, width: int) -> int:
        """Max rows per fused dispatch at ``tier``: keeps width x
        per-row candidate plane (cap*cand) within ``row_chunk_elems``
        so the gather/merge working set stays cache-resident — past
        the budget wide batches go memory-bound and per-query cost
        triples. Power of two (tiles the pow2-padded buckets exactly,
        so every chunk reuses ONE executable shape) with a 256-row
        floor so dispatch overhead stays amortized — unless the device
        memory bound (``_mem_rows``) is lower."""
        per_row = max(1, int(tier[0]) * int(tier[1]))
        cw = max(1, self.cfg.row_chunk_elems // per_row)
        cw = max(256, 1 << (cw.bit_length() - 1))
        return min(width, cw, self._mem_rows(tier))

    def _fused_chunked(self, op: _AdaptiveOp, tier, bargs, width: int):
        """Dispatch ``bargs`` (width rows) through the tier's fused
        executable in _row_chunk-width slices (``_call_rows``);
        returns (out, ok)."""
        cw = self._row_chunk(tier, width)
        qs = self._use_qshard(cw)
        fn = self._compile(self._key(op.base, "fused", tier, qshard=qs),
                           lambda: op.fused(*tier), qshard=qs)
        return self._call_rows(fn, bargs, cw)

    def _run_bucketed(self, op: _AdaptiveOp, pargs, sticky):
        """Wide-batch tier-bucketed dispatch (DESIGN.md §13).

        One tiny device probe (tag "p", (Q, ·) replicated ints) is read
        back (counted in ``probe_syncs``, NOT ``host_syncs`` — the
        zero-sync contract concerns the ok-flag retry loop) and ranks
        every query: rect-family rows run at the LOWEST ladder tier
        where the probe guarantees feasibility (provided they are also
        feasible at sticky — otherwise they join the hard bucket AT the
        sticky tier, exactly where serial dispatch would run them); kNN
        rows bucket by predicted resolution round at the sticky tier.
        Each bucket pads to a power of two (repeating its own row 0, a
        real query of the same bucket) and dispatches its own fused
        executable; results scatter back in request order. Per-row
        outputs of the fused programs depend only on (row, tier), so
        the whole dance is bitwise-identical to one sticky-tier batch.
        """
        qn = pargs[0].shape[0]
        cand_p = (self.cfg.knn_cand if op.bucketer is not None
                  else sticky[1])
        pfn = self._compile(self._key(op.base, "p", (cand_p,)),
                            lambda: op.probe(cand_p))
        probe = self._call(pfn, *pargs)
        with obs.span("lilis.exec.sync"):
            probe = np.asarray(probe)
        self.probe_syncs += 1
        if op.bucketer is not None:
            rank = np.asarray(op.bucketer(probe, *sticky))
            tier_of = [sticky] * (int(rank.max()) + 1)
        else:
            tier_of = self._ladder_tiers(op, sticky)
            nb = len(tier_of)
            rank = np.full(qn, nb - 1, np.int64)
            for i in reversed(range(nb - 1)):
                rank = np.where(op.feasible(probe, *tier_of[i]), i, rank)
            # rows the probe cannot guarantee at sticky go to a
            # DEDICATED hard bucket at the sticky tier: their on-device
            # exact fallback then fires on that small bucket only,
            # instead of dragging every feasible row through it
            rank = np.where(op.feasible(probe, *sticky), rank, nb)
            tier_of = tier_of + [sticky]
        present = np.unique(rank)
        if present.size == 1 and tier_of[int(present[0])] == sticky:
            # degenerate: one sticky bucket — still row-chunked, the
            # cache-residency cliff does not care about bucket count
            out, ok = self._fused_chunked(op, sticky, pargs, qn)
            self._pending[op.base] = (sticky, ok)
            with obs.span("lilis.exec.post"):
                return op.post(out)
        idxs, outs, oks = [], [], []
        for rk in present.tolist():
            sel = np.nonzero(rank == rk)[0]
            idxs.append(sel)
            tier = tier_of[int(rk)]
            bl = sel.size
            plen = 1 << (bl - 1).bit_length()
            take = jnp.asarray(sel, jnp.int32)
            bargs = tuple(jnp.take(a, take, axis=0) for a in pargs)
            if plen > bl:
                bargs = tuple(jnp.concatenate(
                    [a, jnp.repeat(a[:1], plen - bl, axis=0)], axis=0)
                    for a in bargs)
            out, ok = self._fused_chunked(op, tier, bargs, plen)
            with obs.span("lilis.exec.post"):
                if plen > bl:
                    out = jax.tree_util.tree_map(lambda a: a[:bl], out)
                    ok = ok[:bl]
                outs.append(self._norm_width(op, out, tier, sticky))
            oks.append(ok)
        with obs.span("lilis.exec.post"):
            inv = np.empty(qn, np.int64)
            inv[np.concatenate(idxs)] = np.arange(qn)
            take = jnp.asarray(inv, jnp.int32)
            merged = jax.tree_util.tree_map(
                lambda *leaves: jnp.take(jnp.concatenate(leaves, axis=0),
                                         take, axis=0), *outs)
            ok_all = jnp.take(jnp.concatenate(oks, axis=0), take, axis=0)
            self._pending[op.base] = (sticky, ok_all)
            return op.post(merged)

    def _maxed_both(self, cap, cand):
        return (cap >= self.index.n_pad and
                cand >= self.index.num_partitions)

    def _escalate_both(self, cap, cand):
        return (min(cap * 4, self.index.n_pad),
                min(cand * 2, self.index.num_partitions))

    def _ladder_demote(self, initial, escalate):
        """Demote to the PREDECESSOR on the op's actual escalation
        ladder (initial, escalate(initial), ...) rather than a naive
        cap//4 inverse — when escalation clamped at n_pad /
        num_partitions the arithmetic inverse lands on off-ladder tiers
        that were never compiled, and demotion would churn fresh
        executables instead of reusing warm ones."""
        def demote(cap, cand):
            prev = cur = initial
            for _ in range(64):          # ladders are O(log) long
                if cur == (cap, cand):
                    return prev
                nxt = escalate(*cur)
                if nxt == cur:
                    break                # maxed without finding it
                prev, cur = cur, nxt
            return (cap, cand)           # off-ladder: stay put
        return demote

    # -- per-kind preparation + drivers ----------------------------------

    @staticmethod
    def _prep_base(spec: QuerySpec) -> Optional[Tuple]:
        """Exec-key base of the spec's prep program (core/prep.py), or
        None where the program takes the query arrays as they are (an
        exact kNN) or the spec is no read."""
        if isinstance(spec, (RangeCount, RangeQuery)):
            return ("prep", "rect")
        if isinstance(spec, Knn):
            return ("prep", "knn", spec.k) if spec.mode != "exact" \
                else None
        if isinstance(spec, (PointQuery, CircleQuery, SpatialJoin)):
            return ("prep", spec.kind)
        return None

    def _prep_inputs(self, spec: QuerySpec, args) -> Tuple:
        """The query arrays as device arrays of the programs' dtypes;
        a pruned kNN adds its global-density seed radius (paper Eq. 1)
        as a float32 scalar and the index's per-partition counts."""
        if isinstance(spec, SpatialJoin):
            return (jnp.asarray(args[0], jnp.float32),
                    jnp.asarray(args[1], jnp.int32))
        q = tuple(jnp.asarray(a, jnp.float32) for a in args)
        if isinstance(spec, Knn) and spec.mode != "exact":
            r0g = np.sqrt(max(spec.k, 1) / (np.pi * self.density))
            q += (np.asarray(r0g, np.float32), self.index.count)
        return q

    def _prepare(self, spec: QuerySpec, args) -> Tuple:
        """The argument tuple (pargs) of a read's programs: one launch
        of the spec's compiled prep program, counted in
        ``prep_launches`` (not a dispatch)."""
        with obs.span("lilis.exec.prep"):
            q = self._prep_inputs(spec, args)
            base = self._prep_base(spec)
            if base is None:
                return q
            fn = self._compile(self._key(base),
                               self._factory_for(base, "x", None))
            self.prep_launches += 1
            return fn(self.parts, self.bounds, *q)

    def _run_point(self, spec, args):
        qx, qy, qk = self._prepare(spec, args)
        qs = self._use_qshard(qx.shape[0])
        fn = self._compile(self._key(("point",), qshard=qs),
                           lambda: L._PointLocal(self.index, self.cfg,
                                                 self.backend),
                           qshard=qs)
        hits = self._call(fn, qx, qy, qk)
        with obs.span("lilis.exec.post"):
            return hits > 0

    def _run_range_count(self, spec, args):
        rects, klo, khi = self._prepare(spec, args)
        qs = self._use_qshard(rects.shape[0])
        fn = self._compile(self._key(("range_count",), qshard=qs),
                           lambda: L._RangeCountLocal(self.index,
                                                      self.cfg,
                                                      self.backend),
                           qshard=qs)
        return self._call(fn, rects, klo, khi)

    def _op_range(self, base):
        idx, cfg, bk = self.index, self.cfg, self.backend

        def fused(cap, cand):
            # counts stay exact via the on-device full-refine fallback;
            # ok still flags per-query materialization completeness
            return L._CondFusedLocal(
                idx, cfg, bk,
                primary=L._RangeWindowLocal(idx, cfg, bk, cap, cand),
                fallback=L._RangeCountLocal(idx, cfg, bk),
                fb_args=(0, 1, 2),
                get_ok=lambda pri: pri[2],
                merge_ok=lambda pri: pri,
                merge_fb=lambda pri, fb: (fb, pri[1], pri[2]))

        return _AdaptiveOp(
            base=base, initial=(cfg.range_cap, cfg.range_cand),
            window=lambda cap, cand: L._RangeWindowLocal(idx, cfg, bk,
                                                         cap, cand),
            get_ok=lambda res: res[2], finalize=lambda res: res,
            escalate=self._escalate_both, maxed=self._maxed_both,
            sticky_on_maxed=True, fallback=None, fused=fused,
            demote=self._ladder_demote((cfg.range_cap, cfg.range_cand),
                                       self._escalate_both),
            probe=lambda c: L._WindowNeedLocal(idx, cfg, bk, c,
                                               lambda *q: q[0], 3),
            feasible=self._feasible_rect(True),
            owidth=self._owidth_rect())

    def _run_range(self, spec: RangeQuery, args, strict):
        pargs = self._prepare(spec, args)
        op = self._op_range(spec.sticky_key())
        start = None
        if spec.cap is not None:
            # user cap overrides the starting tier; cand follows sticky
            _, cand0 = self._sticky.get(op.base, op.initial)
            start = (min(spec.cap, self.index.n_pad), cand0)
        return self._adaptive(op, pargs, strict, start=start)

    def _op_circle(self, base, materialize: bool):
        idx, cfg, bk = self.index, self.cfg, self.backend

        def window(cap, cand):
            return L._CircleWindowLocal(idx, cfg, bk, cap, cand,
                                        materialize)

        def fused(cap, cand):
            if materialize:
                return L._CondFusedLocal(
                    idx, cfg, bk, primary=window(cap, cand),
                    fallback=L._CircleCountLocal(idx, cfg, bk),
                    fb_args=(0, 1, 2, 3),
                    get_ok=lambda pri: pri[2],
                    merge_ok=lambda pri: pri,
                    merge_fb=lambda pri, fb: (fb, pri[1], pri[2]))
            return L._CondFusedLocal(
                idx, cfg, bk, primary=window(cap, cand),
                fallback=L._CircleCountLocal(idx, cfg, bk),
                fb_args=(0, 1, 2, 3),
                get_ok=lambda pri: pri[1],
                merge_ok=lambda pri: pri[0],
                merge_fb=lambda pri, fb: fb)

        def fallback(pargs, res):
            qs = self._use_qshard(pargs[0].shape[0])
            fn = self._compile(self._key(("circle_exact",), qshard=qs),
                               lambda: L._CircleCountLocal(idx, cfg, bk),
                               qshard=qs)
            cnt = self._call(fn, *pargs)
            if materialize:    # exact counts; window ids flagged by ok
                return cnt, res[1], res[2]
            return cnt

        return _AdaptiveOp(
            base=base,
            initial=(cfg.circle_cap, cfg.circle_cand), window=window,
            get_ok=lambda res: res[-1],
            finalize=(lambda res: res) if materialize
            else (lambda res: res[0]),
            escalate=self._escalate_both, maxed=self._maxed_both,
            sticky_on_maxed=False, fallback=fallback, fused=fused,
            demote=self._ladder_demote((cfg.circle_cap, cfg.circle_cand),
                                       self._escalate_both),
            probe=lambda c: L._WindowNeedLocal(idx, cfg, bk, c,
                                               lambda *q: q[0], 4),
            feasible=self._feasible_rect(materialize),
            owidth=self._owidth_rect() if materialize else None)

    def _run_circle(self, spec: CircleQuery, args, strict):
        op = self._op_circle(spec.sticky_key(), spec.materialize)
        return self._adaptive(op, self._prepare(spec, args), strict)

    def _knn_exact_fn(self, k, qshard: bool = False):
        return self._compile(self._key(("knn_exact", k), qshard=qshard),
                             lambda: L._KnnExactLocal(self.index,
                                                      self.cfg,
                                                      self.backend, k),
                             qshard=qshard)

    def _op_knn(self, base, k):
        idx, cfg, bk = self.index, self.cfg, self.backend
        cand = cfg.knn_cand

        def window(cap, _cand):
            return L._KnnPrunedLocal(idx, cfg, bk, k, self.spec, cand,
                                     cap)

        def fused(cap, _cand):
            def merge_fb(pri, fb):
                okc = pri[2][:, None]
                return (jnp.where(okc, pri[0], fb[0]),
                        jnp.where(okc, pri[1], fb[1]))

            # fallback ladder: retry overflowed rows at the next cap on
            # device before the exact full scan — stragglers that just
            # need a wider window never drag the batch through the
            # exact path (which costs ~flat per dispatch on pallas)
            exact = L._KnnExactLocal(idx, cfg, bk, k)
            esc = min(cap * 4, idx.n_pad)
            if esc > cap:
                fb = L._KnnLadderLocal(idx, cfg, bk,
                                       primary=window(esc, cand),
                                       exact=exact)
                fb_args = (0, 1, 2)
            else:
                fb, fb_args = exact, (0, 1)
            return L._CondFusedLocal(
                idx, cfg, bk, primary=window(cap, cand),
                fallback=fb, fb_args=fb_args,
                get_ok=lambda pri: pri[2],
                merge_ok=lambda pri: (pri[0], pri[1]),
                merge_fb=merge_fb)

        def fallback(pargs, res):
            # final fallback for unresolved queries: exact scan
            neg, vid, ok = res
            qs = self._use_qshard(pargs[0].shape[0])
            nege, vide = self._call(self._knn_exact_fn(k, qshard=qs),
                                    *pargs[:2])
            okc = ok[:, None]
            return (jnp.where(okc, -neg, -nege),
                    jnp.where(okc, vid, vide))

        return _AdaptiveOp(
            base=base, initial=(cfg.knn_cap, cand), window=window,
            get_ok=lambda res: res[2],
            finalize=lambda res: (-res[0], res[1]),
            escalate=lambda cap, cd: (min(cap * 4, idx.n_pad), cd),
            maxed=lambda cap, cd: cap >= idx.n_pad,
            sticky_on_maxed=False, fallback=fallback, fused=fused,
            post=lambda r: (-r[0], r[1]),
            demote=lambda cap, cd: (max(cap // 4, cfg.knn_cap), cd),
            probe=lambda c: L._KnnNeedLocal(idx, cfg, bk, c),
            bucketer=self._knn_bucketer(k))

    def _run_knn(self, spec: Knn, args, strict):
        pargs = self._prepare(spec, args)
        if spec.mode == "exact":
            qx, qy = pargs
            qs = self._use_qshard(qx.shape[0])
            neg, vid = self._call(self._knn_exact_fn(spec.k, qshard=qs),
                                  qx, qy)
            with obs.span("lilis.exec.post"):
                return -neg, vid
        op = self._op_knn(spec.sticky_key(), spec.k)
        return self._adaptive(op, pargs, strict)

    def _op_join(self, base):
        idx, cfg, bk = self.index, self.cfg, self.backend

        def fused(cap, cand):
            return L._CondFusedLocal(
                idx, cfg, bk,
                primary=L._JoinLocal(idx, cfg, bk, cap, cand),
                fallback=L._JoinFullLocal(idx, cfg, bk),
                fb_args=(0, 1, 2),
                get_ok=lambda pri: pri[1],
                merge_ok=lambda pri: pri[0],
                merge_fb=lambda pri, fb: fb)

        def fallback(pargs, res):
            qs = self._use_qshard(pargs[0].shape[0])
            fn = self._compile(self._key(("join_full",), qshard=qs),
                               lambda: L._JoinFullLocal(idx, cfg, bk),
                               qshard=qs)
            return self._call(fn, *pargs)

        return _AdaptiveOp(
            base=base, initial=(cfg.join_cap, cfg.join_cand),
            window=lambda cap, cand: L._JoinLocal(idx, cfg, bk, cap,
                                                  cand),
            get_ok=lambda res: res[1], finalize=lambda res: res[0],
            escalate=self._escalate_both, maxed=self._maxed_both,
            sticky_on_maxed=False, fallback=fallback, fused=fused,
            demote=self._ladder_demote((cfg.join_cap, cfg.join_cand),
                                       self._escalate_both),
            probe=lambda c: L._WindowNeedLocal(idx, cfg, bk, c,
                                               lambda *q: q[2][:, :4], 3,
                                               z_depth=3),
            feasible=self._feasible_rect(False))

    def _run_join(self, spec: SpatialJoin, args, strict):
        pargs = self._prepare(spec, args)
        if spec.mode == "full":
            qs = self._use_qshard(pargs[0].shape[0])
            fn = self._compile(self._key(("join_full",), qshard=qs),
                               lambda: L._JoinFullLocal(self.index,
                                                        self.cfg,
                                                        self.backend),
                               qshard=qs)
            return self._call(fn, *pargs)
        op = self._op_join(spec.sticky_key())
        return self._adaptive(op, pargs, strict)
