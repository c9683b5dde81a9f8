"""Streaming serve scheduler: request queue, adaptive micro-batching,
and off-hot-path maintenance (DESIGN.md §12).

``SpatialServeSession`` is call-and-wait: one caller, one ``submit``,
one dispatch. The traffic shape LiLIS targets — many small concurrent
point/range/circle/kNN requests plus a live ingest stream — needs the
same front door production inference stacks use: a request queue
drained by a background worker that COALESCES concurrent requests into
micro-batches for the warm fused executables, and defers maintenance
to idle time. This module is that front door:

  submit(spec, *args) -> Ticket      non-blocking; resolves when the
                                     micro-batch that carried the
                                     request completes on device
  drain()                            deterministic synchronous pump
                                     (test mode / start=False)
  request_maintain() -> Ticket       explicit maintenance barrier

Scheduling rules (the invariants tests/test_scheduler*.py pin):

  - FIFO with write barriers: requests are processed in arrival
    order; reads between two writes may be batched together (reads
    commute), but no read is ever hoisted across a write that was
    enqueued before it. A read enqueued after an ``InsertBatch`` /
    ``DeleteBatch`` therefore always observes that write's epoch
    (``Ticket.epoch`` carries the read-your-writes token).
  - Adaptive micro-batching: concurrent reads with the same spec (and
    concat-compatible arg shapes) coalesce along the query axis, up to
    a per-spec cap derived from the MEASURED wide-batch columns in
    ``BENCH_quick.json`` (``micro_batch_caps``) — since the executor's
    tier-bucketed wide-batch dispatch (DESIGN.md §13) every spec's
    wide column is no slower per query, so every measured spec
    coalesces to the measured wide batch. Batch widths are padded to
    power-of-two buckets by repeating row 0 (a real, resolvable query
    — the query-shard pad/unpad precedent), so the compiled-executable
    count stays logarithmic in ``serve_max_batch`` and results stay
    bitwise-identical to serial ``submit()``.
  - Consecutive ``InsertBatch`` writes merge into one update dispatch
    (the ingest-stream fast path); the assigned vids are routed back
    per request. Deletes return one aggregate count and never merge.
  - ``maintain()`` (sticky re-tune + occupancy-triggered compaction)
    runs ONLY when the queue is idle — never between queued requests —
    or through an explicit ``request_maintain()`` barrier. The event
    log records the queue length at every maintenance run;
    ``stats()["maintain_busy"]`` must stay 0.

Thread model: ONE worker thread owns every executor dispatch
(``Executor`` is additionally locked, core/executor.py, so direct
``session.submit`` calls may race the scheduler safely). With
``start=False`` no thread is created and ``drain()`` pumps the same
batch-forming code synchronously — the deterministic mode the
coalescing/ordering tests and the traffic benchmark's bitwise parity
phase use.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core.executor import Executor
from repro.core.plan import (CircleQuery, EngineConfig, InsertBatch, Knn,
                             PointQuery, QuerySpec, RangeCount,
                             RangeQuery, SpatialJoin, UpdateSpec)


def bench_spec_name(spec: QuerySpec) -> str:
    """The BENCH_quick.json spec-column name for a QuerySpec."""
    if isinstance(spec, PointQuery):
        return "point"
    if isinstance(spec, RangeCount):
        return "range_count"
    if isinstance(spec, RangeQuery):
        return "range"
    if isinstance(spec, CircleQuery):
        return "circle_mat" if spec.materialize else "circle"
    if isinstance(spec, Knn):
        return f"knn{spec.k}"
    if isinstance(spec, SpatialJoin):
        return "join"
    return spec.kind


def micro_batch_caps(bench: Union[str, dict, None], backend: str,
                     cfg: EngineConfig) -> dict:
    """Per-spec micro-batch caps from the measured wide-batch columns.

    The quick bench's ``steady_us_per_q`` (narrow) vs
    ``steady_us_per_q_b256`` (wide) columns measure whether coalescing
    pays: a spec with both columns measured coalesces up to the
    measured wide batch (clamped to ``cfg.serve_max_batch`` by the
    caller). The executor's tier-bucketed wide-batch dispatch plus the
    streaming chunked merges (DESIGN.md §13) make the wide column no
    slower per query for EVERY spec — enforced by tools/check.sh's
    monotonicity gate — so the old inverted-spec narrow-cap clamp is
    gone. Missing file / columns -> empty dict (callers default to
    serve_max_batch).
    """
    if isinstance(bench, str):
        try:
            with open(bench) as f:
                bench = json.load(f)
        except (OSError, ValueError):
            return {}
    if not isinstance(bench, dict):
        return {}
    br = (bench.get("backends") or {}).get(backend) or bench
    wide_b = int(bench.get("bench_q_wide", cfg.serve_max_batch))
    caps = {}
    for name, s in (br.get("specs") or {}).items():
        if (s.get("steady_us_per_q") is None
                or s.get("steady_us_per_q_b256") is None):
            continue
        caps[name] = wide_b
    return caps


def _bucket(n: int) -> int:
    """Next power-of-two batch width (bounded executable variants)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class Ticket:
    """Future for one scheduled request.

    ``result()`` blocks until the micro-batch that carried the request
    completed on device. After completion:

      ``epoch``    the index mutation epoch the request observed
                   (reads) or produced (writes) — the read-your-writes
                   barrier token;
      ``batched``  the coalesced query width of the dispatch it rode
                   in (tests assert coalescing actually happened).

    ``seq`` is the ticket's sequence id in its scheduler (submission
    order); a dispatch's spans carry the ``seq`` of its first ticket.
    """

    __slots__ = ("spec", "seq", "epoch", "batched", "_done", "_result",
                 "_exc")

    def __init__(self, spec):
        self.spec = spec
        self.seq = -1
        self.epoch: Optional[int] = None
        self.batched = 0
        self._done = threading.Event()
        self._result = None
        self._exc = None

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.spec!r} not completed "
                               f"within {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._result

    def _resolve(self, result, epoch: int, batched: int):
        self._result = result
        self.epoch = epoch
        self.batched = batched
        self._done.set()

    def _fail(self, exc: BaseException):
        self._exc = exc
        self._done.set()


class _Request:
    __slots__ = ("kind", "spec", "args", "qlen", "sig", "ticket", "t_ns")

    def __init__(self, kind, spec, args, qlen, sig, ticket):
        self.kind = kind          # "read" | "write" | "maintain"
        self.spec = spec
        self.args = args
        self.qlen = qlen
        self.sig = sig
        self.ticket = ticket
        self.t_ns = time.perf_counter_ns()   # submitted (queue wait)


class SpatialScheduler:
    """Queue + batch former + worker over one (locked) Executor."""

    def __init__(self, executor: Executor,
                 bench: Union[str, dict, None] = None,
                 start: bool = True):
        self.ex = executor
        self.cfg = executor.cfg
        if bench is None:
            bench = os.environ.get("BENCH_QUICK_OUT", "BENCH_quick.json")
        self.caps = micro_batch_caps(bench, executor.backend.name,
                                     self.cfg)
        self._q: deque = deque()
        self._cv = threading.Condition()
        self._stopping = False
        self._error: Optional[BaseException] = None  # worker death
        self._inflight = 0        # popped but not yet resolved
        self.events: deque = deque(maxlen=4096)
        self.submitted = 0
        self.reads = 0            # queries dispatched via read batches
        self.read_batches = 0     # coalesced read dispatches
        self.max_batch = 0        # widest coalesced read batch (queries)
        self.writes = 0           # write requests applied
        self.write_merges = 0     # insert requests merged into a run
        self.maintain_runs = 0
        self.maintain_busy = 0    # maintain with a non-empty queue (BAD)
        # -- time counters, ns (DESIGN.md §15) ---------------------------
        self.queue_wait_ns = 0    # read requests: submit -> popped
        self.queue_waits = 0      # read requests popped
        self.dispatch_ns = 0      # inside read dispatches,
        self.device_wait_ns = 0   # ... of which waiting on the device
        self.maintain_ns = 0      # inside maintain() runs
        self._batch_seq = 0       # read dispatches begun
        # -- async precompilation handoff (DESIGN.md §14) -------------
        self.width_fallbacks = 0  # dispatches at a larger warm width
        self._warm = {}           # coalescing sig -> warm pow2 widths
        self._warm_epoch = executor.index.shape_epoch
        self._pc_pending = {}     # (sig, width) -> precompile label
        self._pc_started = False
        self._thread = None
        if start:
            if self.cfg.serve_async_precompile:
                # worker mode only: the manual drain() mode stays
                # fully deterministic for the ordering/parity tests
                self._pc_started = executor.start_precompiler()
            self._thread = threading.Thread(
                target=self._worker, daemon=True,
                name="spatial-serve-scheduler")
            self._thread.start()

    # -- submission ------------------------------------------------------

    def submit(self, spec: QuerySpec, *args) -> Ticket:
        """Enqueue one request; returns immediately with its Ticket."""
        if not isinstance(spec, QuerySpec):
            raise TypeError(f"expected a QuerySpec, got {spec!r}")
        if len(args) != spec.n_args:
            raise TypeError(f"{type(spec).__name__} takes {spec.n_args} "
                            f"data arguments, got {len(args)}")
        args = tuple(a if hasattr(a, "shape") else np.asarray(a)
                     for a in args)
        qlen = int(args[0].shape[0]) if args else 0
        # coalescing signature: same spec (frozen dataclass equality ==
        # same compiled family) AND concat-compatible trailing shapes
        sig = (spec,) + tuple((a.shape[1:], str(a.dtype)) for a in args)
        kind = "write" if isinstance(spec, UpdateSpec) else "read"
        ticket = Ticket(spec)
        req = _Request(kind, spec, args, qlen, sig, ticket)
        with self._cv:
            self._check_open()
            while (self._thread is not None
                   and len(self._q) >= self.cfg.serve_queue_depth):
                self._cv.wait(0.005)     # backpressure
            # the worker may have died (or close() run) while we waited
            self._check_open()
            self._q.append(req)
            ticket.seq = self.submitted
            self.submitted += 1
            self._cv.notify_all()
        return ticket

    def _check_open(self, closing: bool = False) -> None:
        """Raise if the worker died (or, unless ``closing``, if the
        scheduler is closed). Call with ``_cv`` held."""
        if self._error is not None:
            raise RuntimeError("scheduler worker failed") from self._error
        if self._stopping and not closing:
            raise RuntimeError("scheduler is closed")

    def request_maintain(self) -> Ticket:
        """Enqueue an explicit maintenance barrier (arrival order —
        after everything already queued). Resolves with maintain()'s
        {moved} dict; long-lived servers use this to trigger re-tune /
        compaction at a chosen moment without stopping the scheduler."""
        ticket = Ticket(None)
        with self._cv:
            self._check_open()
            self._q.append(_Request("maintain", None, (), 0, None,
                                    ticket))
            ticket.seq = self.submitted
            self.submitted += 1
            self._cv.notify_all()
        return ticket

    # -- batch forming ---------------------------------------------------

    def _cap(self, spec: QuerySpec) -> int:
        cap = self.caps.get(bench_spec_name(spec),
                            self.cfg.serve_max_batch)
        return max(1, min(self.cfg.serve_max_batch, cap))

    def _pick_width(self, reqs, total: int) -> int:
        """Batch width for a read dispatch, with async-precompile
        handoff (DESIGN.md §14). Default: the exact pow2 bucket. When
        the executor's precompile worker is running and this
        (sig, bucket) has never been dispatched, hand the new width to
        the worker and dispatch at the nearest LARGER already-warm
        width instead — extra row-0 padding is bitwise-neutral (each
        ticket slices only its own rows), so only WHERE the compile
        happens changes, never any result. Once the worker reports the
        exact width compiled, later batches switch to it."""
        width = _bucket(total)
        if not self.ex.precompiling:
            return width
        se = self.ex.index.shape_epoch
        if se != self._warm_epoch:       # epoch bump evicted programs
            self._warm.clear()
            self._pc_pending.clear()
            self._warm_epoch = se
        pend = self._pc_pending
        for k in [k for k, lbl in pend.items()
                  if self.ex.precompile_done(lbl)]:
            self._warm.setdefault(k[0], set()).add(k[1])
            del pend[k]
        sig = reqs[0].sig
        warm = self._warm.setdefault(sig, set())
        if width in warm:
            return width
        if (sig, width) not in pend:
            label = self.ex.precompile_async(
                reqs[0].spec, *self._concat_pad(reqs, width))
            if label is not None:
                pend[(sig, width)] = label
        bigger = [w for w in warm if w > width]
        if bigger:
            self.width_fallbacks += 1
            return min(bigger)
        return width                     # nothing warm: compile inline

    def _mark_warm(self, sig, width: int) -> None:
        if not self.ex.precompiling:
            return
        se = self.ex.index.shape_epoch
        if se != self._warm_epoch:
            self._warm.clear()
            self._pc_pending.clear()
            self._warm_epoch = se
        self._warm.setdefault(sig, set()).add(width)

    def _pop(self, timeout: Optional[float] = None):
        with self._cv:
            if not self._q and timeout:
                self._cv.wait(timeout)
            if self._q:
                self._inflight += 1
                self._cv.notify_all()    # free a backpressured submit
                req = self._q.popleft()
                if req.kind == "read":
                    self.queue_wait_ns += time.perf_counter_ns() - req.t_ns
                    self.queue_waits += 1
                return req
            return None

    def _pop_merge(self, req: _Request, total: int):
        """Pop the next queued item iff it merges with an InsertBatch
        run: same spec + signature, and the merged width stays within
        serve_max_batch."""
        with self._cv:
            if (self._q and self._q[0].kind == "write"
                    and self._q[0].sig == req.sig
                    and total + self._q[0].qlen
                    <= self.cfg.serve_max_batch):
                self._inflight += 1
                return self._q.popleft()
        return None

    def _finish(self, n: int):
        with self._cv:
            self._inflight -= n
            self._cv.notify_all()

    def _form_and_run(self, straggler_wait: float = 0.0) -> bool:
        """Drain the queue once: FIFO order, reads coalesced between
        write barriers. Returns whether any request was processed."""
        groups: "OrderedDict[tuple, list]" = OrderedDict()
        sizes: dict = {}
        did = False

        def flush(sig):
            reqs = groups.pop(sig)
            sizes.pop(sig)
            self._dispatch_reads(reqs)

        def flush_all():
            while groups:
                flush(next(iter(groups)))

        while True:
            req = self._pop()
            if req is None and groups and straggler_wait:
                # a partial batch exists: wait briefly for stragglers
                with obs.span("lilis.sched.coalesce"):
                    req = self._pop(timeout=straggler_wait)
            if req is None:
                break
            did = True
            if req.kind == "read":
                groups.setdefault(req.sig, []).append(req)
                sizes[req.sig] = sizes.get(req.sig, 0) + req.qlen
                if sizes[req.sig] >= self._cap(req.spec):
                    flush(req.sig)
            elif req.kind == "maintain":
                flush_all()              # barrier: order preserved
                self._maintain(ticket=req.ticket)
            else:
                flush_all()              # write barrier
                run, total = [req], req.qlen
                if isinstance(req.spec, InsertBatch):
                    while True:
                        nxt = self._pop_merge(req, total)
                        if nxt is None:
                            break
                        run.append(nxt)
                        total += nxt.qlen
                with obs.span("lilis.sched.write", requests=len(run),
                              queries=total, spec=req.spec.kind,
                              ticket=req.ticket.seq):
                    self._dispatch_write(run, total)
        flush_all()
        return did

    # -- dispatch --------------------------------------------------------

    def _concat_pad(self, reqs, width: int):
        """Concat request args along the query axis; pad to ``width``
        by repeating row 0 (a real, resolvable query — can never trip
        the adaptive ok flags; the qshard pad precedent). Padding keeps
        the executable count logarithmic instead of one program per
        arrival width, and tickets slice only their own rows, so ANY
        width >= the total is bitwise-equivalent."""
        cols = zip(*(r.args for r in reqs))
        args = tuple(jnp.concatenate(c, axis=0) for c in cols)
        pad = width - int(args[0].shape[0]) if args else 0
        if pad > 0:
            args = tuple(jnp.concatenate(
                [a, jnp.repeat(a[:1], pad, axis=0)], axis=0)
                for a in args)
        return args

    def _dispatch_reads(self, reqs):
        spec = reqs[0].spec
        total = sum(r.qlen for r in reqs)
        self._batch_seq += 1
        with obs.span("lilis.sched.dispatch", (self, "dispatch_ns"),
                      batch=self._batch_seq, requests=len(reqs),
                      queries=total, spec=spec.kind,
                      ticket=reqs[0].ticket.seq) as sp:
            try:
                with obs.span("lilis.sched.form"):
                    width = self._pick_width(reqs, total)
                    pad = width - total
                    if len(reqs) == 1 and pad == 0:
                        args = reqs[0].args
                    else:
                        args = self._concat_pad(reqs, width)
                sp.set(width=width)
                out = self.ex.run(spec, *args)
                with obs.span("lilis.sched.device_wait",
                              (self, "device_wait_ns")):
                    jax.block_until_ready(out)
            except Exception as e:       # route the failure per request
                for r in reqs:
                    r.ticket._fail(e)
                self._finish(len(reqs))
                return
            epoch = self.ex.epoch
            lo = 0
            with obs.span("lilis.sched.resolve"):
                for r in reqs:
                    if len(reqs) == 1 and pad == 0:
                        res = out
                    else:
                        hi = lo + r.qlen
                        res = jax.tree_util.tree_map(lambda a: a[lo:hi],
                                                     out)
                    r.ticket._resolve(res, epoch, total)
                    lo += r.qlen
        self.reads += total
        self.read_batches += 1
        self.max_batch = max(self.max_batch, total)
        self._mark_warm(reqs[0].sig, width)
        self.events.append(("batch", bench_spec_name(spec), total,
                            width, len(reqs)))
        self._finish(len(reqs))

    def _dispatch_write(self, run, total):
        spec = run[0].spec
        try:
            if len(run) == 1:
                out = self.ex.run(spec, *run[0].args)
            else:                        # merged InsertBatch stream
                xs = jnp.concatenate([r.args[0] for r in run], axis=0)
                ys = jnp.concatenate([r.args[1] for r in run], axis=0)
                out = self.ex.run(spec, xs, ys)
                self.write_merges += len(run) - 1
        except Exception as e:
            for r in run:
                r.ticket._fail(e)
            self._finish(len(run))
            return
        epoch = self.ex.epoch            # the epoch this write produced
        lo = 0
        for r in run:
            res = out if len(run) == 1 else out[lo:lo + r.qlen]
            r.ticket._resolve(res, epoch, total)
            lo += r.qlen
        self.writes += len(run)
        self.events.append(("write", spec.kind, total, len(run)))
        self._finish(len(run))

    def _maintain(self, ticket: Optional[Ticket] = None,
                  idle: bool = False):
        with self._cv:
            qlen = len(self._q)
        with obs.span("lilis.sched.maintain", (self, "maintain_ns"),
                      idle=int(idle)):
            moved = self.ex.maintain()
        self.maintain_runs += 1
        if qlen:
            self.maintain_busy += 1      # should never happen on idle
        self.events.append(("maintain", qlen, bool(moved), idle))
        if ticket is not None:
            ticket._resolve(moved, self.ex.epoch, 0)
            self._finish(1)

    # -- worker / pumping ------------------------------------------------

    def _worker(self):
        try:
            self._serve()
        except BaseException as e:
            # a failure outside a dispatch (which fails its own tickets)
            # must not strand callers blocked in Ticket.result()
            with self._cv:
                self._error = e
                reqs = list(self._q)
                self._q.clear()
                self._cv.notify_all()
            for r in reqs:
                r.ticket._fail(e)
            raise

    def _serve(self):
        straggler = self.cfg.serve_coalesce_us / 1e6
        while True:
            with self._cv:
                if not self._q and not self._stopping:
                    with obs.span("lilis.sched.idle"):
                        while not self._q and not self._stopping:
                            self._cv.wait(0.05)
                if self._stopping and not self._q:
                    return
            self._form_and_run(straggler_wait=straggler)
            # idle maintenance: the queue just drained — run deferred
            # re-tuning / compaction NOW, never between queued requests
            with self._cv:
                idle = not self._q and not self._stopping
            if (idle and self.cfg.serve_idle_maintain
                    and self.ex.maintenance_due()):
                self._maintain(idle=True)

    def drain(self, timeout: float = 60.0):
        """Process everything queued. With start=False this runs the
        batch former synchronously on the calling thread (then idle
        maintenance) — the deterministic test mode. With a live worker
        it blocks until the queue and in-flight work are empty."""
        if self._thread is not None:
            deadline = time.monotonic() + timeout
            while True:
                with self._cv:
                    self._check_open(closing=True)
                    if not self._q and self._inflight == 0:
                        return
                    self._cv.wait(0.005)
                if time.monotonic() > deadline:
                    raise TimeoutError("scheduler drain timed out")
        self._form_and_run()
        if (self.cfg.serve_idle_maintain and self.ex.maintenance_due()):
            self._maintain(idle=True)

    def close(self):
        """Stop accepting requests, flush the queue, join the worker."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60.0)
            self._thread = None
        else:
            self._form_and_run()         # flush manual-mode leftovers
        if self._pc_started:
            self.ex.stop_precompiler()
            self._pc_started = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- introspection ---------------------------------------------------

    def stats(self) -> dict:
        with self._cv:
            qlen, inflight = len(self._q), self._inflight
        return {
            "submitted": self.submitted,
            "queue_len": qlen,
            "inflight": inflight,
            "reads": self.reads,
            "read_batches": self.read_batches,
            "mean_batch": round(self.reads / max(self.read_batches, 1),
                                2),
            "max_batch": self.max_batch,
            "writes": self.writes,
            "write_merges": self.write_merges,
            "maintain_runs": self.maintain_runs,
            "maintain_busy": self.maintain_busy,
            "width_fallbacks": self.width_fallbacks,
            "queue_wait_ns": self.queue_wait_ns,
            "queue_waits": self.queue_waits,
            "dispatch_ns": self.dispatch_ns,
            "device_wait_ns": self.device_wait_ns,
            "maintain_ns": self.maintain_ns,
            "epoch": self.ex.epoch,
        }
