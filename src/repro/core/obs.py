"""Spans and counters of the serve path (DESIGN.md §15).

One helper, ``span``: a ``jax.profiler.TraceAnnotation`` (so the span
lands in the profiler's own host trace, on the clock of the device
planes) that, when given a counter, also adds its elapsed
``time.perf_counter_ns()`` to that counter. Counters are plain ints on
the object that owns the layer; the caller updates them on the thread
(or under the lock) that already owns that object. With no profiler
running a span makes no TraceMe at all: it costs the profiler's
``is_enabled`` check, and two clock reads when it feeds a counter. A
span opened before the profiler starts is not recorded.

Span names are stable and start with ``lilis.``. Metadata values are
ints or short constant strings, never formatted on the hot path; the
keys in use: ``batch`` (read-batch sequence number), ``requests``,
``queries``, ``width``, ``spec``, ``ticket`` (first ticket id),
``program``, ``disk``, ``thread``, ``idle``.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

_now = time.perf_counter_ns
_tracing = TraceAnnotation.is_enabled


class span:
    """``with span("lilis.sched.dispatch", (sched, "dispatch_ns"),
    batch=7):`` -- a profiler span, plus its elapsed nanoseconds added
    to ``sched.dispatch_ns`` when a counter ``(owner, attribute)`` is
    given. ``set(**meta)`` adds metadata known only inside the span."""

    __slots__ = ("_ann", "_counter", "_t0")

    def __init__(self, name: str, counter=None, **meta):
        self._ann = TraceAnnotation(name, **meta) if _tracing() else None
        self._counter = counter

    def set(self, **meta) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**meta)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._counter is not None:
            self._t0 = _now()
        return self

    def __exit__(self, *exc):
        if self._counter is not None:
            owner, attr = self._counter
            setattr(owner, attr, getattr(owner, attr) + _now() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False
