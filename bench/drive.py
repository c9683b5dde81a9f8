"""What the client does with each request of a window, whatever loop
sends it (``bench/loops/<loop>.py``).

Every request is timed from when it was due to be sent to when the
client holds its answer. An answer is a ticket's ``result()``, with one
rule of the serve path's protocol: a materialised window (RangeQuery,
CircleQuery(materialize=True)) whose ``ok`` flag is false was cut at
the tier it ran at (DESIGN.md section 7) and is not the answer. The
client asks again, as often as it must, until a window comes back
whole or the deadline (``LATE_S`` past the window) passes; its latency
counts every round. An answer still cut at the deadline goes to the
check as it is, and the check judges it wrong.

With ``spans`` the harness marks what each thread was doing
(``bench.sleep``, ``bench.submit``, ``bench.wait``) for the trace's
idle-gap labels.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

LATE_S = 60.0           # how long past the window an answer may come


@contextlib.contextmanager
def _nospan(_name):
    yield


def span_fn(spans: bool):
    if not spans:
        return _nospan
    import jax
    return jax.profiler.TraceAnnotation


def materialised(spec) -> bool:
    """Whether ``spec`` answers with a window of ids and an ok flag."""
    return spec.kind == "range" or (spec.kind == "circle"
                                    and spec.materialize)


def window_cut(spec, res) -> bool:
    """Whether ``res`` is a materialised window flagged as cut."""
    return materialised(spec) and not bool(np.asarray(res[2]).all())


class Log:
    """What happened to each request of a window."""

    def __init__(self, reqs, keep):
        n = len(reqs)
        self.reqs = reqs
        self.keep = keep                  # indices whose answer is kept
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.asked = np.zeros(n, np.int64)   # times submitted
        self.error = [None] * n
        self.out = {}
        self.issued = 0
        self.t0 = self.deadline = None

    def start(self, t0: float, seconds: float):
        self.t0, self.deadline = t0, t0 + seconds + LATE_S

    def finish(self, sched, i: int, ticket):
        """Wait for request i's answer, asking again while it is a cut
        window and the deadline has not passed."""
        r = self.reqs[i]
        while True:
            self.asked[i] += 1
            try:
                res = ticket.result(timeout=max(
                    0.0, self.deadline - time.perf_counter()))
                if (window_cut(r.spec, res)
                        and time.perf_counter() < self.deadline):
                    ticket = sched.submit(r.spec, *r.args)
                    continue
            except Exception as e:        # raised, or never came
                self.error[i] = repr(e)
                return
            break
        self.done[i] = time.perf_counter() - self.t0
        if i in self.keep:
            self.out[i] = res

    def answers(self):
        """(request, answer, done) of every kept request that was
        sent."""
        return [(self.reqs[i], self.out.get(i), i in self.out)
                for i in sorted(self.keep) if i < self.issued]
