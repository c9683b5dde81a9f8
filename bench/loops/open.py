"""The open loop: independent users, requests sent at their due times
whatever the system does. Parameter: ``rate``, requests a second.

The gaps are the quantiles of an exponential distribution of mean
1/``rate`` in the seed's order (Poisson arrivals with the same gaps for
every seed), scaled so the last request is due inside the window. One
sender thread submits in order; waiter threads collect the answers, so
a slow answer does not hold up the timing of the others.
"""
import queue
import threading
import time

import numpy as np

WAITERS = 16            # threads collecting answers


def requests(g, seconds, seed):
    rate = float(g.t["rate"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed ^ 0x5EED)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q)) / rate
    due = np.cumsum(gaps)
    due *= seconds / max(due[-1] + gaps.mean(), 1e-9)
    return g.batch(n, seed), due


def widths(engine, traffic, q):
    """The power-of-two widths the scheduler can coalesce requests of q
    queries to, which set-up warms: it appends requests to a batch up
    to its cap (``serve_max_batch``) and pads to a power of two. Below
    the knee a batch stays under ``tier_bucket_min``: wider ones take
    the executor's tier-bucketed path, whose programs (every ladder
    tier times every bucket width) take more than 19 minutes to compile
    on a cold chip and are not warmed; one that forms in the window
    shows in the compile counter."""
    b = lambda n: 1 if n <= 1 else 1 << (n - 1).bit_length()  # noqa
    ws = sorted({b(k * q) for k in range(1, -(-engine.serve_max_batch
                                               // q) + 1)})
    if q < engine.tier_bucket_min:
        ws = [w for w in ws if w < engine.tier_bucket_min]
    return ws


def drive(sched, reqs, due, seconds, log, span):
    """Send ``reqs`` at ``due``; return the window's start once every
    answer is in or the log's deadline has passed."""
    work = queue.SimpleQueue()
    t0 = time.perf_counter()
    log.start(t0, seconds)
    log.due[:] = due

    def waiter():
        while True:
            item = work.get()
            if item is None:
                return
            with span("bench.wait"):
                log.finish(sched, *item)

    pool = [threading.Thread(target=waiter, daemon=True)
            for _ in range(WAITERS)]
    for th in pool:
        th.start()
    with span("bench.window"):
        for i, r in enumerate(reqs):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                with span("bench.sleep"):
                    time.sleep(wait)
            with span("bench.submit"):
                log.sent[i] = time.perf_counter() - t0
                try:
                    ticket = sched.submit(r.spec, *r.args)
                except Exception as e:
                    log.error[i] = repr(e)
                    continue
            work.put((i, ticket))
            log.issued = i + 1
        # the window closes at ``seconds``; answers may still come in
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
    for _ in pool:
        work.put(None)
    for th in pool:
        th.join()
    return t0
