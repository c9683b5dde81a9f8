"""Reduction of a profiler trace (``.xplane.pb``) by the program's own
spans: where the device's idle time falls on the serve path, and how
many eager launches ride along with each compiled LiLIS program.

    python3 bench/spans.py run.xplane.pb     # the reduction, as JSON

The program marks its layers with ``lilis.*`` spans
(``src/repro/core/obs.py``; the list is in DESIGN.md §15) and names
every compiled program ``jit_lilis_<...>``. From one trace this keeps:

  - the ``lilis.*`` host events, grouped by the trace line (thread)
    that holds them; the scheduler's worker is the line with the most
    ``lilis.sched.dispatch`` spans;
  - the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane (busy time)
    and its ``XLA Modules`` line (one event per program launch);
  - the harness's ``bench.window`` span, which bounds the window.

``idle_by_span`` puts each idle gap of the window (a stretch in which
no operation runs on a chip) under one label: the innermost ``lilis.*``
span open on the worker at the gap's middle, else ``worker outside
spans``; the labels add up to window - busy. ``idle_in_dispatch_share``
is the device-idle time inside the worker's dispatch spans, in percent
of the window. ``eager_launches_per_dispatch`` divides the window's
launches of programs not named ``jit_lilis_*`` (eager ops: key
encodes, concatenations, slices) by those that are. Times are averaged
over the chips, like ``bench/trace.py``'s. A trace of a program without
these spans reduces to one label and no launch ratio.
"""
from __future__ import annotations

import bisect
import json
import sys

DISPATCH = "lilis.sched.dispatch"
OUTSIDE = "worker outside spans"
PROGRAM = "jit_lilis_"


def load(path: str) -> dict:
    """{'window': (start_ns, end_ns), 'lines': {line: [(start_ns,
    end_ns, name)]}, 'ops': {plane: [(start_ns, end_ns)]}, 'modules':
    {plane: [(start_ns, end_ns, name)]}} of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lines, ops, modules, wins = {}, {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [(float(ev.start_ns),
                        float(ev.start_ns) + float(ev.duration_ns),
                        ev.name) for ev in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = [(s, e) for s, e, _ in evs]
                else:
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    s = float(ev.start_ns)
                    iv = (s, s + float(ev.duration_ns), ev.name)
                    if ev.name.startswith("lilis."):
                        lines.setdefault(f"{plane.name}#{i}", []).append(iv)
                    elif ev.name == "bench.window":
                        wins.append(iv[:2])
    if not wins:
        raise ValueError("trace holds no bench.window span")
    if not ops:
        raise ValueError("trace holds no TPU device plane")
    return {"window": wins[0], "lines": lines, "ops": ops,
            "modules": modules}


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def innermost(spans) -> list:
    """Non-overlapping (start, end, name) segments of one thread's
    nested spans: at each instant, the innermost span open."""
    segs, stack, t = [], [], 0.0

    def close(upto):
        nonlocal t
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for s, e, n in sorted(spans, key=lambda v: (v[0], -v[1])):
        close(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        t = s
        stack.append((e, n))
    close(float("inf"))
    return segs


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(tr: dict) -> dict:
    """Idle time by span, idle time inside dispatches and launches per
    compiled program of a loaded trace, inside its window."""
    w0, w1 = tr["window"]
    window = w1 - w0
    lines = tr["lines"]
    worker = max(lines, default=None, key=lambda k: sum(
        1 for _, _, n in lines[k] if n == DISPATCH))
    wspans = lines.get(worker, [])
    if not any(n == DISPATCH for _, _, n in wspans):
        wspans = []
    segs = innermost(wspans)
    starts = [s for s, _, _ in segs]
    disp = _union([(max(s, w0), min(e, w1)) for s, e, n in wspans
                   if n == DISPATCH and e > w0 and s < w1])
    nd = len(tr["ops"])
    busy = idle_disp = 0.0
    by_label = {}
    for evs in tr["ops"].values():
        u = _union([(max(s, w0), min(e, w1)) for s, e in evs
                    if e > w0 and s < w1])
        busy += sum(e - s for s, e in u)
        gaps, prev = [], w0
        for s, e in u + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        idle_disp += _overlap(gaps, disp)
        for s, e in gaps:
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            label = segs[i][2] if i >= 0 and segs[i][1] > mid else OUTSIDE
            by_label[label] = by_label.get(label, 0.0) + (e - s)
    worker_by_span = {}
    for s, e, n in segs:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            worker_by_span[n] = worker_by_span.get(n, 0.0) + (e - s)
    lilis = eager = 0
    for evs in tr["modules"].values():
        for s, _e, n in evs:
            if w0 <= s < w1:
                if n.startswith(PROGRAM):
                    lilis += 1
                else:
                    eager += 1
    dispatches = sum(1 for s, _, n in wspans if n == DISPATCH
                     and w0 <= s < w1)
    return {
        "window_s": window / 1e9,
        "busy_s": busy / nd / 1e9,
        "idle_by_span": [[n, t / nd / 1e9] for n, t in
                         sorted(by_label.items(), key=lambda kv: -kv[1])],
        "idle_in_dispatch_share": (100.0 * idle_disp / nd / window
                                   if disp else None),
        "worker_by_span": [[n, t / 1e9] for n, t in sorted(
            worker_by_span.items(), key=lambda kv: -kv[1])],
        "dispatches": dispatches,
        "lilis_launches": lilis // nd,
        "eager_launches": eager // nd,
        "eager_launches_per_dispatch": eager / lilis if lilis else None,
    }


if __name__ == "__main__":
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
