"""Reduction of a profiler trace (``.xplane.pb``) to the device's busy
and idle time, the time in Pallas kernels, the top device operations,
and the idle gaps labelled by what the harness's host spans were doing.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. Busy time is the union of their intervals
inside the traced window (the host span ``bench.window``), averaged
over the chips. A Pallas kernel is an operation whose HLO is a
``tpu_custom_call`` (read from the event's stats where the trace gives
them, else from the op's name). An idle gap is a stretch of the window
in which no operation runs; it is labelled by the harness span open at
its middle, by priority: a ticket in flight (``bench.wait``: the
request is inside the program, on the host), a submission
(``bench.submit``), no request due (``bench.sleep``), else ``other``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

LABELS = (("bench.wait", "ticket in flight"),
          ("bench.submit", "submitting"),
          ("bench.sleep", "no request due"))


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(iv):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _is_kernel(ev) -> bool:
    name = ev.name
    if "custom-call" in name or "tpu_custom_call" in name:
        return True
    try:
        for k, v in ev.stats:
            if isinstance(v, str) and "tpu_custom_call" in v:
                return True
    except Exception:
        pass
    return False


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def short(name: str) -> str:
    """``<opcode> <instruction>`` of an op event named by its HLO text
    (``%knn_topk.6 = (...) custom-call(...)`` -> ``custom-call
    %knn_topk.6``)."""
    head, sep, rest = name.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    return f"{m.group(1)} {head}" if m else name[:80]


def load(path: str):
    """{'device': {plane: [(start_ns, end_ns, name, kernel)]},
    'host': [(start_ns, end_ns, name)]} of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((s, s + float(ev.duration_ns),
                                short(ev.name), _is_kernel(ev)))
            dev[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = float(ev.start_ns)
                        host.append((s, s + float(ev.duration_ns),
                                     ev.name))
    return {"device": dev, "host": host}


def reduce(tr: dict) -> dict:
    """busy_s, window_s, kernel_s, top device ops and labelled idle
    gaps of a loaded trace, inside its ``bench.window`` span."""
    wins = [(s, e) for s, e, n in tr["host"] if n == "bench.window"]
    if not wins:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = wins[0]
    window_ns = w1 - w0
    planes = tr["device"]
    if not planes:
        raise ValueError("trace holds no TPU device plane")
    busy_ns, kernel_ns, ops, gaps = 0.0, 0.0, {}, []
    for evs in planes.values():
        clip = [(max(s, w0), min(e, w1), n, k) for s, e, n, k in evs
                if e > w0 and s < w1]
        u = _union([(s, e) for s, e, _, _ in clip])
        busy_ns += sum(e - s for s, e in u)
        # kernel time: union of kernel intervals (nested ops count once)
        kernel_ns += sum(e - s for s, e in
                         _union([(s, e) for s, e, _, k in clip if k]))
        for s, e, n, _ in clip:
            ops[n] = ops.get(n, 0.0) + (e - s)
        prev = w0
        for s, e in u + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    nd = len(planes)
    spans = {tag: _union([(s, e) for s, e, n in tr["host"] if n == tag])
             for tag, _ in LABELS}
    starts = {tag: [s for s, _ in u] for tag, u in spans.items()}
    by_label = {}
    for s, e in gaps:
        mid = (s + e) / 2
        label = "other"
        for tag, name in LABELS:
            i = bisect.bisect_right(starts[tag], mid) - 1
            if i >= 0 and spans[tag][i][1] >= mid:
                label = name
                break
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns / nd / 1e9,
        "window_s": window_ns / 1e9,
        "kernel_s": kernel_ns / nd / 1e9,
        "device_ops": [[n, t / nd / 1e9] for n, t in top],
        "idle_gaps": [[n, t / nd / 1e9] for n, t in
                      sorted(by_label.items(), key=lambda kv: -kv[1])][:10],
        "gaps": len(gaps),
    }
