"""Keep of a profiler trace only what ``bench/spans.py`` and
``bench/trace.py`` read: the ``XLA Ops`` and ``XLA Modules`` lines of
each TPU plane, and the ``bench.*`` and ``lilis.*`` host spans, each
event kept only if it overlaps the ``bench.window`` span. Host events
are unchanged; device events keep their names and times and lose their
stats (op costs, source lines), which neither reduction reads. Makes
the committed test trace small:

    python3 bench/fixtures/slim_spans.py full.xplane.pb slim.xplane.pb
    gzip -9 slim.xplane.pb

Needs the XSpace protobuf module that ships with TensorFlow.
"""
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

DEVICE_LINES = ("XLA Ops", "XLA Modules")
HOST_PREFIXES = ("bench.", "lilis.")


def _ps(line, ev):
    """(start, end) of an event in picoseconds on the trace's clock."""
    s = line.timestamp_ns * 1000 + ev.offset_ps
    return s, s + ev.duration_ps


def slim(xs):
    win = None
    for pl in xs.planes:
        if pl.name.startswith("/host:CPU"):
            for line in pl.lines:
                for ev in line.events:
                    if pl.event_metadata[ev.metadata_id].name == \
                            "bench.window":
                        win = _ps(line, ev)
    if win is None:
        raise SystemExit("trace holds no bench.window span")
    out = xplane_pb2.XSpace()
    for pl in xs.planes:
        if pl.name.startswith("/device:TPU:"):
            def want(line, ev):
                return line.name in DEVICE_LINES
        elif pl.name.startswith("/host:CPU"):
            def want(line, ev):
                return pl.event_metadata[ev.metadata_id].name.startswith(
                    HOST_PREFIXES)
        else:
            continue
        keep = []
        for line in pl.lines:
            evs = [ev for ev in line.events if want(line, ev)
                   and _ps(line, ev)[1] > win[0]
                   and _ps(line, ev)[0] < win[1]]
            if evs:
                nl = xplane_pb2.XLine()
                nl.CopyFrom(line)
                del nl.events[:]
                nl.events.extend(evs)
                keep.append(nl)
        np_ = out.planes.add()
        np_.CopyFrom(pl)
        del np_.lines[:]
        np_.lines.extend(keep)
        used = {ev.metadata_id for line in keep for ev in line.events}
        for k in list(np_.event_metadata.keys()):
            if k not in used:
                del np_.event_metadata[k]
        if pl.name.startswith("/device:TPU:"):
            for md in np_.event_metadata.values():
                del md.stats[:]
            for line in np_.lines:
                for ev in line.events:
                    del ev.stats[:]
    return out


if __name__ == "__main__":
    src, dst = sys.argv[1], sys.argv[2]
    xs = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        xs.ParseFromString(f.read())
    data = slim(xs).SerializeToString()
    with open(dst, "wb") as f:
        f.write(data)
    print(len(data))
