"""Keep of a profiler trace only what ``bench/trace.py`` reads: the
``XLA Ops`` line of each TPU plane and the harness's ``bench.*`` host
spans, events unchanged. Makes the committed test trace small:

    python3 bench/fixtures/slim_trace.py full.xplane.pb slim.xplane.pb
    gzip -9 slim.xplane.pb

Needs the XSpace protobuf module that ships with TensorFlow.
"""
import sys
from tensorflow.tsl.profiler.protobuf import xplane_pb2
src, dst = sys.argv[1], sys.argv[2]
xs = xplane_pb2.XSpace(); xs.ParseFromString(open(src, "rb").read())
out = xplane_pb2.XSpace()
for pl in xs.planes:
    if pl.name.startswith("/device:TPU:"):
        keep = [l for l in pl.lines if l.name == "XLA Ops"]
    elif pl.name.startswith("/host:CPU"):
        keep = []
        for l in pl.lines:
            evs = [e for e in l.events if pl.event_metadata[e.metadata_id].name.startswith("bench.")]
            if evs:
                nl = xplane_pb2.XLine(); nl.CopyFrom(l); del nl.events[:]; nl.events.extend(evs); keep.append(nl)
    else:
        continue
    np_ = out.planes.add(); np_.CopyFrom(pl); del np_.lines[:]; np_.lines.extend(keep)
    # drop metadata not referenced
    used = {e.metadata_id for l in keep for e in l.events}
    for k in list(np_.event_metadata.keys()):
        if k not in used: del np_.event_metadata[k]
data = out.SerializeToString()
open(dst, "wb").write(data); print(len(data))
