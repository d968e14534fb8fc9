"""Cut a profiler trace down to a small file for the reduction's tests:
keep the events that start in the first ``ms`` milliseconds and the
planes whose names start with the given prefixes.

    python bench/tools/trim_trace.py <in.xplane.pb> <out.xplane.pb> <ms> [prefix ...]
"""
from __future__ import annotations

import sys


def trim(src: str, dst: str, ms: float, prefixes=("/device:", "/host:CPU")) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = [p for p in space.planes if p.name.startswith(tuple(prefixes))]
    del space.planes[:]
    limit_ps = int(ms * 1e9)
    for p in keep:
        for line in p.lines:
            evs = [e for e in line.events if e.offset_ps < limit_ps]
            del line.events[:]
            line.events.extend(evs)
        used = {e.metadata_id for line in p.lines for e in line.events}
        for k in [k for k in p.event_metadata if k not in used]:
            del p.event_metadata[k]
        space.planes.append(p)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], float(sys.argv[3]), tuple(sys.argv[4:]) or
         ("/device:", "/host:CPU"))
