"""Print what a profiler trace holds: planes, lines, the busiest event
names with their counts and seconds, and the stats of one event of each
name.  For looking at a trace by hand before writing code against it.

    python bench/tools/tracedump.py <file.xplane.pb> [out.json]
"""
from __future__ import annotations

import collections
import json
import sys

import jax


def dump(path: str, top: int = 40) -> list:
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            cnt, dur, ex = collections.Counter(), collections.Counter(), {}
            for ev in line.events:
                cnt[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if ev.name not in ex and len(ex) < top:
                    ex[ev.name] = {k: str(v)[:300] for k, v in ev.stats}
            lines.append({"line": line.name, "events": sum(cnt.values()),
                          "top": [[k, cnt[k], dur[k] * 1e-9]
                                  for k, _ in dur.most_common(top)],
                          "examples": ex})
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    d = dump(sys.argv[1])
    text = json.dumps(d, indent=1, default=str)
    if len(sys.argv) > 2:
        open(sys.argv[2], "w").write(text)
    for p in d:
        print(p["plane"], [(ln["line"], ln["events"]) for ln in p["lines"]][:12])
