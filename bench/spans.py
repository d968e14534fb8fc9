"""Reduce the program's own spans in a profiler trace (``.xplane.pb``):
the ``jax.profiler.TraceAnnotation`` events whose names start with
``repro.``, which the program records on its calling thread.

For each span name: total seconds, self seconds (each span's duration
less the part of it that its child spans cover), count, and its stats
summed (``TraceAnnotation`` keyword arguments, and ``set_metadata``,
come back as event stats).  Spans on one host line nest; a child is a
span that lies inside another on the same line.

Device idle time is the part of the traced window in which no operation
ran on the first chip that ran any, with the busy union, chip and time
origin of ``bench/trace.py`` (events count from the start of the trace,
the window ends ``window_s`` after it).  Each idle stretch is put down
to the innermost span over it; what no span covers is left
unattributed.

The readers take the reduction from their ``ctx`` (``of_ctx``): the
cell's trace is the one ``harness.profiled`` wrote under
``.bench_trace/<kind>``, and the traced window is ``ctx["trace"]``'s.
"""
from __future__ import annotations

import bisect
import collections
import glob
import numbers

from bench import harness, trace

PREFIX = "repro."
TRACES = harness.ROOT / ".bench_trace"


def _overlap(a: float, b: float, intervals, ends) -> float:
    """Length of ``[a, b]`` that the sorted, disjoint ``intervals`` (with
    ``ends`` their end points) cover."""
    total = 0.0
    for s, e in intervals[bisect.bisect_right(ends, a):]:
        if s >= b:
            break
        total += min(b, e) - max(a, s)
    return total


def _nest(events):
    """``events`` of one line, as (start, end, name, stats), each with the
    intervals of its direct children: [(start, end, name, stats, kids)]."""
    out, stack = [], []
    for s, e, name, stats in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        node = (s, e, name, stats, [])
        if stack and e <= stack[-1][1]:
            stack[-1][4].append((s, e))
        out.append(node)
        stack.append(node)
    return out


def reduce(path: str, window_s: float) -> dict:
    """The trace at ``path``, whose traced window lasted ``window_s`` host
    seconds, reduced to plain numbers (seconds):

    * ``spans``: span name -> ``seconds``, ``self_s``, ``count``,
      ``stats`` (summed numeric stats) and ``idle_s`` (device idle time
      with this span the innermost over it);
    * ``idle_s``: all device idle time in the window, and
      ``unattributed_idle_s``: the part no span covers.  Both are None
      where the trace holds no device operations."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    busy, lines = None, []
    for plane in data.planes:
        if trace._is_device(plane.name):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == trace.OPS for ev in line.events]
            if busy is None and ops:
                busy = trace._union(ops)
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                lines.append(_nest(evs))

    idle = []
    if busy is not None:
        end_ns = window_s * 1e9
        edges = [[-1.0, 0.0]] + busy + [[end_ns, end_ns + 1]]
        idle = [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]
    idle_ends = [e for _, e in idle]

    spans = collections.defaultdict(
        lambda: {"seconds": 0.0, "self_s": 0.0, "count": 0,
                 "stats": collections.Counter(), "idle_s": 0.0})
    covered = []
    for nodes in lines:
        for s, e, name, stats, kids in nodes:
            a = spans[name]
            a["count"] += 1
            a["seconds"] += (e - s) * 1e-9
            a["stats"].update({k: v for k, v in stats.items()
                               if isinstance(v, numbers.Number)})
            kids = trace._union(kids)
            a["self_s"] += (e - s - sum(ke - ks for ks, ke in kids)) * 1e-9
            bounds = [[s, s]] + kids + [[e, e]]
            a["idle_s"] += 1e-9 * sum(_overlap(x, y, idle, idle_ends)
                                      for (_, x), (y, _) in zip(bounds, bounds[1:]) if y > x)
            covered.append((s, e))
    idle_s = unattributed = None
    if busy is not None:
        idle_s = sum(b - a for a, b in idle) * 1e-9
        unattributed = idle_s - 1e-9 * sum(
            _overlap(s, e, idle, idle_ends) for s, e in trace._union(covered))
    return {
        "spans": {k: {**v, "stats": dict(v["stats"])} for k, v in spans.items()},
        "idle_s": idle_s,
        "unattributed_idle_s": unattributed,
    }


def of_ctx(ctx: dict) -> dict:
    """The reduction of the cell's trace, kept in ``ctx["spans"]`` for the
    next reader; no spans and no idle time where there is no trace."""
    if "spans" not in ctx:
        found = sorted(glob.glob(str(TRACES / ctx["kind"] / "**" / "*.xplane.pb"),
                                 recursive=True))
        ctx["spans"] = (reduce(found[-1], ctx["trace"]["window_s"]) if found else
                        {"spans": {}, "idle_s": None, "unattributed_idle_s": None})
    return ctx["spans"]


def per_call_ms(ctx: dict, name: str) -> float | None:
    """Seconds of span ``name`` per traced call, in ms; None where the
    trace holds no such span."""
    span = of_ctx(ctx)["spans"].get(name)
    return 1e3 * span["seconds"] / ctx["calls"] if span else None
