"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, device time per XLA program and per device
operation, and the longest idle gaps with what the benchmark's host
annotations say the host was doing in them.

On a TPU the profiler writes one plane per chip (``/device:TPU:<n>``)
with an ``XLA Modules`` line (one event per program execution) and an
``XLA Ops`` line (one event per operation, a loop's body nested inside
the loop's own event).  Op events are named by their HLO text, ``%name =
shape op(...)``; a Pallas kernel is a ``custom-call``, keyed here as
``<name> custom-call <output shape>``.  Programs jitted from a
``functools.partial`` are all named ``jit__unknown(<id>)``, so a program
is also known by the kernels that ran inside its executions.  Busy time
is the union of the op intervals, averaged over the chips that ran
anything.  Event times on all planes count from the start of the trace.
"""
from __future__ import annotations

import bisect
import collections
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
MIN_GAP_NS = 1000          # idle gaps shorter than 1 us are not listed
_SHAPE = re.compile(r"^[a-z0-9]+\[[0-9,]*\]")


def op_key(name: str) -> str:
    """``%closed_call.12 = f32[64,2,8,128]{...} custom-call(...)`` ->
    ``closed_call.12 custom-call f32[64,2,8,128]``; other ops keep only
    their name."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if "custom-call(" in rest:
        m = _SHAPE.match(rest)
        return f"{head} custom-call {m.group(0) if m else '?'}"
    return head


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and not plane_name.startswith(
        ("/device:CPU", "/device:CUSTOM"))


def reduce(path: str, window_s: float, marks=("bench.",), top: int = 10) -> dict:
    """The trace at ``path``, whose traced window lasted ``window_s``
    host seconds, reduced to plain numbers (seconds) and names:

    * ``ops``: op key -> [seconds, count], leaf operations only (a loop
      whose body ran as other events is left out);
    * ``modules``: program name -> [seconds, count];
    * ``module_kernels``: program name -> custom-call keys seen inside it;
    * ``busy_s``, ``window_s``, ``chips``, and ``breakdown`` (top device
      ops, longest idle gaps labelled by the host annotation that
      overlaps them most)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops = collections.defaultdict(lambda: [0.0, 0])
    modules = collections.defaultdict(lambda: [0.0, 0])
    module_kernels = collections.defaultdict(set)
    busy, spans, chips = [], [], 0
    for plane in data.planes:
        if not _is_device(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(marks):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
            continue
        op_events, mod_events = [], []
        for line in plane.lines:
            if line.name == OPS:
                op_events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                             for ev in line.events]
            elif line.name == MODULES:
                mod_events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                              for ev in line.events]
        if not op_events:
            continue
        chips += 1
        op_events.sort(key=lambda e: (e[0], -e[1]))
        busy.append(_union([(s, e) for s, e, _ in op_events]))
        kernels = []                       # (start, key) of custom-calls
        for i, (s, e, name) in enumerate(op_events):
            key = op_key(name)
            if " custom-call " in key:
                kernels.append((s, key))
            parent = i + 1 < len(op_events) and op_events[i + 1][0] < e
            if not parent:
                a = ops[key]
                a[0] += (e - s) * 1e-9
                a[1] += 1
        starts = [k[0] for k in kernels]
        for s, e, name in mod_events:
            a = modules[name]
            a[0] += (e - s) * 1e-9
            a[1] += 1
            lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
            module_kernels[name].update(k for _, k in kernels[lo:hi])
    busy_ns = [sum(e - s for s, e in u) for u in busy]
    gaps = []
    if busy:
        end_ns = window_s * 1e9
        edges = [[-1.0, 0.0]] + busy[0] + [[end_ns, end_ns + 1]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a >= MIN_GAP_NS:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    labelled = []
    for length, a, b in gaps[:top]:
        over = collections.Counter()
        for s, e, name in spans:
            o = min(b, e) - max(a, s)
            if o > 0:
                over[name] += o
        label = over.most_common(1)[0][0] if over else "host without a benchmark span"
        labelled.append([label, length * 1e-9])
    return {
        "window_s": window_s,
        "busy_s": (sum(busy_ns) / len(busy_ns)) * 1e-9 if busy_ns else 0.0,
        "chips": chips,
        "ops": dict(ops),
        "modules": dict(modules),
        "module_kernels": {k: sorted(v) for k, v in module_kernels.items()},
        "breakdown": {
            "device_ops": [[k, v[0]] for k, v in
                           sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]],
            "idle_gaps": labelled,
        },
    }


def kernel_time(reduced: dict, pattern: str) -> tuple[float, int]:
    """(seconds, count) of the ops whose key matches ``pattern``."""
    rx = re.compile(pattern)
    s, n = 0.0, 0
    for key, (sec, cnt) in reduced["ops"].items():
        if rx.search(key):
            s += sec
            n += cnt
    return s, n


def program_time(reduced: dict, pattern: str) -> tuple[float, int]:
    """(seconds, executions) of the programs whose name matches
    ``pattern`` or inside which a kernel whose key matches it ran."""
    rx = re.compile(pattern)
    s, n = 0.0, 0
    for name, (sec, cnt) in reduced["modules"].items():
        if rx.search(name) or any(rx.search(k) for k in reduced["module_kernels"].get(name, ())):
            s += sec
            n += cnt
    return s, n
