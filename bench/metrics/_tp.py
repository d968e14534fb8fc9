"""Shared arithmetic of the tensor-parallel serving readers: the paged
decode kernel as one chip of the mesh runs it, and the device time of
collective operations.

Under ``Scheduler(tp=N)`` each chip runs the paged decode kernel on its
own KV heads, so the kernel writes f32[slots, kv_heads / N, group, hd].
A collective is known by its HLO op in the op event's text (``%name =
shape all-reduce(...)``, or an async ``-start``/``-done`` half): a
shard_map ``psum`` keeps its own name but is an ``all-reduce``."""
from __future__ import annotations

import re

from bench import trace

COLLECTIVE = re.compile(
    r"(?:^%?|\s)(?:all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start|-done)?[.(\s]")


def decode_kernel(ctx) -> str:
    c, s = ctx["config"], ctx["serve"]
    Hk, hd = c["num_key_value_heads"], c["head_dim"]
    G = c["num_attention_heads"] // Hk
    return rf"custom-call f32\[{s['max_slots']},{Hk // s['tp']},{G},{hd}\]$"


def collective_seconds(path: str) -> float:
    """Device seconds in which a collective op ran, summed over the
    chips of the trace at ``path`` (per chip, the union of the
    collective op events, so a nested or overlapping pair counts once)."""
    import jax

    total = 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not trace._is_device(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace.OPS:
                spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if COLLECTIVE.search(ev.name)]
                total += sum(e - s for s, e in trace._union(spans))
    return total * 1e-9
