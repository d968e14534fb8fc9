"""Shared arithmetic of the serving metrics' readers: which tokens the
traced span decoded and which prompts it prefilled, from the run's own
records (token delivery times, prompt lengths, prefix hits), and how the
two attention kernels show in the trace.

The kernels are found by their output shapes, which the configuration
fixes: the paged decode kernel writes f32[slots, kv_heads, group, hd],
the prefix-prefill kernel f32[rows, kv_heads, bucket * group, hd]."""
from __future__ import annotations


def decode_positions(ctx) -> list:
    """Position of each decode step delivered in the traced span: token
    j >= 1 of a request with prompt length P came from the step at
    position P + j - 1."""
    t0, t1 = ctx["t_start"], ctx["t_stop"]
    out = []
    for r in ctx["records"]:
        P = len(r.ask.prompt)
        out += [P + j - 1 for j, t in enumerate(r.times) if j and t0 <= t <= t1]
    return out


def prefills(ctx) -> tuple[list, list]:
    """(context, tail) lengths of the prompts whose first token came in
    the traced span."""
    t0, t1 = ctx["t_start"], ctx["t_stop"]
    ctxs, tails = [], []
    for r in ctx["records"]:
        if r.times and t0 <= r.times[0] <= t1:
            ctxs.append(r.hit)
            tails.append(len(r.ask.prompt) - r.hit)
    return ctxs, tails


def _dims(ctx):
    c = ctx["config"]
    Hk, hd = c["num_key_value_heads"], c["head_dim"]
    return Hk, c["num_attention_heads"] // Hk, hd


def decode_kernel(ctx) -> str:
    Hk, G, hd = _dims(ctx)
    return rf"custom-call f32\[{ctx['serve']['max_slots']},{Hk},{G},{hd}\]$"


def prefill_kernel(ctx) -> str:
    Hk, G, hd = _dims(ctx)
    s = ctx["serve"]
    rows = "|".join(str(b * G) for b in sorted(set(s["prefill_buckets"] + [s["max_len"]])))
    return rf"custom-call f32\[\d+,{Hk},(?:{rows}),{hd}\]$"
