"""Decode tokens delivered per decode program execution in the traced
span: the mean number of active slots a step carried."""
from bench import harness, trace


def read(ctx):
    s = harness.load("metrics", "_serve")
    _, n = trace.program_time(ctx["trace"], s.decode_kernel(ctx))
    return len(s.decode_positions(ctx)) / n if n else None
