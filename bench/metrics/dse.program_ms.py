"""Device time of the batched NSGA-II program (``_run_batched_jit``) per
``run_batched`` call in the traced span, in ms."""
from bench import trace


def read(ctx):
    seconds, n = trace.program_time(ctx["trace"], r"_run_batched_jit")
    return 1e3 * seconds / ctx["calls"] if n else None
