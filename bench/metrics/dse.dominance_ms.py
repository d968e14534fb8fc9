"""Device time of the Pallas dominance kernel (``kernels/pareto_rank.py``,
the custom call of ``dominance_matrix_pallas``) per ``run_batched`` call
in the traced span, in ms."""
from bench import trace


def read(ctx):
    seconds, n = trace.kernel_time(ctx["trace"], r"^dominance_matrix_pallas\S* custom-call ")
    return 1e3 * seconds / ctx["calls"] if n else None
