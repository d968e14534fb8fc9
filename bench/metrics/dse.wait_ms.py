"""Host time blocked on the batched program and its copy back to the
host (the ``repro.nsga2.wait`` span) per ``run_batched`` call in the
traced span, in ms."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "repro.nsga2.wait")
