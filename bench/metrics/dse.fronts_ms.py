"""Time of the per-scenario front extraction (the ``repro.nsga2.fronts``
span: each scenario's final ranks and archive front, with their
dispatches and transfers) per ``run_batched`` call in the traced span,
in ms."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "repro.nsga2.fronts")
