"""Host time deduplicating the scenarios' archives (the
``repro.nsga2.dedup`` span, ``np.unique`` by row) per ``run_batched``
call in the traced span, in ms."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "repro.nsga2.dedup")
