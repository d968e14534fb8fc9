"""Host time deduplicating the scenarios' archives (the
``repro.nsga2.dedup`` span: one dedup of every archive's rows through a
packed integer key, its ``packed`` stat the rows that took that path)
per ``run_batched`` call in the traced span, in ms."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "repro.nsga2.dedup")
