"""Jitted programs launched per ``run_batched`` call in the traced span:
the ``programs`` stat of the ``repro.nsga2.run_batched`` span, which the
program counts where it dispatches each."""
from bench import spans


def read(ctx):
    span = spans.of_ctx(ctx)["spans"].get("repro.nsga2.run_batched")
    if not span or "programs" not in span["stats"]:
        return None
    return span["stats"]["programs"] / ctx["calls"]
