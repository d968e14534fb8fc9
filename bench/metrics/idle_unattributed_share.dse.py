"""Share of the device's idle time in the traced DSE calls over which
the program recorded no ``repro.`` span, in %."""
from bench import spans


def read(ctx):
    s = spans.of_ctx(ctx)
    if not s["spans"] or not s["idle_s"]:
        return None
    return 100.0 * s["unattributed_idle_s"] / s["idle_s"]
