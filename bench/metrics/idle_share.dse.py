"""Share of the traced DSE calls in which no operation ran on the
device, in %."""


def read(ctx):
    t = ctx["trace"]
    if not t["chips"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
