"""Share of the device's busy time in the traced serving span spent in
collective operations (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all and their async halves), both summed over
the chips of the tensor-parallel mesh, in %."""
from bench import harness


def read(ctx):
    t = ctx["trace"]
    if not t["chips"] or not t["busy_s"] or "xplane" not in ctx:
        return None
    seconds = harness.load("metrics", "_tp").collective_seconds(ctx["xplane"])
    return 100.0 * seconds / (t["busy_s"] * t["chips"])
