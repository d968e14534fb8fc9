"""The tensor-parallel decode program's share of one chip's HBM
roofline: the bytes one chip must read for the decode steps in the
traced span (``bench/work/tp_decode.py``: its parameter shard once per
execution, and the K/V of the live positions of its KV heads) over the
chip's bandwidth, over the decode program's device time per chip.  The
decode program is the one the per-chip paged decode kernel runs in."""
from bench import harness, trace


def read(ctx):
    param_bytes = ctx.get("param_bytes_per_chip")
    chips = ctx["trace"]["chips"]
    seconds, n = trace.program_time(ctx["trace"], harness.load("metrics", "_tp").decode_kernel(ctx))
    pos = harness.load("metrics", "_serve").decode_positions(ctx)
    if not param_bytes or not chips or not seconds or not pos:
        return None
    nbytes = harness.load("work", "tp_decode").hbm_bytes(
        ctx["config"], ctx["serve"]["tp"], param_bytes, n / chips, pos)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / (seconds / chips)
