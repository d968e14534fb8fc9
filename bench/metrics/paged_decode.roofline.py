"""The paged decode attention kernel's share of its roofline: the least
time the chip needs for the live work of the decode steps in the traced
span (``bench/work/paged_decode.py``), the larger of FLOPs over peak and
bytes over bandwidth, over the kernel's device time."""
from bench import harness, trace


def read(ctx):
    s = harness.load("metrics", "_serve")
    seconds, _ = trace.kernel_time(ctx["trace"], s.decode_kernel(ctx))
    pos = s.decode_positions(ctx)
    if not seconds or not pos:
        return None
    flops, nbytes = harness.load("work", "paged_decode").work(ctx["config"], pos)
    pk = ctx["peaks"]
    return 100.0 * max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]) / seconds
