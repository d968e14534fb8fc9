"""The prefix-prefill attention kernel's share of its roofline: the
least time the chip needs for the live work of the prompts prefilled in
the traced span (``bench/work/prefix_prefill.py``) over the kernel's
device time."""
from bench import harness, trace


def read(ctx):
    s = harness.load("metrics", "_serve")
    seconds, _ = trace.kernel_time(ctx["trace"], s.prefill_kernel(ctx))
    ctxs, tails = s.prefills(ctx)
    if not seconds or not tails:
        return None
    flops, nbytes = harness.load("work", "prefix_prefill").work(ctx["config"], ctxs, tails)
    pk = ctx["peaks"]
    return 100.0 * max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]) / seconds
