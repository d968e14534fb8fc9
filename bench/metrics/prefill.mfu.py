"""Model FLOPs of the prompt tokens prefilled in the traced span that
were not served from cache (tail queries attending [context ; causal
tail]) over the prefill programs' device time, as a share of the chip's
bf16 peak.  The prefill programs are those the prefix-prefill kernel
runs in."""
import numpy as np

from bench import harness, trace


def read(ctx):
    s = harness.load("metrics", "_serve")
    seconds, _ = trace.program_time(ctx["trace"], s.prefill_kernel(ctx))
    ctxs, tails = s.prefills(ctx)
    if not seconds or not tails:
        return None
    c, t = np.asarray(ctxs), np.asarray(tails)
    pairs = int((t * c + t * (t + 1) // 2).sum())
    flops = harness.load("work", "model").flops(ctx["config"], t.sum(), pairs)
    return 100.0 * flops / seconds / ctx["peaks"]["bf16_flops"]
