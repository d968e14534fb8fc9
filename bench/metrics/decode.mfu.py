"""Model FLOPs of the decode tokens delivered in the traced span (every
matmul weight twice, plus attention over each token's live context) over
the decode program's device time, as a share of the chip's bf16 peak.
The decode program is the one the paged decode kernel runs in."""
import numpy as np

from bench import harness, trace


def read(ctx):
    s = harness.load("metrics", "_serve")
    seconds, _ = trace.program_time(ctx["trace"], s.decode_kernel(ctx))
    pos = np.asarray(s.decode_positions(ctx))
    if not seconds or not pos.size:
        return None
    flops = harness.load("work", "model").flops(ctx["config"], pos.size, (pos + 1).sum())
    return 100.0 * flops / seconds / ctx["peaks"]["bf16_flops"]
