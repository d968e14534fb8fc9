"""Work of the prefix-prefill attention kernel (``_prefix_kernel``) for
the prompts it fills: each row's ``ctx`` positions served from cached
pages are read once as context; its ``tail`` new positions are projected
queries, keys and values, and query t attends ctx + t + 1 positions.
Padding rows, bucket padding and context capacity past ``ctx`` count
nothing."""
from __future__ import annotations

import numpy as np


def work(c: dict, ctx, tail, kv_bytes: int = 2, q_bytes: int = 2, out_bytes: int = 4):
    """(flops, bytes) over all layers for prefill rows with context
    lengths ``ctx`` and tail lengths ``tail``."""
    ctx = np.asarray(ctx, np.int64)
    tail = np.asarray(tail, np.int64)
    L, H = c["num_hidden_layers"], c["num_attention_heads"]
    Hk, hd = c["num_key_value_heads"], c["head_dim"]
    pairs = int((tail * ctx + tail * (tail + 1) // 2).sum())
    flops = L * 4 * H * hd * pairs
    kv = int((ctx + tail).sum())
    nbytes = L * (2 * Hk * hd * kv_bytes * kv
                  + int(tail.sum()) * H * hd * (q_bytes + out_bytes))
    return flops, nbytes
