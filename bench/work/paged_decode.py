"""Work of the paged decode attention kernel (``_decode_gqa_kernel``) for
the tokens it decodes: what the algorithm needs, not what the kernel is
launched with.  A decode step at position ``p`` writes K/V at ``p`` and
attends positions 0..p, so it needs p + 1 keys and values per KV head,
its query and its output; inactive slots and positions past ``p``
(up to ``max_len``) count nothing."""
from __future__ import annotations

import numpy as np


def work(c: dict, pos, kv_bytes: int = 2, q_bytes: int = 2, out_bytes: int = 4):
    """(flops, bytes) over all layers for decode steps of active slots at
    positions ``pos`` (one entry per slot and step)."""
    pos = np.asarray(pos, np.int64)
    L, H = c["num_hidden_layers"], c["num_attention_heads"]
    Hk, hd = c["num_key_value_heads"], c["head_dim"]
    keys = int((pos + 1).sum())
    flops = L * 4 * H * hd * keys
    nbytes = L * (2 * Hk * hd * kv_bytes * keys
                  + pos.size * H * hd * (q_bytes + out_bytes))
    return flops, nbytes
