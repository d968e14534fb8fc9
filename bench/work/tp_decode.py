"""HBM bytes one chip of a tensor-parallel mesh must read for the decode
steps of a dense GQA decoder: what the algorithm needs, not what the
program is launched with.  Each decode execution reads the chip's whole
parameter shard (``param_bytes_per_chip``, the scheduler's counter) once
for all its slots; a slot decoding at position ``p`` reads the keys and
values of positions 0..p of the chip's ``kv_heads / tp`` heads in every
layer.  Inactive slots and positions past ``p`` count nothing."""
from __future__ import annotations

import numpy as np


def hbm_bytes(c: dict, tp: int, param_bytes_per_chip: int, executions: float,
              pos, kv_bytes: int = 2) -> float:
    """Bytes per chip for ``executions`` decode steps that decoded tokens
    at positions ``pos`` (one entry per slot and step)."""
    pos = np.asarray(pos, np.int64)
    L, hd = c["num_hidden_layers"], c["head_dim"]
    kv_heads = c["num_key_value_heads"] // tp
    kv = L * 2 * kv_heads * hd * kv_bytes * int((pos + 1).sum())
    return executions * param_bytes_per_chip + kv
