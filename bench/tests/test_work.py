"""Kernel work functions count live work from the run's records, and the
peaks table knows only the devices it lists."""
import json
import pathlib

import numpy as np
import pytest

from bench import harness

CONF = json.loads((pathlib.Path(__file__).resolve().parents[1]
                   / "configs" / "qwen2.5-3b.json").read_text())["config"]


def test_decode_counts_live_positions_not_max_len():
    work = harness.load("work", "paged_decode").work
    pos = np.array([0, 15, 16, 700, 2047])       # five active slots of 64
    flops, nbytes = work(CONF, pos)
    L, H, Hk, hd = 36, 16, 2, 128
    keys = int((pos + 1).sum())
    assert keys == 1 + 16 + 17 + 701 + 2048
    assert flops == L * 4 * H * hd * keys
    assert nbytes == L * (2 * Hk * hd * 2 * keys + pos.size * H * hd * (2 + 4))
    padded = L * 2 * Hk * hd * 2 * 64 * 2048     # slots x max_len, as launched
    assert nbytes < padded / 10


def test_prefill_counts_tail_over_context():
    work = harness.load("work", "prefix_prefill").work
    flops, nbytes = work(CONF, ctx=[0, 1024], tail=[700, 64])
    L, H, Hk, hd = 36, 16, 2, 128
    pairs = 700 * 701 // 2 + (64 * 1024 + 64 * 65 // 2)
    assert flops == L * 4 * H * hd * pairs
    assert nbytes == L * (2 * Hk * hd * 2 * (700 + 1024 + 64) + (700 + 64) * H * hd * 6)


def test_model_flops_of_qwen():
    model = harness.load("work", "model")
    assert model.matmul_params(CONF) == 3_085_697_024
    assert model.flops(CONF, 1, 0) == 2 * 3_085_697_024


def test_peaks_reject_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v4")
