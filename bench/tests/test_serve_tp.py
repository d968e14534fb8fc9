"""The tensor-parallel serving cell at a size the CPU holds: a smoke run on
four virtual devices is correct, and the two tp readers compute what
their docstrings say from hand-made traces."""
import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

_RUN = r"""
import json, sys
sys.path[:0] = [".", "src"]
from bench import harness, serve_tp
conf = json.load(open("bench/tests/data/mistral-smoke-tp.json"))
mix = json.load(open("bench/tests/data/chat-smoke.json"))
bench = {"per_layer": [], "end_to_end": [{"name": "output_tokens_per_s"}, {"name": "setup_s"}]}
result, compared = serve_tp.run(bench, {"name": "smoke"}, conf, mix, 2 ** 33 + 5, 3.0,
                                False, harness.device_info())
print(json.dumps({"result": result, "compared": compared}))
"""


def test_sound_tp_run_on_four_devices_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _RUN], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["result"]["correct"], rec
    assert rec["result"]["attempted"] > 5 and rec["result"]["failed"] == 0
    assert rec["result"]["device"]["count"] == 4
    assert set(rec["result"]["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert "param_bytes_per_chip" in out.stderr


def _record(prompt_len, times):
    return types.SimpleNamespace(ask=types.SimpleNamespace(prompt=np.zeros(prompt_len)),
                                 times=times, hit=0)


CONFIG = {"num_hidden_layers": 40, "num_attention_heads": 32, "num_key_value_heads": 8,
          "head_dim": 128}
SERVE = {"tp": 4, "max_slots": 64}
DECODE = "closed_call.12 custom-call f32[64,2,4,128]"


def test_hbm_roofline_reads_bytes_over_decode_time():
    reader = harness.load("metrics", "tp.decode.hbm_roofline")
    # Two requests, prompt 100 and 300: in the span [1, 2] s the first
    # delivered tokens 1 and 2 (positions 100, 101), the second token 1
    # (position 300); 4 chips each ran the decode program 2 times for 0.1 s.
    recs = [_record(100, [0.5, 1.2, 1.4]), _record(300, [0.9, 1.5])]
    ctx = {"records": recs, "t_start": 1.0, "t_stop": 2.0, "config": CONFIG,
           "serve": SERVE, "param_bytes_per_chip": 6_000_000_000,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"chips": 4, "modules": {"jit__unknown(7)": [0.8, 8]},
                     "module_kernels": {"jit__unknown(7)": [DECODE]}}}
    kv = 40 * 2 * 2 * 128 * 2 * (101 + 102 + 301)
    want = 100 * (2 * 6e9 + kv) / 819e9 / 0.2
    assert reader.read(ctx) == pytest.approx(want)
    assert reader.read({**ctx, "param_bytes_per_chip": None}) is None
    ctx["trace"]["module_kernels"] = {"jit__unknown(7)": ["custom-call f32[64,8,4,128]"]}
    assert reader.read(ctx) is None          # the whole-model kernel shape is not tp's


class _Event:
    def __init__(self, name, start, duration):
        self.name, self.start_ns, self.duration_ns = name, start, duration


def _plane(name, events):
    line = types.SimpleNamespace(name="XLA Ops", events=events)
    return types.SimpleNamespace(name=name, lines=[line])


def test_collective_share_counts_collective_ops_over_busy(monkeypatch):
    import jax

    planes = [
        _plane("/device:TPU:0", [
            _Event("%fusion.1 = bf16[64,5120] fusion(%a)", 0, 600),
            _Event("%all-reduce.6 = bf16[64,5120] all-reduce(%f)", 600, 100),
            _Event("%psum.2 = bf16[64,1,5120] all-reduce(%g)", 700, 50),
            _Event("%all-gather.5 = bf16[64,131072] all-gather(%h)", 740, 60),
        ]),
        _plane("/device:TPU:1", [
            _Event("%all-reduce-start.1 = bf16[8] all-reduce-start(%x)", 0, 20),
            _Event("%copy.77 = bf16[8] copy(%x)", 20, 500),
        ]),
        _plane("/host:CPU", [_Event("%all-reduce.9 = f32[1] all-reduce(%y)", 0, 10 ** 6)]),
    ]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: types.SimpleNamespace(planes=planes)))
    tp = harness.load("metrics", "_tp")
    # chip 0: 600..800 (the gather overlaps the psum by 10 ns), chip 1: 20
    assert tp.collective_seconds("x") == pytest.approx(220e-9)
    reader = harness.load("metrics", "tp.collective_share")
    ctx = {"xplane": "x", "trace": {"chips": 2, "busy_s": 660e-9}}
    assert reader.read(ctx) == pytest.approx(100 * 220 / 1320)
    assert reader.read({"trace": {"chips": 0, "busy_s": 0.0}}) is None


def test_work_counts_parameter_shard_once_per_step():
    work = harness.load("work", "tp_decode")
    c = {**CONFIG}
    assert work.hbm_bytes(c, 4, 10, 3, [0, 1]) == 3 * 10 + 40 * 2 * 2 * 128 * 2 * 3
    assert work.hbm_bytes(c, 1, 10, 1, []) == 10


def test_tp_cell_traffic_fits_its_configuration():
    """The tp cell's mix at the manifest's window, with a seed above 32
    bits: every request fits ``max_len``, and 64 of the longest fit the
    pool's pages at once."""
    from bench import gen

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == "mistral-nemo-12b.tp4.chat")
    conf = harness.read_json("configs", f"{cell['config']}.json")
    mix = harness.read_json("traffic", f"{cell['traffic']}.json")
    asks = [a for s in gen.generate(mix, 2 ** 33 + 1, manifest["run_seconds"],
                                    conf["config"]["vocab_size"]) for a in s]
    s = conf["serve"]
    sizes = sorted(len(a.prompt) + a.n_tokens for a in asks)
    assert sizes[-1] <= s["max_len"]
    pages = sum(-(-n // s["page_size"]) for n in sizes[-s["max_slots"]:])
    assert pages < s["n_pages"], (pages, s["n_pages"])
