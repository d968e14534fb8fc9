"""``chip_smoke.py`` off the chip: the script refuses a host without a
TPU, and its phase functions run at smoke configs with the kernels in
interpret mode (the four-chip phase on four virtual CPU devices)."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

from repro import configs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke_module():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_serve_phase_smoke():
    smoke = _smoke_module()
    # The smoke config computes in f32; a cache of the same dtype turns
    # prefix reuse on, as bf16 does at the published config.
    cfg = dataclasses.replace(configs.get_smoke_config("qwen2.5-3b"),
                              cache_dtype="float32")
    rec = smoke.serve_phase(
        cfg, max_slots=4, max_len=64,
        page_size=8, prompt_lens=(24, 20, 12, 22), prefix_len=16,
        n_tokens=4, buckets=(8, 32), interpret=True,
    )
    stats = rec["lockstep_vs_xla"]
    assert stats["token_steps"] == 4 * 4
    assert stats["max_logit_diff"] <= stats["max_tolerance"]


def test_dse_phase_smoke():
    smoke = _smoke_module()
    rec = smoke.dse_phase([("int8", 65536), ("bf16", 32768)], pop_size=16,
                          generations=4, interpret=True)
    assert len(rec["front_sizes"]) == 2


def test_dcim_phase_smoke():
    smoke = _smoke_module()
    rec = smoke.dcim_phase(16, 128, 64, interpret=True)
    assert rec["fp_matmul_rel_err"] < 0.05


_FOUR_CHIP_SNIPPET = r"""
import dataclasses, importlib.util, json
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro import configs
cfg = dataclasses.replace(configs.get_smoke_config("mistral-nemo-12b"),
                          cache_dtype="float32")
rec = smoke.four_chip_phase(cfg, compare_layers=1, max_len=64, page_size=8,
                            prompt_lens=(20, 16, 12, 24), prefix_len=8,
                            n_tokens=4, buckets=(16, 32))
print(json.dumps(rec))
"""


def test_four_chip_phase_on_virtual_devices(run_in_8dev_subprocess):
    (rec,) = run_in_8dev_subprocess(_FOUR_CHIP_SNIPPET, n_devices=4)
    assert rec["served_streams_identical"] == "4/4"
    # The counter reads one chip's shards: a quarter of the smoke model's
    # float32 matrices and vocabulary, plus its norms whole.
    cfg = configs.get_smoke_config("mistral-nemo-12b")
    d, ff, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    q, kv = cfg.n_heads * cfg.hd, cfg.n_kv * cfg.hd
    matrices = L * (2 * d * q + 2 * d * kv + 3 * d * ff) + 2 * V * d
    norms = (2 * L + 1) * d
    assert rec["param_bytes_per_chip_full"] == 4 * (matrices // 4 + norms)
    stats = rec["lockstep_tp_vs_tp1"]
    assert stats["max_logit_diff"] <= stats["max_tolerance"]
