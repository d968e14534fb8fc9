"""The trace reduction: exact arithmetic on a synthetic trace, and the
shape of a real one (a trimmed chip trace committed beside this file)."""
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _synthetic(path):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()

    def plane(name, lines):
        p = space.planes.add(id=len(space.planes), name=name)
        names = {}
        for lname, events in lines:
            line = p.lines.add(id=len(p.lines), name=lname)
            for ev_name, start_us, dur_us in events:
                if ev_name not in names:
                    names[ev_name] = len(names) + 1
                    p.event_metadata[names[ev_name]].id = names[ev_name]
                    p.event_metadata[names[ev_name]].name = ev_name
                line.events.add(metadata_id=names[ev_name], offset_ps=start_us * 10 ** 6,
                                duration_ps=dur_us * 10 ** 6)

    # Device: a decode program 0-40 us whose loop (0-40) holds an op and
    # the decode kernel, a gap, then a prefill program 100-160 us.
    dec = "%closed_call.12 = f32[64,2,8,128]{3,2,1,0} custom-call(s32[64]{0} %a)"
    pre = "%closed_call.4 = f32[1,2,6144,128]{3,2,1,0} custom-call(s32[1]{0} %b)"
    plane("/device:TPU:0", [
        (trace.MODULES, [("jit__unknown(1)", 0, 40), ("jit__unknown(2)", 100, 60)]),
        (trace.OPS, [("%while.6 = (s32[]) while(%x)", 0, 40), ("%fusion.1 = f32[2]", 0, 10),
                     (dec, 10, 30), ("%fusion.2 = f32[2]", 100, 40), (pre, 140, 20)]),
    ])
    plane("/host:CPU", [("python", [("bench.submit", 45, 50), ("other", 0, 200)])])
    path.write_bytes(space.SerializeToString())


def test_synthetic_reduction(tmp_path):
    f = tmp_path / "t.xplane.pb"
    _synthetic(f)
    r = trace.reduce(str(f), window_s=200e-6)
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(100e-6)          # [0, 40] + [100, 160]
    decode = r"custom-call f32\[64,2,8,128\]$"
    prefill = r"custom-call f32\[\d+,2,(?:6144|12288),128\]$"
    assert trace.program_time(r, decode) == pytest.approx((40e-6, 1))
    assert trace.program_time(r, prefill) == pytest.approx((60e-6, 1))
    assert trace.kernel_time(r, decode) == pytest.approx((30e-6, 1))
    assert trace.kernel_time(r, prefill) == pytest.approx((20e-6, 1))
    assert "while.6" not in r["ops"]                   # a loop is not a leaf
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "bench.submit" and gaps[0][1] == pytest.approx(60e-6)
    assert gaps[1][0] == "host without a benchmark span"
    assert gaps[1][1] == pytest.approx(40e-6)
    top = dict(r["breakdown"]["device_ops"])
    assert top["fusion.2"] == pytest.approx(40e-6)
    assert top["closed_call.12 custom-call f32[64,2,8,128]"] == pytest.approx(30e-6)


def test_recorded_serving_trace():
    """The first 60 ms of a traced qwen2.5-3b.chat span on one v5e chip:
    one decode program execution begins in it, and the layers it reached
    by the cut each ran the paged decode kernel once."""
    r = trace.reduce(str(DATA / "serve.xplane.pb"), window_s=0.06)
    assert r["chips"] == 1 and 0 < r["busy_s"] <= r["window_s"]
    decode = r"custom-call f32\[64,2,8,128\]$"
    seconds, n = trace.program_time(r, decode)
    assert n == 1 and 0.05 < seconds < 0.1
    k_seconds, k_n = trace.kernel_time(r, decode)
    assert 1 <= k_n <= 36
    assert 0 < k_seconds < seconds


def test_recorded_dse_trace():
    """The first 60 ms of three DSE calls: one batched program and its
    dominance kernel."""
    r = trace.reduce(str(DATA / "dse.xplane.pb"), window_s=0.06)
    seconds, n = trace.program_time(r, r"_run_batched_jit")
    assert n == 1 and 0.02 < seconds < 0.1
    k_seconds, k_n = trace.kernel_time(r, r"^dominance_matrix_pallas\S* custom-call ")
    assert k_n > 0 and 0 < k_seconds < seconds
