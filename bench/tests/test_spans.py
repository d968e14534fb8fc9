"""The program-span reduction: exact arithmetic on a synthetic trace with
nested spans and device gaps, the readers of the span metrics, and the
readers that came before them unchanged on the recorded DSE trace."""
import json
import pathlib

import pytest

from bench import harness, spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
US = 1e-6


def _synthetic(path):
    """Device busy [0, 10], [30, 40], [90, 100] us of a 120 us window; on
    the host, one call [5, 110] holding ``a`` [12, 28] and ``b`` [45, 95],
    which holds ``c`` twice ([50, 60], [70, 80]); a benchmark span round
    it all, and a ``repro.`` span on another thread."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()

    def plane(name, lines):
        p = space.planes.add(id=len(space.planes), name=name)
        names, stat_ids = {}, {}
        for lname, events in lines:
            line = p.lines.add(id=len(p.lines), name=lname)
            for ev_name, start_us, dur_us, *stats in events:
                if ev_name not in names:
                    names[ev_name] = len(names) + 1
                    p.event_metadata[names[ev_name]].id = names[ev_name]
                    p.event_metadata[names[ev_name]].name = ev_name
                ev = line.events.add(metadata_id=names[ev_name],
                                     offset_ps=start_us * 10 ** 6,
                                     duration_ps=dur_us * 10 ** 6)
                for k, v in (stats[0] if stats else {}).items():
                    if k not in stat_ids:
                        stat_ids[k] = len(stat_ids) + 1
                        p.stat_metadata[stat_ids[k]].id = stat_ids[k]
                        p.stat_metadata[stat_ids[k]].name = k
                    ev.stats.add(metadata_id=stat_ids[k], int64_value=v)

    plane("/device:TPU:0", [
        (trace.MODULES, [("jit_f(1)", 0, 10), ("jit_g(2)", 30, 10), ("jit_h(3)", 90, 10)]),
        (trace.OPS, [("%fusion.1 = f32[2]", 0, 10), ("%fusion.2 = f32[2]", 30, 10),
                     ("%fusion.3 = f32[2]", 90, 10)]),
    ])
    plane("/host:CPU", [
        ("python", [("bench.outer", 0, 115), ("repro.call", 5, 105, {"programs": 3}),
                    ("repro.a", 12, 16, {"rows_in": 10, "rows_out": 4}),
                    ("repro.b", 45, 50), ("repro.c", 50, 10, {"bytes": 5}),
                    ("repro.c", 70, 10, {"bytes": 6}), ("other", 60, 5)]),
        ("worker", [("repro.other", 112, 4)]),
    ])
    path.write_bytes(space.SerializeToString())


@pytest.fixture
def reduced(tmp_path):
    f = tmp_path / "t.xplane.pb"
    _synthetic(f)
    return spans.reduce(str(f), window_s=120 * US)


def test_totals_and_self_time(reduced):
    s = reduced["spans"]
    assert set(s) == {"repro.call", "repro.a", "repro.b", "repro.c", "repro.other"}
    assert s["repro.call"]["seconds"] == pytest.approx(105 * US)
    assert s["repro.call"]["self_s"] == pytest.approx((105 - 16 - 50) * US)
    assert s["repro.b"]["self_s"] == pytest.approx((50 - 20) * US)
    assert s["repro.c"]["count"] == 2
    assert s["repro.c"]["seconds"] == s["repro.c"]["self_s"] == pytest.approx(20 * US)


def test_stats_summed(reduced):
    s = reduced["spans"]
    assert s["repro.call"]["stats"] == {"programs": 3}
    assert s["repro.a"]["stats"] == {"rows_in": 10, "rows_out": 4}
    assert s["repro.c"]["stats"] == {"bytes": 11}
    assert s["repro.b"]["stats"] == {}


def test_idle_to_the_innermost_span(reduced):
    """Idle [10, 30], [40, 90], [100, 120]: ``a`` takes [12, 28], ``c``
    [50, 60] and [70, 80], ``b`` the rest of [45, 90], the call what lies
    in none of its children; [110, 112] and [116, 120] lie in no span."""
    s = reduced["spans"]
    assert reduced["idle_s"] == pytest.approx(90 * US)
    assert s["repro.a"]["idle_s"] == pytest.approx(16 * US)
    assert s["repro.c"]["idle_s"] == pytest.approx(20 * US)
    assert s["repro.b"]["idle_s"] == pytest.approx(25 * US)
    assert s["repro.call"]["idle_s"] == pytest.approx((2 + 2 + 5 + 10) * US)
    assert s["repro.other"]["idle_s"] == pytest.approx(4 * US)
    assert reduced["unattributed_idle_s"] == pytest.approx(6 * US)


def test_span_readers(reduced):
    ctx = {"calls": 2, "spans": {**reduced, "spans": {
        f"repro.nsga2.{k}": v for k, v in
        (("wait", reduced["spans"]["repro.a"]), ("dedup", reduced["spans"]["repro.b"]),
         ("fronts", reduced["spans"]["repro.c"]),
         ("run_batched", reduced["spans"]["repro.call"]))}}}
    read = {m: harness.load("metrics", m).read(ctx) for m in
            ("dse.wait_ms", "dse.dedup_ms", "dse.fronts_ms", "dse.programs_per_call",
             "idle_unattributed_share.dse")}
    assert read["dse.wait_ms"] == pytest.approx(16e-3 / 2)
    assert read["dse.dedup_ms"] == pytest.approx(50e-3 / 2)
    assert read["dse.fronts_ms"] == pytest.approx(20e-3 / 2)
    assert read["dse.programs_per_call"] == 1.5
    assert read["idle_unattributed_share.dse"] == pytest.approx(100 * 6 / 90)


def test_readers_find_the_cells_trace(tmp_path, monkeypatch):
    """With no ``spans`` in ``ctx`` the readers reduce the trace that the
    profiler wrote under the cell's kind, once for all of them; with no
    trace there they read nothing."""
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    names = ("dse.wait_ms", "dse.dedup_ms", "dse.fronts_ms", "dse.programs_per_call",
             "idle_unattributed_share.dse")
    ctx = {"kind": "dse", "calls": 2, "trace": {"window_s": 120 * US}}
    assert all(harness.load("metrics", m).read(ctx) is None for m in names)
    assert ctx["spans"]["spans"] == {}

    found = tmp_path / "dse" / "plugins" / "profile" / "run"
    found.mkdir(parents=True)
    _synthetic(found / "host.xplane.pb")
    ctx = {"kind": "dse", "calls": 2, "trace": {"window_s": 120 * US}}
    assert harness.load("metrics", "idle_unattributed_share.dse").read(ctx) == \
        pytest.approx(100 * 6 / 90)
    assert ctx["spans"] == spans.reduce(str(found / "host.xplane.pb"), window_s=120 * US)


def test_recorded_dse_trace_readers_unchanged(tmp_path, monkeypatch):
    """The first 60 ms of three DSE calls, recorded before the program had
    spans, found where the profiler writes the cell's trace: the readers
    that came before the span metrics read what they read then, and the
    span metrics read nothing."""
    path = str(DATA / "dse.xplane.pb")
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    (tmp_path / "dse").mkdir()
    (tmp_path / "dse" / "dse.xplane.pb").write_bytes(pathlib.Path(path).read_bytes())
    ctx = {"kind": "dse", "calls": 3, "trace": trace.reduce(path, window_s=0.06),
           "config": {}, "peaks": harness.peaks("TPU v5 lite")}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    got = harness.per_layer_metrics(bench, "sega-dse.8x128", ctx)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "dse.program_ms": 16.424804,
        "dse.dominance_ms": 0.040992666666666684,
        "idle_share.dse": 17.880463333333317,
    }, rel=1e-12)
    assert ctx["spans"]["spans"] == {}
