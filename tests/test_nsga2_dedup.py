"""``nsga2._dedup``, which deduplicates every scenario's archive at once
through a packed integer key, against ``np.unique(a, axis=0)`` per
archive: the same rows, dtype, shape and order on every input, the span's
``packed`` stat saying which path ran, and DSE results bitwise equal to
those of the per-archive reference."""
import glob

import jax
import numpy as np
import pytest

from repro.core import nsga2
from repro.core.precision import get as get_precision
from repro.core.scenario import N_GENES, ScenarioTable
from repro.core.space import DesignSpace

I32 = np.iinfo(np.int32)


def _in_bounds(rng, n):
    """``n`` genomes inside the DSE's gene bounds, with many repeats."""
    return np.stack([rng.integers(3, 17, n), rng.integers(1, 12, n),
                     rng.integers(0, 5, n)], axis=1).astype(np.int32)


def _cases():
    rng = np.random.default_rng(20260)
    return {
        # name: (archives, whether the packed key can hold them)
        "scenarios": ([_in_bounds(rng, n) for n in (8320, 100, 1000, 3)], True),
        "one_archive": ([_in_bounds(rng, 8320)], True),
        "single_row": ([np.array([[5, 2, 1]], np.int32)], True),
        "all_equal": ([np.full((64, N_GENES), 7, np.int32),
                       np.full((9, N_GENES), 7, np.int32)], True),
        "negative": ([rng.integers(-6, 3, (500, N_GENES)).astype(np.int32)
                      for _ in range(3)], True),
        "empty_archive": ([_in_bounds(rng, 50), np.zeros((0, N_GENES), np.int32),
                           _in_bounds(rng, 7)], True),
        # few rows over a wide range: the keys are sorted, not tabled
        "sparse_keys": ([rng.integers(0, 1000, (40, N_GENES)).astype(np.int32)
                         for _ in range(2)], True),
        "int32_limits": ([np.array([[I32.min, 0, I32.max], [I32.max, I32.min, 0],
                                    [I32.min, 0, I32.max], [0, 0, 0]], np.int32),
                          np.array([[I32.max, I32.max, I32.max]], np.int32)], False),
    }


CASES = _cases()


def _unique_by_row(archives):
    return [np.unique(np.asarray(a).reshape(-1, N_GENES), axis=0) for a in archives]


def _assert_same_rows(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("name", sorted(CASES))
def test_dedup_equals_unique_by_row(name):
    archives, _ = CASES[name]
    _assert_same_rows(nsga2._dedup(archives), _unique_by_row(archives))


@pytest.mark.parametrize("name", sorted(CASES))
def test_dedup_span_reports_the_path(tmp_path, name):
    """``packed`` is every row where the key holds the genes, and 0 where
    the dedup fell back to ``np.unique`` by row."""
    archives, packable = CASES[name]
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = nsga2._dedup(archives)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    (stats,) = [dict(ev.stats)
                for plane in jax.profiler.ProfileData.from_file(path).planes
                for line in plane.lines for ev in line.events
                if ev.name == "repro.nsga2.dedup"]
    rows_in = sum(len(a) for a in archives)
    assert stats["rows_in"] == rows_in
    assert stats["rows_out"] == sum(len(a) for a in got)
    assert stats["packed"] == (rows_in if packable else 0)


SPECS = [("int8", 16384), ("fp8", 32768)]
CFG = nsga2.NSGA2Config(pop_size=16, generations=4)


def _run(runner):
    if runner == "run_batched":
        return nsga2.run_batched(ScenarioTable.from_specs(SPECS), CFG)
    return [nsga2.run_static(DesignSpace(prec=get_precision(p), w_store=w), CFG)
            for p, w in SPECS]


@pytest.mark.parametrize("runner", ["run_batched", "run_static"])
def test_results_bitwise_equal_to_unique_by_row(monkeypatch, runner):
    packed = _run(runner)
    monkeypatch.setattr(nsga2, "_dedup", _unique_by_row)
    reference = _run(runner)
    for a, b in zip(packed, reference):
        for f in ("genes", "objectives", "violation", "ranks",
                  "front_genes", "front_objectives"):
            x, y = getattr(a, f), getattr(b, f)
            assert x.dtype == y.dtype and x.shape == y.shape, f
            assert x.tobytes() == y.tobytes(), f
