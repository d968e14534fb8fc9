"""The numpy cost model agrees with the program's evaluation on random
genomes of every scenario, and its bfloat16 control does not."""
import numpy as np
import pytest

from bench import dse_reference

SCENARIOS = [("int2", 16384), ("int8", 65536), ("fp8", 16384), ("fp32", 131072)]


@pytest.mark.parametrize("fmt,w", SCENARIOS)
def test_matches_program(fmt, w):
    from repro.core import scenario
    from repro.core.explorer import _normalize_scenarios
    from repro.core.scenario import ScenarioTable

    table = ScenarioTable.from_specs(_normalize_scenarios([(fmt, w)]))
    row = table.row(0)
    rng = np.random.default_rng(0)
    lo, hi = np.asarray(row.gene_lo), np.asarray(row.gene_hi)
    genes = rng.integers(lo, hi + 1, (256, 3)).astype(np.int32)
    F, v = scenario.evaluate_host(row, genes)
    Fr, vr = dse_reference.evaluate(fmt, w, genes)
    assert np.array_equal(v, vr)
    assert np.abs(F - Fr).max() / np.abs(Fr).max() < 1e-5
    Fc, _ = dse_reference.evaluate(fmt, w, genes, dtype="bfloat16")
    assert (np.abs(Fc - Fr) / np.abs(Fr)).max() > 1e-3


@pytest.mark.parametrize("fmt,w", SCENARIOS)
def test_feasible_genomes_are_the_programs_space(fmt, w):
    from repro.core.precision import get as get_precision
    from repro.core.space import DesignSpace

    space = DesignSpace(prec=get_precision(fmt), w_store=w)
    ours = {tuple(g) for g in dse_reference.feasible_genomes(fmt, w).tolist()}
    assert ours == {tuple(g) for g in space.enumerate_feasible().tolist()}


def test_front_gaps():
    fmt, w = "int4", 16384
    G = dse_reference.feasible_genomes(fmt, w)
    F, _ = dse_reference.evaluate(fmt, w, G)
    front = G[~dse_reference.dominated(F)]
    assert dse_reference.front_gaps(fmt, w, front, 0.0) == (0, 0)
    assert dse_reference.front_gaps(fmt, w, front[1:], 0.0) == (1, 0)
    dominated = G[dse_reference.dominated(F)][:3]
    assert dse_reference.front_gaps(fmt, w, np.concatenate([front, dominated]), 0.0) == (0, 3)
    assert dse_reference.front_gaps(fmt, w, [[0, 0, 0]], 0.0) == (len(front), 1)


def test_ranks_match_program():
    from repro.core.pareto import non_dominated_sort

    rng = np.random.default_rng(3)
    F = rng.integers(0, 6, (64, 4)).astype(np.float32)
    v = np.where(rng.random(64) < 0.3, rng.integers(1, 3, 64), 0).astype(np.float32)
    assert np.array_equal(dse_reference.ranks(F, v), np.asarray(non_dominated_sort(F, v)))


def test_dominated():
    F = np.array([[1.0, 1, 1, 1], [2, 2, 2, 2], [0, 3, 3, 3], [1, 1, 1, 1]])
    assert dse_reference.dominated(F).tolist() == [False, True, False, False]
