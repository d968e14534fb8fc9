"""The ``repro.nsga2.*`` spans that ``nsga2.run_batched`` records under the
profiler: read back from a CPU trace of one smoke-sized call, with their
nesting, their stats and the ``programs`` counter checked against the
executions in the same trace; and the results of a traced call equal to
an untraced one."""
import glob

import jax
import numpy as np
import pytest

from repro.core import nsga2
from repro.core.scenario import ScenarioTable

SPECS = [("int8", 16384), ("int4", 16384)]
CFG = nsga2.NSGA2Config(pop_size=16, generations=4)
S = len(SPECS)
EXECUTE = "PjRtCpuExecutable::Execute"   # one per program execution on the CPU

# span -> (its parent span, how many a call records)
SPANS = {
    "repro.nsga2.run_batched": (None, 1),
    "repro.nsga2.upload": ("repro.nsga2.run_batched", 1),
    "repro.nsga2.dispatch": ("repro.nsga2.run_batched", 1),
    "repro.nsga2.wait": ("repro.nsga2.run_batched", 1),
    "repro.nsga2.dedup": ("repro.nsga2.run_batched", 1),
    "repro.nsga2.fronts": ("repro.nsga2.run_batched", 1),
    "repro.nsga2.final_ranks": ("repro.nsga2.fronts", S),
    "repro.nsga2.archive_front": ("repro.nsga2.fronts", S),
}


def _record(logdir, fn):
    """Run ``fn`` under the profiler; its result and the events of the
    trace's host lines, as (start_ns, end_ns, name, stats, line)."""
    jax.profiler.start_trace(str(logdir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                events.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                               dict(ev.stats), (plane.name, i)))
    return out, events


def _parent(ev, spans):
    """The innermost ``repro.`` span on ``ev``'s line that holds it."""
    holders = [p for p in spans if p is not ev and p[4] == ev[4]
               and p[0] <= ev[0] and ev[1] <= p[1]]
    return min(holders, key=lambda p: p[1] - p[0], default=None)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    table = ScenarioTable.from_specs(SPECS)
    nsga2.run_batched(table, CFG)           # compile outside the trace
    results, events = _record(tmp_path_factory.mktemp("trace"),
                              lambda: nsga2.run_batched(table, CFG))
    spans = [e for e in events if e[2].startswith("repro.")]
    return results, events, spans


def _one(spans, name):
    (span,) = [s for s in spans if s[2] == name]
    return span


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_recorded_and_nested(traced, name):
    _, _, spans = traced
    parent, count = SPANS[name]
    mine = [s for s in spans if s[2] == name]
    assert len(mine) == count
    for s in mine:
        p = _parent(s, spans)
        assert (p[2] if p else None) == parent


def test_only_the_listed_spans(traced):
    _, _, spans = traced
    assert {s[2] for s in spans} == set(SPANS)


def test_call_stats(traced):
    _, _, spans = traced
    stats = _one(spans, "repro.nsga2.run_batched")[3]
    assert stats["scenarios"] == S
    assert stats["pop"] == CFG.pop_size
    assert stats["generations"] == CFG.generations


def test_transfer_bytes(traced):
    _, _, spans = traced
    P, G = CFG.pop_size, CFG.generations
    table = ScenarioTable.from_specs(SPECS)
    up = sum(np.asarray(a).nbytes for a in jax.tree.leaves(table)) + 8
    assert _one(spans, "repro.nsga2.upload")[3]["bytes"] == up
    # pops (S,P,3) i32, F (S,P,4) f32, v (S,P) f32, archives (S,(G+1)P,3) i32
    down = 4 * S * (P * 3 + P * 4 + P + (G + 1) * P * 3)
    assert _one(spans, "repro.nsga2.wait")[3]["bytes"] == down


def test_dedup_rows(traced):
    results, _, spans = traced
    stats = _one(spans, "repro.nsga2.dedup")[3]
    assert stats["rows_in"] == S * (CFG.generations + 1) * CFG.pop_size
    assert sum(len(r.front_genes) for r in results) <= stats["rows_out"] <= stats["rows_in"]
    # the genes lie in the DSE's small bounds: every row goes through the packed key
    assert stats["packed"] == stats["rows_in"]


def test_programs_counts_the_executions(traced):
    """``programs`` equals the program executions the trace shows inside
    the call: the seed, the broadcast, the batched program, and the ranks
    and archive-front programs of each scenario."""
    _, events, spans = traced
    call = _one(spans, "repro.nsga2.run_batched")
    runs = [e for e in events if e[2] == EXECUTE and e[4] == call[4]
            and call[0] <= e[0] and e[1] <= call[1]]
    assert call[3]["programs"] == len(runs) == 3 + 2 * S


def test_wait_follows_dispatch(traced):
    _, _, spans = traced
    order = [_one(spans, f"repro.nsga2.{n}")
             for n in ("upload", "dispatch", "wait", "dedup", "fronts")]
    assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))


def test_traced_results_equal_untraced(traced):
    results, _, _ = traced
    again = nsga2.run_batched(ScenarioTable.from_specs(SPECS), CFG)
    for a, b in zip(results, again):
        for f in ("genes", "objectives", "violation", "ranks",
                  "front_genes", "front_objectives"):
            x, y = getattr(a, f), getattr(b, f)
            assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), f


def test_run_static_shares_the_extraction_spans(tmp_path):
    """``run_static`` shares the final ranking and front extraction with
    the batched path, and records them under the same names, its archive
    deduplicated by the same helper."""
    from repro.core.precision import get as get_precision
    from repro.core.space import DesignSpace

    space = DesignSpace(prec=get_precision("int8"), w_store=16384)
    nsga2.run_static(space, CFG)
    _, events = _record(tmp_path, lambda: nsga2.run_static(space, CFG))
    spans = [e for e in events if e[2].startswith("repro.")]
    assert sorted(s[2] for s in spans) == [
        "repro.nsga2.archive_front", "repro.nsga2.dedup", "repro.nsga2.final_ranks"]
