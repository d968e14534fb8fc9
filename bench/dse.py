"""The DSE cell: back-to-back ``nsga2.run_batched`` calls from one caller
(a closed loop), every call with the seed given by ``--seed``.

Set-up builds the scenario table from the configuration file and makes
one call, which compiles the batched program (its config, seed included,
is a static argument, so each new seed compiles anew) and the host-side
extraction programs.  The window then calls ``run_batched`` until
``--seconds`` have passed; generations per second are 64 x the calls
completed over the time they took, host extraction included.  Every
call's fronts and ranks must equal the last one's, which is checked in
full against the numpy reference (``bench/dse_reference.py``)."""
from __future__ import annotations

import dataclasses
import hashlib
import sys
import time

import jax
import numpy as np

from bench import dse_reference, harness

TRACE_CALLS = 3


def _digest(results) -> str:
    h = hashlib.sha1()
    for r in results:
        h.update(np.ascontiguousarray(r.front_genes).tobytes())
        h.update(np.ascontiguousarray(r.front_objectives).tobytes())
        h.update(np.ascontiguousarray(r.ranks).tobytes())
    return h.hexdigest()


def run(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
        seconds: float, trace: bool, dev: dict) -> tuple[dict, dict]:
    from repro.core import nsga2
    from repro.core.explorer import _normalize_scenarios
    from repro.core.scenario import ScenarioTable

    c = conf["config"]
    clock = harness.CompileClock()
    t_setup = time.perf_counter()
    scen = [tuple(s) for s in c["scenarios"]]
    table = ScenarioTable.from_specs(_normalize_scenarios(scen))
    ncfg = nsga2.NSGA2Config(pop_size=c["pop_size"], generations=c["generations"],
                             seed=seed % 2 ** 63)
    first = _digest(nsga2.run_batched(table, ncfg))
    setup_s = time.perf_counter() - t_setup
    compiles_before = clock.events

    prof: dict = {}
    digests, results = [], None
    calls = 0
    t0 = time.perf_counter()
    if trace:
        with harness.profiled(prof, "dse"):
            prof["t_start"] = time.perf_counter()
            for _ in range(TRACE_CALLS):
                with jax.profiler.TraceAnnotation("bench.run_batched"):
                    results = nsga2.run_batched(table, ncfg)
                digests.append(_digest(results))
                calls += 1
    call_s = []
    while (now := time.perf_counter()) - t0 < seconds:
        with jax.profiler.TraceAnnotation("bench.run_batched"):
            results = nsga2.run_batched(table, ncfg)
        digests.append(_digest(results))
        calls += 1
        call_s.append(time.perf_counter() - now)
    elapsed = time.perf_counter() - t0
    compiles_in_window = clock.events - compiles_before
    mem = harness.peak_bytes()
    q = np.percentile(call_s, [10, 50, 90, 100]) * 1e3 if call_s else [np.nan] * 4
    print(f"calls {calls} elapsed_s {elapsed:.3f} per_call_ms {elapsed / calls * 1e3:.2f} "
          f"call_ms_p10_p50_p90_max {q[0]:.1f} {q[1]:.1f} {q[2]:.1f} {q[3]:.1f} "
          f"compiles_in_window {compiles_in_window}", file=sys.stderr, flush=True)
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compile events inside the window")

    result = {"attempted": calls, "failed": 0,
              "device": {**dev, "memory_peak_bytes": mem}}
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(prof["xplane"], prof["window_s"], marks=("bench.",))
        ctx = {"kind": "dse", "calls": TRACE_CALLS, "trace": reduced,
               "config": c, "peaks": harness.peaks(dev["kind"])}
        result["metrics"] = harness.per_layer_metrics(bench, cell["name"], ctx)
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        result["metrics"] = {
            "dse_generations_per_s": {"value": c["generations"] * calls / elapsed,
                                      "unit": "gen/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    compared = check(conf, scen, results, digests, first)
    result["correct"] = all(v["value"] <= v["limit"] for v in compared.values())
    return result, compared


def check(conf: dict, scen, results, digests, first: str) -> dict:
    """The last call's results against the numpy reference: every front
    and final-population objective within the limit's relative error;
    each front against the exact Pareto set of its scenario, enumerated
    in full (``dse_reference.front_gaps``, with the tolerance twice that
    limit); the final population's ranks, which the dominance kernel
    produces, equal to the non-dominated sort of its own objectives; and
    every call's fronts and ranks identical."""
    limits = conf["limits"]
    tol = 2 * limits["max_rel_objective_error"]
    worst, missing, extra, misranked = 0.0, 0, 0, 0
    for (fmt, w), r in zip(scen, results):
        for genes, got in ((r.front_genes, r.front_objectives), (r.genes, r.objectives)):
            if len(genes):
                F, _ = dse_reference.evaluate(fmt, w, genes)
                err = np.abs(got.astype(np.float64) - F) / np.abs(F)
                worst = max(worst, float(err.max()))
        m, e = dse_reference.front_gaps(fmt, w, r.front_genes, tol)
        missing += m
        extra += e
        misranked += int((dse_reference.ranks(r.objectives, r.violation) != r.ranks).sum())
    return {
        "differing_calls": {"value": sum(d != first for d in digests), "limit": 0},
        "misranked_points": {"value": misranked, "limit": 0},
        "missing_front_points": {"value": missing, "limit": limits["missing_front_points"]},
        "extra_front_points": {"value": extra, "limit": limits["extra_front_points"]},
        "max_rel_objective_error": {"value": worst,
                                    "limit": limits["max_rel_objective_error"]},
    }


def control(scen, results):
    """The control: the numpy reference at bfloat16 put in the program's
    place, its objectives (and the ranks they give) of the genomes that
    ``results`` returned."""
    out = []
    for (fmt, w), r in zip(scen, results):
        front, _ = dse_reference.evaluate(fmt, w, r.front_genes, dtype="bfloat16")
        pop, _ = dse_reference.evaluate(fmt, w, r.genes, dtype="bfloat16")
        out.append(dataclasses.replace(
            r, front_objectives=front.astype(np.float32), objectives=pop.astype(np.float32),
            ranks=dse_reference.ranks(pop, r.violation)))
    return out
