"""Readings that a cell's correctness limits are set from, taken on the
chip at the cell's own size, in one process (set-up is paid once).

    python bench/tools/calibrate.py [--bench MANIFEST] <cell> <seconds> <seeds> <control seeds> <first seed>

Serving cells: for each seed, the program is served the cell's mix for
``seconds`` with that seed's weights and traffic; then, as in a run,
the widest gap of a sampled served token's float32 reference logit
below the reference's best.  For the first ``control seeds`` seeds the
control is read too: the reference at float8 picks the token at each
position of the same sequences, and its gap is read in float32.
DSE cells: for each seed, the numbers the cell compares, of one
``run_batched`` call and of the reference at bfloat16 in its place (the
control).  One JSON line per seed.  The cell is looked up in
``--bench`` (``BENCHMARK.json`` by default).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import dse, gen, harness, reference, serve, weights  # noqa: E402


def serve_readings(conf, mix, seconds, seeds, n_control):
    from functools import partial

    from repro.models import lm

    sched, session, _ = serve.setup(conf, mix, seeds[0], seconds)
    shapes = jax.eval_shape(partial(lm.init, cfg=sched.cfg), jax.random.PRNGKey(0))
    for k, seed in enumerate(seeds):
        if k:
            sched.params = None
            gc.collect()
            sched.params = weights.program_params(conf["config"], shapes, seed)
            session = sched.session(fresh=True)
        sessions = gen.generate(mix, seed, seconds, sched.cfg.vocab_size)
        t0 = time.perf_counter() + 0.05
        win = serve.Window(session, sessions, t0, seconds)
        session.start()
        time.sleep(max(0.0, t0 - time.perf_counter()))
        win.run()
        unfinished = win.finish(serve.GRACE_S)
        session.stop()
        recs = win.records
        for r in recs:
            r.handle = None
        del win, session
        sched._session = None
        gc.collect()
        picked = serve.sample(recs, seed, conf["check"]["sample"])
        prompts = [r.ask.prompt for r in picked]
        served = [np.asarray(r.tokens, np.int32) for r in picked]
        gaps = reference.served_gaps(conf["config"], seed, prompts, served)
        line = {"seed": seed, "requests": len(recs), "unfinished": unfinished,
                "checked_tokens": int(sum(len(g) for g in gaps)),
                "program_max_gap": float(max(g.max() for g in gaps)),
                "program_p99_gap": float(np.percentile(np.concatenate(gaps), 99))}
        if k < n_control:
            cg = reference.served_gaps(conf["config"], seed, prompts, served, control=True)
            line["control_max_gap"] = float(max(g.max() for g in cg))
            line["control_p99_gap"] = float(np.percentile(np.concatenate(cg), 99))
        print(json.dumps(line), flush=True)


def dse_readings(conf, seeds):
    from repro.core import nsga2
    from repro.core.explorer import _normalize_scenarios
    from repro.core.scenario import ScenarioTable

    c = conf["config"]
    scen = [tuple(s) for s in c["scenarios"]]
    table = ScenarioTable.from_specs(_normalize_scenarios(scen))
    for seed in seeds:
        res = nsga2.run_batched(table, nsga2.NSGA2Config(
            pop_size=c["pop_size"], generations=c["generations"], seed=seed))
        line = {"seed": seed}
        for side, rs in (("program", res), ("control", dse.control(scen, res))):
            d = dse._digest(rs)
            for k, v in dse.check(conf, scen, rs, [d], d).items():
                line[f"{side}_{k}"] = v["value"]
        print(json.dumps(line), flush=True)


def main(bench_path, cell_name, seconds, n, n_control, first):
    harness.require_chips(1)
    harness.use_cache()
    bench = json.loads((ROOT / bench_path).read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    conf = harness.read_json("configs", f"{cell['config']}.json")
    seeds = [first + 7919 * k for k in range(n)]
    if conf["runner"] == "serve":
        mix = harness.read_json("traffic", f"{cell['traffic']}.json")
        serve_readings(conf, mix, seconds, seeds, n_control)
    else:
        dse_readings(conf, seeds)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="BENCHMARK.json")
    ap.add_argument("cell")
    ap.add_argument("seconds", type=float)
    ap.add_argument("seeds", type=int)
    ap.add_argument("control_seeds", type=int)
    ap.add_argument("first_seed", type=int)
    a = ap.parse_args()
    main(a.bench, a.cell, a.seconds, a.seeds, a.control_seeds, a.first_seed)
