"""Find a serving cell's knee once, by a sweep of offered rates on the
chip, all in one process.  For each rate the cell's mix is offered for
``seconds`` at that rate; the run then waits for the session to drain
before the next rate.

    python bench/tools/sweep.py [--bench MANIFEST] <cell> <seed> <seconds> <rate> [rate ...]

The cell is looked up in ``--bench`` (``BENCHMARK.json`` by default).

Per rate it prints one JSON line: requests due, the share of those due
in the window's first half that had finished by its end, the TTFT
median of the first and second halves of the arrivals (a growing
backlog shows as a later half far above the first), TTFT and ITL p95,
and tokens delivered per second of the window.  The knee is the highest
rate at which the backlog does not grow: both halves' TTFT medians stay
within twice the first (lowest) rate's first-half median; the sweep
stops at the first rate past it.  The completion share does not decide:
where the longest request's own decode outlasts half the window, it
stays under 97% at any load.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import gen, harness, serve  # noqa: E402


def main(bench_path: str, cell_name: str, seed: int, seconds: float, rates) -> None:
    dev = harness.require_chips(1)
    harness.use_cache()
    bench = json.loads((ROOT / bench_path).read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    conf = harness.read_json("configs", f"{cell['config']}.json")
    mix = harness.read_json("traffic", f"{cell['traffic']}.json")
    clock = harness.CompileClock()
    sched, session, _ = serve.setup(conf, mix, seed, seconds)
    vocab = sched.cfg.vocab_size
    print(json.dumps({"device": dev, "cell": cell_name}), flush=True)
    base = None
    for rate in rates:
        sessions = gen.generate(mix, seed, seconds, vocab, rate_per_s=rate)
        t0 = time.perf_counter() + 0.05
        win = serve.Window(session, sessions, t0, seconds)
        session.start()
        time.sleep(max(0.0, t0 - time.perf_counter()))
        win.run()
        unfinished = win.finish(90.0)
        session.stop()
        recs = win.records
        half = [r for r in recs if r.due - t0 < seconds / 2]
        late = [r for r in recs if r.due - t0 >= seconds / 2]
        done_by_end = sum(1 for r in half if r.times and r.done and r.times[-1] <= win.end)
        ttft = lambda rs: [r.times[0] - r.due for r in rs if r.times]  # noqa: E731
        itl = [b - a for r in recs for a, b in zip(r.times, r.times[1:])]
        toks = sum(1 for r in recs for t in r.times if t0 <= t <= win.end)
        line = {
            "rate_per_s": rate, "due": len(recs), "unfinished": unfinished,
            "first_half_done_share": done_by_end / max(len(half), 1),
            "ttft_p50_first_half_ms": 1e3 * float(np.median(ttft(half))) if half else None,
            "ttft_p50_second_half_ms": 1e3 * float(np.median(ttft(late))) if late else None,
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft(recs), 95)),
            "itl_p95_ms": 1e3 * float(np.percentile(itl, 95)),
            "output_tokens_per_s": toks / seconds,
            "compiles": clock.events}
        print(json.dumps(line), flush=True)
        base = base or line["ttft_p50_first_half_ms"]
        worst = max(line["ttft_p50_first_half_ms"], line["ttft_p50_second_half_ms"])
        if unfinished or worst > 2.0 * base:
            break


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="BENCHMARK.json")
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("seconds", type=float)
    ap.add_argument("rates", type=float, nargs="+")
    a = ap.parse_args()
    main(a.bench, a.cell, a.seed, a.seconds, a.rates)
