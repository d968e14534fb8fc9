"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``bench/configs/<config>.json``) names the runner that
runs it (``bench/serve.py`` or ``bench/dse.py``), and its traffic mix is
``bench/traffic/<traffic>.json``.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window.
The run needs a TPU with as many chips as the cell asks for, and exits
non-zero with no result otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def run_cell(name: str, seed: int, seconds: float, trace: bool, dev: dict,
             bench: dict | None = None):
    """The cell's (result, compared); the caller has checked the device."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    conf = harness.read_json("configs", f"{cell['config']}.json")
    mix = harness.read_json("traffic", f"{cell['traffic']}.json")
    runner = __import__(f"bench.{conf['runner']}", fromlist=["run"])
    return runner.run(bench, cell, conf, mix, seed, seconds, trace, dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        sys.exit(f"bench: no workload {args.workload!r} in BENCHMARK.json")
    dev = harness.require_chips(cell["chips"])
    harness.use_cache()
    result, compared = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), dev, bench)
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
