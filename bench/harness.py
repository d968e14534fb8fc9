"""What every cell's run shares: the device check, the compile clock, the
compile cache, the profiler, loading a cell's files by name, and the
result line."""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import pathlib
import sys
import time

import jax

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """The run's device, or exit non-zero with no result: the benchmark
    never falls back to the CPU."""
    dev = device_info()
    if dev["platform"] != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform {dev['platform']!r}")
    if dev["count"] < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found {dev['count']}")
    return dev


def use_cache() -> str:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, a fixed
    path inside the checkout, holding every program however short its
    compile, so that only a checkout's first run compiles.  The program's
    own helper (``repro.launch.compile_cache``) takes the directory the
    environment names, so the benchmark names this one there too."""
    from repro.launch.compile_cache import use_compile_cache

    path = str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    if use_compile_cache() != path:
        raise RuntimeError("the program keeps its compile cache elsewhere")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No size cap: a cell's programs (and the reference's) outgrow the
    # 192 MB some machines set by environment, and an LRU cache that
    # evicts them compiles again in every run.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, read from its
    own monitoring events (from ``chip_smoke.py``), and the number of
    programs it compiled or loaded from the persistent cache (each is one
    ``backend_compile`` event; eager dispatch traces without one)."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += duration
        if name == "/jax/core/compile/backend_compile_duration":
            self.events += 1


def peak_bytes() -> int:
    """Peak device memory of the fullest chip."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def load(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, found by name."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts) -> dict:
    return json.loads((BENCH.joinpath(*parts)).read_text())


def peaks(kind: str) -> dict:
    table = read_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


@contextlib.contextmanager
def profiled(out: dict, tag: str):
    """Profile the block with the device tracer and host annotations (no
    Python function tracer); ``out`` gets the ``.xplane.pb`` path and the
    traced window's host seconds."""
    logdir = ROOT / ".bench_trace" / tag
    for f in glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True):
        os.remove(f)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        out["window_s"] = time.perf_counter() - t0
        jax.profiler.stop_trace()
        out["xplane"] = sorted(glob.glob(str(logdir / "**" / "*.xplane.pb"),
                                         recursive=True))[-1]


def per_layer_metrics(bench: dict, cell: str, ctx: dict) -> dict:
    """Each per-layer metric that lists this cell (or lists no cells),
    from its reader ``bench/metrics/<name>.py``; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = load("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(bench: dict, cell: str, values: dict) -> dict:
    """Of a run's end-to-end readings, those ``BENCHMARK.json`` lists for
    this cell (or lists for every cell): a reading the benchmark does not
    bound for the cell, such as a session mix's TTFT tail, is left out of
    the result line."""
    listed = {m["name"] for m in bench["end_to_end"]
              if cell in m.get("workloads", [cell])}
    return {k: v for k, v in values.items() if k in listed}


def emit(result: dict, compared: dict) -> None:
    """The contract's last lines: each compared number beside its limit on
    standard error, and the result as the last line of standard output,
    with ``compared`` as its last key."""
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["compared"] = compared
    print(json.dumps(result), flush=True)
