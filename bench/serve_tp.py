"""A serving cell whose model is larger than one chip: one replica
tensor-parallel over the chips of one host, ``Scheduler(tp=N)`` with the
configuration's ``serve.tp``.

It runs as ``bench/serve.py`` does, and uses its ``Window``, ``check``,
``sample`` and ``program_config``, with three differences:

* the weights (``bench/weights.py``'s seeded tensors) are made in one
  jitted program, each laid out by the serving rules on the mesh the
  scheduler uses, so no chip ever holds a whole tensor of the model;
* the scheduler's ``param_bytes_per_chip`` counter goes into the
  readers' ``ctx``, with the trace's path and the mesh's width;
* the program's state is freed on every chip before the float32
  reference runs on the first.
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, harness, weights
from bench.serve import GRACE_S, TRACE_S, Window, _pct, check, program_config


@partial(jax.jit, static_argnames=("leaves", "layers", "d"))
def _tensors(words, *, leaves, layers, d):
    """The parameter leaves, each made where its sharding places it:
    ``leaves`` holds (name, shape, dtype, stacked, sharding) per leaf,
    a stacked block leaf being the layer tensors along axis 0."""
    out = []
    for name, shape, dtype, stacked, sharding in leaves:
        if stacked:
            x = jax.vmap(lambda i, name=name, shape=shape, dtype=dtype: weights.tensor(
                words, name, i, shape, d, dtype))(jnp.arange(layers))
        else:
            x = weights.tensor(words, name, 0, shape, d, dtype)
        out.append(jax.lax.with_sharding_constraint(x, sharding))
    return tuple(out)


def params_on_mesh(c: dict, shapes, seed: int, mesh):
    """``weights.program_params``'s tree, with its layout check, made in
    one program in the layout ``serve_param_sharding_tree`` gives
    ``mesh``, so no device holds more than its shard of any tensor."""
    from repro.dist import sharding as shd

    jax.eval_shape(lambda: weights.program_params(c, shapes, seed))  # layout check
    layout = jax.tree.leaves(shd.serve_param_sharding_tree(shapes, mesh))
    leaves = []
    for (path, leaf), sharding in zip(jax.tree_util.tree_flatten_with_path(shapes)[0], layout):
        name = weights._path_name(path)
        stacked = name.startswith("blocks.")
        leaves.append((name.split(".", 2)[2] if stacked else name,
                       tuple(leaf.shape[1:] if stacked else leaf.shape),
                       jnp.dtype(leaf.dtype), stacked, sharding))
    made = _tensors(jnp.asarray(weights.seed_words(seed)), leaves=tuple(leaves),
                    layers=c["num_hidden_layers"], d=c["hidden_size"])
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes), made)


def setup(conf: dict, mix: dict, seed: int, seconds: float):
    """Weights on the mesh, scheduler and warmed programs; returns
    (sched, session, sessions of the window)."""
    from repro.dist import sharding as shd
    from repro.models import lm
    from repro.serve import Request, Scheduler

    cfg = program_config(conf)
    serve_kw = dict(conf["serve"])
    tp = serve_kw.pop("tp")
    mesh = shd.make_mesh((tp,), ("model",))
    shapes = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    params = jax.block_until_ready(params_on_mesh(conf["config"], shapes, seed, mesh))
    sched = Scheduler(cfg, params, mesh=mesh, **serve_kw)
    del params
    session = sched.session()
    sessions = gen.generate(mix, seed, seconds, cfg.vocab_size)
    longest_prompt = max(len(a.prompt) for s in sessions for a in s)
    top = sched._bucket_for(longest_prompt)
    rng = np.random.default_rng([seed, 0xA11])
    for i, b in enumerate(x for x in sched.prefill_buckets if x <= top):
        n = b - 2                            # still in bucket b, and fits max_len
        session.serve([Request(prompt=rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                               n_tokens=2, rid=10 ** 9 + i)])
    jax.block_until_ready(session.pool)
    return sched, session, sessions


def run(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
        seconds: float, trace: bool, dev: dict) -> tuple[dict, dict]:
    clock = harness.CompileClock()
    t_setup = time.perf_counter()
    sched, session, sessions = setup(conf, mix, seed, seconds)
    setup_s = time.perf_counter() - t_setup
    compiles_before = clock.events
    param_bytes = sched.param_bytes_per_chip

    prof: dict = {}
    t0 = time.perf_counter() + 0.05
    win = Window(session, sessions, t0, seconds)
    tracer = None
    if trace:
        span = min(TRACE_S, seconds)
        start = t0 + (seconds - span) / 2

        def traced():
            time.sleep(max(0.0, start - time.perf_counter()))
            with harness.profiled(prof, "serve_tp"):
                prof["t_start"] = time.perf_counter()
                time.sleep(span)
            prof["t_stop"] = prof["t_start"] + prof["window_s"]

        tracer = threading.Thread(target=traced, name="bench-trace")
    session.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    if tracer is not None:
        tracer.start()
    win.run()
    unfinished = win.finish(GRACE_S)
    drain_s = time.perf_counter() - win.end
    session.stop()
    if tracer is not None:
        tracer.join()
    compiles_in_window = clock.events - compiles_before
    mem = harness.peak_bytes()

    recs = win.records
    ttft = [r.times[0] - r.due for r in recs if r.times]
    itl = [b - a for r in recs for a, b in zip(r.times, r.times[1:])]
    in_window = sum(1 for r in recs for t in r.times if t0 <= t <= win.end)
    lag = [r.called - r.due for r in recs]
    print(f"requests {len(recs)} unfinished {unfinished} tokens_in_window {in_window} "
          f"ttft_p50_ms {_pct(ttft, 50) * 1e3:.1f} ttft_p95_ms {_pct(ttft, 95) * 1e3:.1f} "
          f"itl_p50_ms {_pct(itl, 50) * 1e3:.2f} "
          f"generator_lag_p50_ms {_pct(lag, 50) * 1e3:.2f} "
          f"generator_lag_max_ms {max(lag, default=0) * 1e3:.1f} "
          f"drain_s {drain_s:.1f} compiles_in_window {compiles_in_window} "
          f"param_bytes_per_chip {param_bytes} memory_peak_bytes {mem}",
          file=sys.stderr, flush=True)
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compile events inside the window")

    result = {"attempted": len(recs), "failed": unfinished,
              "device": {**dev, "memory_peak_bytes": mem}}
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(prof["xplane"], prof["window_s"], marks=("bench.",))
        ctx = {"kind": "serve", "records": recs, "trace": reduced,
               "xplane": prof["xplane"], "t_start": prof["t_start"],
               "t_stop": prof["t_stop"], "config": conf["config"],
               "serve": conf["serve"], "param_bytes_per_chip": param_bytes,
               "peaks": harness.peaks(dev["kind"])}
        result["metrics"] = harness.per_layer_metrics(bench, cell["name"], ctx)
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        result["metrics"] = harness.end_to_end_metrics(bench, cell["name"], {
            "ttft_p95_ms": {"value": _pct(ttft, 95) * 1e3, "unit": "ms"},
            "itl_p95_ms": {"value": _pct(itl, 95) * 1e3, "unit": "ms"},
            "output_tokens_per_s": {"value": in_window / seconds, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        })

    # Free the program's state on every chip before the reference runs.
    del session, sched, win
    for r in recs:
        r.handle = None
    gc.collect()
    t_check = time.perf_counter()
    compared = check(conf, recs, seed, unfinished)
    print(f"check_s {time.perf_counter() - t_check:.1f}", file=sys.stderr, flush=True)
    result["correct"] = all(c["value"] <= c["limit"] for c in compared.values())
    return result, compared
