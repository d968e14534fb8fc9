"""Run the programs users pay chip time for, once each, on one TPU chip,
and check what comes out.

    python chip_smoke.py               # one chip: serve, DSE, DCIM numerics
    python chip_smoke.py --four-chips  # mistral-nemo-12b at tp=4 vs tp=1

Everything runs in this one process, which holds the chip.  Phases:

* **serve** — qwen2.5-3b at its published widths (random weights from a
  seed) serves four requests through ``Scheduler``/``ServeSession`` with
  the fused Pallas paged-attention kernels, two of them sharing a
  256-token prefix so the prefix-prefill kernel runs with context.  The
  same requests are then served with ``attn_backend="xla"``; the two
  kernels alone are compared with the XLA attention on sharp attention
  inputs; and a teacher-forced lockstep run compares the logits of the
  two attention paths at every step (the tolerance is
  ``logit_tolerance``'s).
* **dse** — ``nsga2.run_batched`` over the 8 scenarios of
  ``benchmarks/bench_dse.py`` at pop 128 x 64 generations with the
  compiled Pallas dominance kernel, whose matrix on each final
  population must equal ``core.pareto.dominance_matrix``.
* **dcim** — ``ops.dcim_mvm`` at 256x2048 @ 2048x11008 int8 (a dense
  shape of the served model) must equal an exact int32 matmul;
  ``ops.dcim_fp_matmul`` at that shape prints its error against an f32
  matmul at full precision.
* **four_chips** (``--four-chips`` alone) — mistral-nemo-12b at its
  published widths serves through ``Scheduler`` at tp=4 in the serving
  layout: each chip holds a quarter of every matrix and of the
  vocabulary (``wo`` and ``w_down`` row-parallel, each ending in one
  all-reduce), 6.12e9 parameter bytes, which the scheduler's
  ``param_bytes_per_chip`` counter reads from the placed shards.  Then,
  cut to ``compare_layers`` layers, tp=4 against tp=1 in lockstep
  within ``logit_tolerance``: the all-reduce adds in another order than
  one chip's dot, so tp is not bitwise, and a greedy token may differ
  only at a near-tie.

Each phase prints one JSON line (device, compile and run seconds,
``peak_bytes_in_use``, what was compared); the last line is
``{"ok": true, "device": {...}}``.  Any failure raises and exits non-zero.
No phase measures speed.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import Request, Scheduler, init_params  # noqa: E402


# ------------------------------ measurement ----------------------------------
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, read from its
    own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += duration


CLOCK = None


def _clock() -> CompileClock:
    global CLOCK
    if CLOCK is None:
        CLOCK = CompileClock()
    return CLOCK


@contextlib.contextmanager
def timed(rec: dict, key: str):
    """Wall and compile seconds of the block, as ``<key>_s`` and
    ``<key>_compile_s``."""
    c0, t0 = _clock().seconds, time.perf_counter()
    yield
    rec[f"{key}_s"] = time.perf_counter() - t0
    rec[f"{key}_compile_s"] = _clock().seconds - c0


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def check(cond, what: str):
    if not cond:
        raise AssertionError(what)


# ------------------------------- serving --------------------------------------
def logit_tolerance(logits: np.ndarray) -> float:
    """Both attention paths feed bf16 operands to f32-accumulating dots
    and differ only in f32 summation order and exp/divide rounding, so
    each layer's attention output differs by at most about one bf16 ulp
    after its cast back to bf16.  Allowed: 8 bf16 ulps (2^-8 relative
    each) of the largest logit, max|logit| / 32."""
    return float(np.abs(logits).max()) / 32.0


def compare_logits(a: np.ndarray, b: np.ndarray, stats: dict) -> None:
    """Rows of ``a`` against reference rows ``b`` (both (R, V) f32):
    every logit within the tolerance, and every greedy token equal or a
    near-tie of the reference (its reference logit within the tolerance
    of the reference maximum)."""
    tol = logit_tolerance(b)
    diff = float(np.abs(a - b).max())
    check(diff <= tol, f"logits differ by {diff} > tolerance {tol}")
    ta, tb = a.argmax(-1), b.argmax(-1)
    rows = np.arange(len(b))
    near = b[rows, ta] >= b[rows, tb] - tol
    check(bool(np.all((ta == tb) | near)), "greedy token outside tolerance")
    stats["max_logit_diff"] = max(stats.get("max_logit_diff", 0.0), diff)
    stats["max_tolerance"] = max(stats.get("max_tolerance", 0.0), tol)
    stats["token_steps"] = stats.get("token_steps", 0) + len(b)
    stats["near_tie_flips"] = stats.get("near_tie_flips", 0) + int(np.sum(ta != tb))


def serve_requests(vocab: int, prompt_lens, prefix_len: int, n_tokens: int,
                   seed: int):
    """``prompt_lens[0]`` and ``prompt_lens[-1]`` share their first
    ``prefix_len`` tokens; the sharing request comes last, so it is
    admitted after the first one's pages are filled and prefills only
    its tail, attending to them as context."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, prefix_len)
    prompts = [rng.integers(1, vocab, n) for n in prompt_lens]
    for i in (0, len(prompts) - 1):
        prompts[i][:prefix_len] = prefix
    return [Request(prompt=p.astype(np.int32), n_tokens=n_tokens, rid=i)
            for i, p in enumerate(prompts)]


def lockstep(cfg, sides, reqs, *, max_len: int, page_size: int,
             prefix_len: int, buckets, stats: dict) -> None:
    """Teacher-forced comparison of two serving configurations on the
    same requests, through ``lm.prefill_paged``/``lm.decode_step_paged``.

    ``sides`` is two (params, attn_backend, mesh) triples, the second
    the reference.  Prefill runs as the scheduler runs it — every
    request but the last in one burst, the last one's tail over the
    first one's prefix pages — then both sides decode step by step, fed
    the reference's greedy token, and ``compare_logits`` judges every
    step's logits."""
    S = len(reqs)
    nb = max_len // page_size
    n_pages = S * nb + 1
    pages, bt, nxt = [], np.zeros((S, nb), np.int32), 1
    for i, r in enumerate(reqs):
        need = -(-(r.prompt.size + r.n_tokens) // page_size)
        own = list(range(nxt, nxt + need))
        nxt += need
        if i == S - 1:                       # shares the first one's prefix
            own[:prefix_len // page_size] = pages[0][:prefix_len // page_size]
        pages.append(own)
        bt[i, :need] = own

    def stage(rows, ctx, bucket):
        W = 1 << (len(rows) - 1).bit_length()
        tok = np.zeros((W, bucket), np.int32)
        tbl = np.zeros((W, nb), np.int32)
        slots = np.full(W, S, np.int32)
        ctx_len = np.zeros(W, np.int32)
        tv = np.zeros(W, np.int32)
        for j, i in enumerate(rows):
            tail = reqs[i].prompt[ctx:]
            tok[j, :tail.size] = tail
            tbl[j], slots[j], ctx_len[j], tv[j] = bt[i], i, ctx, tail.size
        return tuple(jnp.asarray(a) for a in (tok, tbl, slots, ctx_len, tv))

    stages = [(list(range(S - 1)), 0, buckets[1]), ([S - 1], prefix_len, buckets[0])]
    runs = []
    for params, backend, mesh in sides:
        c = dataclasses.replace(cfg, attn_backend=backend).validate()
        ctx = (contextlib.nullcontext if mesh is None
               else lambda m=mesh: shd.use_mesh(shd.serving_context(m)))
        prefill = jax.jit(lambda p, pool, tok, tbl, sl, cl, tv, c=c: lm.prefill_paged(
            p, {"tokens": tok}, c, pool, tbl, sl, cl, tv, page_size, True))
        decode = jax.jit(lambda p, pool, tok, pos, tbl, c=c: lm.decode_step_paged(
            p, {"tokens": tok[:, None]}, pos, pool, tbl, c))
        pool = lm.init_paged_pool(c, S, n_pages, page_size, mesh=mesh)
        runs.append({"params": params, "ctx": ctx, "prefill": prefill,
                     "decode": decode, "pool": pool})

    first = [np.zeros((S, 0), np.float32) for _ in runs]
    for rows, ctx_tokens, bucket in stages:
        args = stage(rows, ctx_tokens, bucket)
        for k, run in enumerate(runs):
            with run["ctx"]():
                run["pool"], lg = run["prefill"](run["params"], run["pool"], *args)
            lg = np.asarray(lg[:len(rows), -1], np.float32)
            first[k] = lg if first[k].size == 0 else np.concatenate([first[k], lg])
    compare_logits(first[0], first[1], stats)

    cur = first[1].argmax(-1).astype(np.int32)
    pos = np.array([r.prompt.size for r in reqs], np.int32)
    for _ in range(max(r.n_tokens for r in reqs) - 1):
        logits = []
        for run in runs:
            with run["ctx"]():
                lg, run["pool"] = run["decode"](
                    run["params"], run["pool"], jnp.asarray(cur),
                    jnp.asarray(pos), jnp.asarray(bt))
            logits.append(np.asarray(lg[:, -1], np.float32))
        compare_logits(logits[0], logits[1], stats)
        cur = logits[1].argmax(-1).astype(np.int32)
        pos += 1


def attention_kernels_vs_xla(cfg, *, slots: int, max_len: int,
                             page_size: int, buckets, interpret: bool,
                             seed: int) -> dict:
    """The paged decode and prefix-prefill kernels against the XLA
    attention, alone, at the serving shapes, on inputs whose attention is
    sharp (large scores), where a wrong mask or page would move the
    output by O(1).  Both paths round the softmax to bf16 before PV, so
    a probability may differ by one bf16 ulp (2^-8 relative), and the
    output by at most 2^-8 * max|v|; allowed: twice that."""
    from repro.kernels import ops

    kernel = "pallas_interpret" if interpret else "pallas"
    rng = np.random.default_rng(seed)
    H, Hk, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim or cfg.d_model // cfg.n_heads
    nb = max_len // page_size
    n_pages = slots * nb + 1
    bf = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    k_pages, v_pages = bf(n_pages, page_size, Hk, hd), bf(n_pages, page_size, Hk, hd)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:slots * nb]
                        .reshape(slots, nb), jnp.int32)
    pos = jnp.asarray(rng.integers(0, max_len, slots), jnp.int32)
    q = bf(slots, 1, H, hd) * 8
    tol = 2.0 ** -7 * float(jnp.abs(v_pages.astype(jnp.float32)).max())
    out = {}
    got, want = (ops.paged_decode_gqa(q, k_pages, v_pages, table, pos, backend=b)
                 for b in (kernel, "xla"))
    out["decode_max_diff"] = float(jnp.abs(got - want).max())

    T = buckets[0]
    qt, kc, vc = bf(slots, T, H, hd) * 8, bf(slots, max_len, Hk, hd), bf(slots, max_len, Hk, hd)
    kt, vt = bf(slots, T, Hk, hd), bf(slots, T, Hk, hd)
    ctx = jnp.asarray(rng.integers(0, max_len, slots), jnp.int32)
    got, want = (ops.prefix_prefill(qt, kc, vc, kt, vt, ctx, backend=b)
                 for b in (kernel, "xla"))
    out["prefill_max_diff"] = float(jnp.abs(got - want).max())
    out["tolerance"] = tol
    check(max(out["decode_max_diff"], out["prefill_max_diff"]) <= tol,
          f"attention kernels differ from XLA attention: {out}")
    return out


def served_programs_have_kernel(sched: Scheduler, interpret: bool) -> bool:
    """Trace the decode and every prefill program the scheduler compiled,
    with the shapes the session gave them.  A compiled kernel lowers to
    ``tpu_custom_call``; an interpreted one leaves no custom call, so it
    is looked for in the jaxpr."""
    sess = sched.session()
    S, nb = sched.max_slots, sched.pages_per_slot
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    progs = [sched._decode.trace(
        sched.params, sess.pool, i32(S), i32(S), jax.ShapeDtypeStruct((S,), jnp.bool_),
        i32(S, nb), jax.ShapeDtypeStruct((S, 2), jnp.uint32), i32(S),
        jax.ShapeDtypeStruct((S,), jnp.float32))]
    for (bucket, W), fn in sched._prefills.items():
        progs.append(fn.trace(
            sched.params, sess.pool, i32(W, bucket), i32(W, nb), i32(W), i32(W),
            i32(W), jax.ShapeDtypeStruct((W, 2), jnp.uint32),
            jax.ShapeDtypeStruct((W,), jnp.float32)))
    for tr in progs:
        if interpret:
            if "pallas_call" not in str(tr.jaxpr):
                return False
        elif "tpu_custom_call" not in tr.lower().as_text():
            return False
    return len(progs) >= 2


def serve_phase(cfg, *, max_slots: int = 4, max_len: int = 1024,
                page_size: int = 16, prompt_lens=(320, 300, 180, 296),
                prefix_len: int = 256, n_tokens: int = 24,
                buckets=(64, 512), interpret: bool = False,
                seed: int = 0) -> dict:
    rec = {"phase": "serve", "model": cfg.name, "layers": cfg.n_layers,
           "d_model": cfg.d_model, "vocab": cfg.vocab_size,
           "slots": max_slots, "max_len": max_len, "page_size": page_size}
    with timed(rec, "init"):
        params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(seed)))
    rec["param_bytes"] = sum(x.nbytes for x in jax.tree.leaves(params))
    reqs = serve_requests(cfg.vocab_size, prompt_lens, prefix_len, n_tokens, seed)
    kernel = "pallas_interpret" if interpret else "pallas"
    kw = dict(max_slots=max_slots, max_len=max_len, page_size=page_size,
              prefill_buckets=buckets)

    served = {}
    for backend in (kernel, "xla"):
        sched = Scheduler(cfg, params, attn_backend=backend, **kw)
        with timed(rec, f"serve_{backend}"):
            res = sched.serve(reqs)
        # A fresh session re-serves the same trace on the compiled programs.
        programs = sched.compile_counts()["total"]
        with timed(rec, f"reserve_{backend}"):
            again = sched.session(fresh=True).serve(reqs)
        check(sched.compile_counts()["total"] == programs,
              f"{backend}: re-serving compiled a new program")
        check([list(r.tokens) for r in res] == [list(r.tokens) for r in again],
              f"{backend}: re-serving changed the tokens")
        check(all(r.generated.size == n_tokens for r in res),
              f"{backend}: a request stopped short")
        check(res[-1].prefix_hit_tokens == prefix_len,
              f"{backend}: the shared prefix was not reused")
        if backend == kernel:
            check(served_programs_have_kernel(sched, interpret),
                  "decode/prefill programs do not contain the Pallas kernels")
        served[backend] = [r.generated for r in res]
        rec[f"compiled_programs_{backend}"] = programs

    same = [bool(np.array_equal(a, b)) for a, b in zip(served[kernel], served["xla"])]
    rec["served_streams_identical"] = f"{sum(same)}/{len(same)}"
    rec["served_first_divergence"] = [
        None if s else int(np.flatnonzero(a != b)[0])
        for s, a, b in zip(same, served[kernel], served["xla"])]

    rec["kernels_vs_xla"] = attention_kernels_vs_xla(
        cfg, slots=max_slots, max_len=max_len, page_size=page_size,
        buckets=buckets, interpret=interpret, seed=seed)
    stats = {}
    with timed(rec, "lockstep"):
        lockstep(cfg, [(params, kernel, None), (params, "xla", None)], reqs,
                 max_len=max_len, page_size=page_size, prefix_len=prefix_len,
                 buckets=buckets, stats=stats)
    rec["lockstep_vs_xla"] = stats
    rec["peak_bytes_in_use"] = peak_bytes()
    return rec


# --------------------------------- DSE ----------------------------------------
def dse_phase(scenarios, *, pop_size: int = 128, generations: int = 64,
              interpret: bool = False) -> dict:
    from repro.core import nsga2, pareto
    from repro.core.explorer import _normalize_scenarios
    from repro.core.scenario import ScenarioTable
    from repro.kernels.pareto_rank import dominance_matrix_pallas

    rec = {"phase": "dse", "scenarios": len(scenarios), "pop": pop_size,
           "generations": generations}
    cfg = nsga2.NSGA2Config(pop_size=pop_size, generations=generations)
    table = ScenarioTable.from_specs(_normalize_scenarios(scenarios))
    with timed(rec, "run_cold"):
        results = nsga2.run_batched(table, cfg)
    with timed(rec, "run_warm"):
        again = nsga2.run_batched(table, cfg)
    check(all(np.array_equal(a.front_genes, b.front_genes)
              for a, b in zip(results, again)), "DSE rerun changed a front")
    if not interpret:
        keys = jnp.broadcast_to(jax.random.PRNGKey(cfg.seed),
                                (len(scenarios), 2))
        text = nsga2._run_batched_jit.lower(
            jax.tree.map(jnp.asarray, table), cfg, keys).as_text()
        check("tpu_custom_call" in text,
              "run_batched did not compile the Pallas dominance kernel")
    for r in results:
        F, v = jnp.asarray(r.objectives), jnp.asarray(r.violation)
        got = np.asarray(dominance_matrix_pallas(F, v, interpret=interpret)) != 0
        want = np.asarray(pareto.dominance_matrix(F, v))
        check(np.array_equal(got, want),
              "Pallas dominance differs from core.pareto.dominance_matrix")
    rec["compared"] = ("Pallas dominance == core.pareto.dominance_matrix on "
                       f"{len(results)} final populations of {pop_size}")
    rec["front_sizes"] = [int(r.front_genes.shape[0]) for r in results]
    rec["peak_bytes_in_use"] = peak_bytes()
    return rec


# ---------------------------- DCIM numerics -----------------------------------
def dcim_phase(M: int = 256, K: int = 2048, N: int = 11008, *,
               interpret: bool = False, seed: int = 0) -> dict:
    from repro.kernels import ops, ref

    rec = {"phase": "dcim", "shape": [M, K, N]}
    rng = np.random.default_rng(seed)
    x8 = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int8)
    w8 = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int8)
    interp = True if interpret else None
    if not interpret:
        check("tpu_custom_call" in ops.dcim_mvm.lower(x8, w8).as_text(),
              "dcim_mvm did not compile the Pallas kernel")
    with timed(rec, "mvm"):
        got = jax.block_until_ready(ops.dcim_mvm(x8, w8, interpret=interp))
    want = jax.lax.dot(x8, w8, preferred_element_type=jnp.int32)
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          "dcim_mvm differs from the exact int32 matmul")
    rec["mvm_compared"] = "int8 dcim_mvm == exact int32 matmul"

    xf = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    wf = jnp.asarray(rng.standard_normal((K, N)) * 0.05, jnp.float32)
    with timed(rec, "fp_matmul"):
        y = jax.block_until_ready(ops.dcim_fp_matmul(xf, wf, interpret=interp))
    with jax.default_matmul_precision("highest"):
        y_ref = ref.fp_matmul_f32_ref(xf, wf)
    y, y_ref = np.asarray(y), np.asarray(y_ref)
    check(np.isfinite(y).all(), "dcim_fp_matmul produced non-finite values")
    err = np.abs(y - y_ref)
    rec["fp_matmul_max_abs_err"] = float(err.max())
    rec["fp_matmul_rel_err"] = float(err.max() / np.abs(y_ref).max())
    rec["peak_bytes_in_use"] = peak_bytes()
    return rec


# ------------------------------ four chips ------------------------------------
def four_chip_phase(cfg, *, tp: int = 4, compare_layers: int = 8,
                    max_slots: int = 4, max_len: int = 512,
                    page_size: int = 16, prompt_lens=(200, 160, 120, 240),
                    prefix_len: int = 128, n_tokens: int = 16,
                    buckets=(128, 256), seed: int = 0) -> dict:
    """``Scheduler(tp=N)`` with a model no single chip holds, then the
    same program at ``compare_layers`` layers against tp=1, in lockstep
    within ``logit_tolerance``."""
    rec = {"phase": "four_chips", "model": cfg.name, "tp": tp}
    mesh = shd.make_mesh((tp,), ("model",))
    kw = dict(max_slots=max_slots, max_len=max_len, page_size=page_size,
              prefill_buckets=buckets)
    reqs = serve_requests(cfg.vocab_size, prompt_lens, prefix_len, n_tokens, seed)

    with timed(rec, "init_full"):
        params = jax.block_until_ready(
            init_params(cfg, jax.random.PRNGKey(seed), mesh))
    sched = Scheduler(cfg, params, mesh=mesh, **kw)
    rec["param_bytes_per_chip_full"] = sched.param_bytes_per_chip
    with timed(rec, "serve_full"):
        res = sched.serve(reqs)
    check(all(r.generated.size == n_tokens for r in res),
          "tp serving stopped a request short")
    rec["peak_bytes_in_use_full"] = {
        str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:tp]}
    del sched, params   # the session/scheduler cycle holds both
    gc.collect()

    cut = dataclasses.replace(cfg, n_layers=compare_layers).validate()
    p1 = jax.block_until_ready(init_params(cut, jax.random.PRNGKey(seed)))
    pN = jax.device_put(p1, shd.serve_param_sharding_tree(p1, mesh))
    with timed(rec, "serve_cut_tp"):
        got = Scheduler(cut, pN, mesh=mesh, **kw).serve(reqs)
    with timed(rec, "serve_cut_tp1"):
        want = Scheduler(cut, p1, **kw).serve(reqs)
    same = [bool(np.array_equal(a.tokens, b.tokens)) for a, b in zip(got, want)]
    rec["served_streams_identical"] = f"{sum(same)}/{len(same)}"
    stats = {}
    with timed(rec, "lockstep"):
        lockstep(cut, [(pN, "auto", mesh), (p1, "auto", None)], reqs,
                 max_len=max_len, page_size=page_size, prefix_len=prefix_len,
                 buckets=buckets, stats=stats)
    rec["lockstep_tp_vs_tp1"] = stats
    rec["compare_layers"] = compare_layers
    return rec


# --------------------------------- main ---------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 serving path and its tp=1 comparison")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {dev['platform']!r}")
    use_compile_cache()

    if args.four_chips:
        check(dev["count"] >= 4, f"--four-chips needs 4 chips, found {dev['count']}")
        records = [four_chip_phase(configs.get_config("mistral-nemo-12b"))]
    else:
        from benchmarks.bench_dse import SCENARIOS

        records = [serve_phase(configs.get_config("qwen2.5-3b")),
                   dse_phase(SCENARIOS), dcim_phase()]
    for rec in records:
        print(json.dumps({"device": dev["kind"], **rec}), flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
