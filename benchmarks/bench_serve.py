"""Continuous-batching vs bucketed-batch serving benchmark, plus the
shared-prefix paging trace.

Serves ONE mixed-length greedy arrival trace (mixed prompt lengths AND
mixed n_tokens) through both paths:

  * ``bucketed`` — the historical ``Engine`` + ``bucket_requests`` loop:
    requests group into equal-prompt-length batches and every batch is
    held until its LONGEST generation finishes (and pays one prefill
    compile per distinct prompt length),
  * ``continuous`` — ``serve.Scheduler``: a fixed pool of decode slots
    over the paged KV cache, one jitted decode program, bucketed burst
    prefill; slots retire and recycle per request, so throughput is
    bounded by slot count instead of the slowest bucket member.

A third child, ``prefix``, serves a SHARED-PREFIX trace (many requests
over one long system prompt — the serve-trace shape DCIM evaluation
harnesses produce) twice: through the paged scheduler with prefix reuse
+ burst prefill, and through the PR-3 monolithic scheduler
(``paged=False``) that must prefill every prompt in full.  Prefix reuse
turns the repeated prefix prefill into page refcounting, so useful
tokens/s rises with the shared fraction; tokens must stay identical.

A fourth child, ``session``, measures the PERSISTENT-SESSION win: the
same shared-prefix trace served twice through ONE scheduler, whose
``ServeSession`` keeps the device pool and prefix cache alive between
``serve()`` calls.  Trace 2 must record cross-trace prefix hits (its
FIRST request — the cold miss of a per-trace pool — now hits the pages
trace 1 filled), compile nothing new, and serve identical tokens.

A fifth child, ``multitenant``, fires a BURSTY OVERLOAD trace at one
background-pumped session from concurrent producer threads — three
priority classes (batch/web/interactive), a bounded queue that sheds
part of the burst, chunked prefill for the long batch prompts, and
preemption armed.  It reports p50/p99 submit-to-done latency per
priority class and the shed count, and FAILS (nonzero exit) if any
admitted request loses tokens versus an uncontended reference serve of
the same trace, or if the second burst compiles new programs.

A sixth child, ``sharded``, runs on a FORCED-8-DEVICE host platform
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set by the
parent before the child's jax import) and serves the standard trace at
tp=1 and tp=8 through ``Scheduler(tp=...)``.  Tokens must be
bitwise-identical across widths and the compiled-program count must not
grow — the child exits nonzero otherwise.  It reports tokens/s and
tokens/s-per-device; on a host CPU where all forced devices share the
same cores, per-device is the honest throughput figure.

Reports useful tokens/s (only the tokens each request asked for count)
and p50/p99 request completion latency, cold (first trace, compiles
included) and warm (second trace).  Paths must produce IDENTICAL greedy
tokens per request — the token-exactness guard that keeps the
comparison honest (scheduling and caching are never numerics changes).

Each path runs in its OWN subprocess so all are measured cold; the
record lands in ``BENCH_serve.json`` at the repo root via
``core.results.ResultStore`` (CI regenerates it with ``--smoke``).

Usage:
  PYTHONPATH=src python -m benchmarks.bench_serve            # full
  PYTHONPATH=src python -m benchmarks.bench_serve --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import platform
import sys
import threading
import time

import numpy as np

from .common import run_child

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

ARCH = "qwen2.5-3b"


def _trace(smoke: bool):
    """Deterministic mixed-length trace: (prompts, n_tokens per request)."""
    n_req = 16 if smoke else 32
    rng = np.random.default_rng(0)
    from repro import configs

    cfg = configs.get_smoke_config(ARCH)
    plens = rng.choice([3, 5, 8, 11, 13, 16, 20], size=n_req)
    ntoks = rng.choice([4, 8, 12, 20, 28] if smoke else [8, 16, 32, 48, 64],
                       size=n_req)
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in plens]
    return cfg, prompts, [int(n) for n in ntoks]


def _prefix_trace(smoke: bool):
    """Shared-prefix trace: every request = one long common system
    prefix + a short unique tail.  Uses a lossless cache dtype so prefix
    reuse is active (the reuse gate requires token-exactness)."""
    import dataclasses

    from repro import configs

    n_req = 16 if smoke else 32
    prefix_len = 80 if smoke else 160
    rng = np.random.default_rng(7)
    cfg = dataclasses.replace(
        configs.get_smoke_config(ARCH), cache_dtype="float32"
    )
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    tails = rng.choice([2, 3, 5, 8], size=n_req)
    prompts = [
        np.concatenate(
            [prefix, rng.integers(0, cfg.vocab_size, t).astype(np.int32)]
        )
        for t in tails
    ]
    ntoks = [int(n) for n in rng.choice([2, 3, 4], size=n_req)]
    max_len = 128 if smoke else 256
    return cfg, prompts, ntoks, max_len, prefix_len


def _percentiles(lat):
    lat = np.asarray(sorted(lat))
    return {
        "p50_s": round(float(np.percentile(lat, 50)), 4),
        "p99_s": round(float(np.percentile(lat, 99)), 4),
    }


def _digest(tokens_by_rid):
    body = json.dumps([[int(t) for t in tokens_by_rid[r]]
                       for r in sorted(tokens_by_rid)])
    return hashlib.sha1(body.encode()).hexdigest()


def _serve_continuous(cfg, params, prompts, ntoks, max_len, max_slots):
    from repro.serve import Request, Scheduler

    sched = Scheduler(cfg, params, max_slots=max_slots, max_len=max_len)
    reqs = [Request(prompt=p, n_tokens=n) for p, n in zip(prompts, ntoks)]

    def run():
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        toks = {r.rid: r.generated for r in results}
        lat = [r.finished_wall_s for r in results]
        return wall, toks, lat

    cold = run()
    warm = run()
    extra = {
        "decode_steps": sched.last_stats.decode_steps,
        "prefills": sched.last_stats.prefills,
        "occupancy": round(sched.last_stats.occupancy, 3),
        "compiled_programs": sched.compile_counts()["total"],
    }
    return cold, warm, extra


def _serve_prefix(cfg, params, prompts, ntoks, max_len):
    """Shared-prefix trace through prefix-reuse paging vs the PR-3
    monolithic scheduler; both continuous, same slots, same trace."""
    from repro.serve import Request, Scheduler

    reqs = [Request(prompt=p, n_tokens=n) for p, n in zip(prompts, ntoks)]
    out = {}
    for tag, opts in (
        ("reuse", dict(paged=True, prefix_reuse=True, burst_prefill=True,
                       page_size=8)),
        ("monolithic", dict(paged=False)),
    ):
        sched = Scheduler(cfg, params, max_slots=4, max_len=max_len, **opts)

        def run():
            t0 = time.perf_counter()
            results = sched.serve(reqs)
            wall = time.perf_counter() - t0
            toks = {r.rid: r.generated for r in results}
            lat = [r.finished_wall_s for r in results]
            return wall, toks, lat

        cold, warm = run(), run()
        stats = sched.last_stats
        out[tag] = {
            "cold": cold, "warm": warm,
            "extra": {
                "prefills": stats.prefills,
                "prefill_batches": stats.prefill_batches,
                "prefix_reuse_active": stats.prefix_reuse_active,
                "prefix_hit_tokens": (
                    stats.paging["prefix_hit_tokens"] if stats.paging else 0
                ),
                "compiled_programs": sched.compile_counts()["total"],
            },
        }
    return out


def _serve_session(cfg, params, prompts, ntoks, max_len):
    """The warm-session trace: the SAME shared-prefix trace through one
    persistent session, twice.  Trace 1 fills the prefix pages (compiles
    included); trace 2 hits them cross-trace — no pool rebuild, no new
    compiles, identical tokens."""
    from repro.serve import Request, Scheduler

    sched = Scheduler(cfg, params, max_slots=4, max_len=max_len, page_size=8)
    reqs = [Request(prompt=p, n_tokens=n) for p, n in zip(prompts, ntoks)]

    def run():
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        toks = {r.rid: r.generated for r in results}
        stats = sched.last_stats
        return {
            "wall": wall, "toks": toks,
            "lat": [r.finished_wall_s for r in results],
            "prefix_hit_tokens": stats.paging["prefix_hit_tokens"],
            "cross_trace_hit_tokens": stats.paging["cross_trace_hit_tokens"],
            "prefix_misses": stats.paging["prefix_misses"],
            "compiled_programs": sched.compile_counts()["total"],
        }

    return run(), run()


def _multitenant_trace(smoke: bool):
    """Bursty three-class trace: priority-1 batch jobs with long
    (chunk-length) prompts and generations, priority-2 web traffic,
    priority-3 interactive requests with short prompts and tight
    latency expectations.  Lossless cache dtype so chunked prefill and
    preemption are active."""
    import dataclasses

    from repro import configs

    per_class = 6 if smoke else 12
    rng = np.random.default_rng(11)
    cfg = dataclasses.replace(
        configs.get_smoke_config(ARCH), cache_dtype="float32"
    )
    prompts, ntoks, prios = [], [], []
    for prio, plen_pool, ntok_pool in (
        (1, [28, 36, 40], [6, 8]),
        (2, [8, 12, 16], [4, 6]),
        (3, [3, 5, 7], [2, 3]),
    ):
        for _ in range(per_class):
            p = int(rng.choice(plen_pool))
            prompts.append(rng.integers(0, cfg.vocab_size, p).astype(np.int32))
            ntoks.append(int(rng.choice(ntok_pool)))
            prios.append(prio)
    return cfg, prompts, ntoks, prios, 64


def _serve_multitenant(cfg, params, prompts, ntoks, prios, max_len,
                       smoke: bool):
    """Two overload bursts from concurrent producers against ONE driven
    session, then an uncontended reference serve for the token guard."""
    from repro.serve import Request, Scheduler

    n = len(prompts)
    # A deliberately tight queue bound: the cold burst's compile-heavy
    # first steps make 3 producers pile 18 submits onto a 4-deep queue,
    # so admission control visibly sheds under overload.
    sched = Scheduler(cfg, params, max_slots=4, max_len=max_len, page_size=8,
                      max_queue=4, prefill_chunk=8)
    session = sched.session()

    def burst(rid_base):
        lock = threading.Lock()
        waits = []          # (rid, priority, handle, t_submitted)
        shed = []
        by_thread = {t: [i for i in range(n) if i % 3 == t] for t in range(3)}

        def producer(tid):
            for i in by_thread[tid]:
                req = Request(
                    prompt=prompts[i], n_tokens=ntoks[i], rid=rid_base + i,
                    priority=prios[i], tenant=f"class{prios[i]}",
                )
                t_sub = time.perf_counter()
                try:
                    h = session.submit(req)
                except ValueError:       # queue overloaded: shed
                    with lock:
                        shed.append(i)
                    continue
                with lock:
                    waits.append((i, prios[i], h, t_sub))

        # A burst may span several traces (the session can idle briefly
        # between producer waves), so per-burst counters are deltas of
        # the session-lifetime totals, not last_stats of the final trace.
        pre = (session.total_preemptions, session.total_prefill_chunks,
               session.total_shed)
        t0 = time.perf_counter()
        with session.driving():
            threads = [threading.Thread(target=producer, args=(t,))
                       for t in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            lat_by_class, toks = {}, {}
            for i, prio, h, t_sub in waits:
                res = h.wait(timeout=1800)
                lat_by_class.setdefault(prio, []).append(
                    time.perf_counter() - t_sub
                )
                toks[i] = res.generated
        wall = time.perf_counter() - t0
        return {
            "wall": wall, "toks": toks, "shed": sorted(shed),
            "lat_by_class": lat_by_class,
            "preemptions": session.total_preemptions - pre[0],
            "prefill_chunks": session.total_prefill_chunks - pre[1],
            "stats_shed": session.total_shed - pre[2],
            "compiled_programs": sched.compile_counts()["total"],
        }

    b1 = burst(0)
    b2 = burst(1000)

    # Uncontended reference: same requests, same rids (same PRNG streams),
    # fresh scheduler, no queue bound — what each admitted request's
    # tokens MUST be, independent of interleaving/shedding/preemption.
    ref_sched = Scheduler(cfg, params, max_slots=4, max_len=max_len,
                          page_size=8)
    ref = {r.rid: r.generated
           for r in ref_sched.serve(
               [Request(prompt=prompts[i], n_tokens=ntoks[i], rid=i)
                for i in range(n)]
           )}
    ok = True
    for b, base in ((b1, 0), (b2, 1000)):
        for i, toks in b["toks"].items():
            if (len(toks) != ntoks[i]
                    or not np.array_equal(np.asarray(toks), ref[i])):
                ok = False

    # Compile-budget contract under concurrency: one decode program and
    # at most one prefill program per (tail bucket, pow2 burst width).
    # Raw totals may legitimately grow between bursts — burst 2 hits
    # burst 1's cached prefix pages, shortening tails into a bucket the
    # cold burst never used — so we assert the budget formula instead.
    counts = sched.compile_counts()
    widths = [w for w in (1, 2, 4, 8, 16) if w <= 4]
    budget_ok = (
        counts["decode"] == 1
        and all(v <= len(widths) for v in counts["prefill"].values())
        and counts["total"] <= 1 + len(widths) * len(sched.prefill_buckets)
    )
    return b1, b2, ok, budget_ok


def _serve_sharded(smoke: bool):
    """Tensor-parallel serving: the standard mixed trace at tp=1 vs tp=N
    through ``Scheduler(tp=...)``, N being every device the child sees —
    8 forced host devices on a CPU, the chips of an accelerator host.
    Greedy tokens must be bitwise-identical across widths (the exactness
    invariant the ``repro.dist`` serving rules guarantee) and the record
    carries tokens/s AND tokens/s-per-device — on a host CPU the per-
    device figure is the honest one, since 8 forced devices share the
    same cores.  With a single device there is nothing to shard: the
    record says so and the reason goes to stderr."""
    import dataclasses

    import jax

    from repro import configs  # noqa: F401  (via _trace)
    from repro.models import lm
    from repro.serve import Request, Scheduler

    n_dev = jax.device_count()
    if jax.default_backend() == "cpu" and n_dev != 8:
        raise SystemExit(f"sharded child expected 8 forced devices, "
                         f"got {n_dev}")
    if n_dev < 2:
        msg = (f"sharded: skipped, tp needs at least 2 devices and "
               f"{jax.default_backend()} has {n_dev}")
        print(msg, file=sys.stderr)
        print(json.dumps({"path": "sharded", "devices": n_dev, "skipped": msg}))
        return
    cfg, prompts, ntoks = _trace(smoke)
    cfg = dataclasses.replace(cfg, cache_dtype="float32")
    max_len = 64 if smoke else 128
    params = lm.init(jax.random.PRNGKey(0), cfg)
    useful = sum(ntoks)
    rec = {"path": "sharded", "devices": n_dev, "n_requests": len(prompts),
           "useful_tokens": useful}
    keys = {}
    for tp in (1, n_dev):
        sched = Scheduler(cfg, params, max_slots=4, max_len=max_len,
                          page_size=8, tp=tp)
        reqs = [Request(prompt=p, n_tokens=n)
                for p, n in zip(prompts, ntoks)]

        def run():
            t0 = time.perf_counter()
            results = sched.serve(reqs)
            wall = time.perf_counter() - t0
            toks = {r.rid: r.generated for r in results}
            return wall, toks, [r.finished_wall_s for r in results]

        cold, warm = run(), run()
        keys[tp] = _digest(cold[1])
        sub = _path_record(f"tp{tp}", useful, cold, warm, {
            "compiled_programs": sched.compile_counts()["total"],
            "decode_programs": sched.compile_counts()["decode"],
        })
        sub["warm_tokens_per_s_per_device"] = round(
            sub["warm_tokens_per_s"] / tp, 2
        )
        rec[f"tp{tp}"] = sub
    rec["tokens_identical"] = len(set(keys.values())) == 1
    rec["compiles_identical"] = (
        rec["tp1"]["compiled_programs"] == rec[f"tp{n_dev}"]["compiled_programs"]
    )
    print(json.dumps(rec))
    if not rec["tokens_identical"] or not rec["compiles_identical"]:
        raise SystemExit(1)     # exactness guard: fail the parent loudly


def _serve_bucketed(cfg, params, prompts, ntoks, max_len):
    from repro.serve import Engine, bucket_requests

    eng = Engine(cfg, params, max_len=max_len)
    buckets = bucket_requests([list(p) for p in prompts])

    def run():
        t0 = time.perf_counter()
        toks, lat = {}, []
        for idx, arr in buckets:
            # The whole bucket runs until its longest request finishes —
            # that is the pathology continuous batching removes.
            n_max = max(ntoks[i] for i in idx)
            out = eng.generate(arr, n_tokens=n_max, request_ids=idx)
            done = time.perf_counter() - t0
            for row, i in enumerate(idx):
                toks[i] = out.tokens[row, out.prompt_len:out.prompt_len + ntoks[i]]
                lat.append(done)
        return time.perf_counter() - t0, toks, lat

    cold = run()
    warm = run()
    return cold, warm, {"n_buckets": len(buckets)}


def _path_record(path, useful, cold, warm, extra):
    rec = {"path": path, "useful_tokens": useful, **extra}
    for tag, (wall, toks, lat) in (("cold", cold), ("warm", warm)):
        rec[f"{tag}_s"] = round(wall, 3)
        rec[f"{tag}_tokens_per_s"] = round(useful / max(wall, 1e-9), 2)
        rec[f"{tag}_latency"] = _percentiles(lat)
    rec["tokens_key"] = _digest(cold[1])
    rec["cold_warm_identical"] = _digest(cold[1]) == _digest(warm[1])
    return rec


def run_one(path: str, smoke: bool) -> None:
    """Child-process entry: run one serving path cold, print JSON."""
    import jax

    from repro.models import lm

    if path == "sharded":
        _serve_sharded(smoke)
        return

    if path == "session":
        cfg, prompts, ntoks, max_len, prefix_len = _prefix_trace(smoke)
        params = lm.init(jax.random.PRNGKey(0), cfg)
        useful = sum(ntoks)
        t1, t2 = _serve_session(cfg, params, prompts, ntoks, max_len)
        rec = {
            "path": "session",
            "n_requests": len(prompts),
            "shared_prefix_tokens": int(prefix_len),
            "useful_tokens": useful,
            "tokens_identical": _digest(t1["toks"]) == _digest(t2["toks"]),
            "compiles_unchanged": (
                t1["compiled_programs"] == t2["compiled_programs"]
            ),
            "warm_speedup": round(t1["wall"] / max(t2["wall"], 1e-9), 2),
        }
        for tag, t in (("trace1", t1), ("trace2", t2)):
            rec[tag] = {
                "wall_s": round(t["wall"], 3),
                "tokens_per_s": round(useful / max(t["wall"], 1e-9), 2),
                "latency": _percentiles(t["lat"]),
                "prefix_hit_tokens": t["prefix_hit_tokens"],
                "cross_trace_hit_tokens": t["cross_trace_hit_tokens"],
                "prefix_misses": t["prefix_misses"],
                "compiled_programs": t["compiled_programs"],
            }
        print(json.dumps(rec))
        return

    if path == "multitenant":
        cfg, prompts, ntoks, prios, max_len = _multitenant_trace(smoke)
        params = lm.init(jax.random.PRNGKey(0), cfg)
        b1, b2, tokens_ok, budget_ok = _serve_multitenant(
            cfg, params, prompts, ntoks, prios, max_len, smoke
        )
        rec = {
            "path": "multitenant",
            "n_requests": len(prompts),
            "classes": sorted(set(prios)),
            "tokens_match_reference": bool(tokens_ok),
            "compiles_within_budget": bool(budget_ok),
        }
        for tag, b in (("burst1", b1), ("burst2", b2)):
            served = sum(len(t) for t in b["toks"].values())
            rec[tag] = {
                "wall_s": round(b["wall"], 3),
                "served_tokens": served,
                "tokens_per_s": round(served / max(b["wall"], 1e-9), 2),
                "shed_requests": len(b["shed"]),
                "preemptions": b["preemptions"],
                "prefill_chunks": b["prefill_chunks"],
                "compiled_programs": b["compiled_programs"],
                "latency_by_class": {
                    f"priority_{p}": _percentiles(lat)
                    for p, lat in sorted(b["lat_by_class"].items())
                },
            }
        print(json.dumps(rec))
        return

    if path == "prefix":
        cfg, prompts, ntoks, max_len, prefix_len = _prefix_trace(smoke)
        params = lm.init(jax.random.PRNGKey(0), cfg)
        both = _serve_prefix(cfg, params, prompts, ntoks, max_len)
        useful = sum(ntoks)
        rec = {
            "path": "prefix",
            "n_requests": len(prompts),
            "shared_prefix_tokens": int(prefix_len),
            "prompt_tokens": int(sum(p.size for p in prompts)),
        }
        for tag, r in both.items():
            rec[tag] = _path_record(tag, useful, r["cold"], r["warm"], r["extra"])
        rec["tokens_identical"] = (
            rec["reuse"]["tokens_key"] == rec["monolithic"]["tokens_key"]
        )
        for t in ("warm", "cold"):
            rec[f"{t}_speedup"] = round(
                rec["reuse"][f"{t}_tokens_per_s"]
                / max(rec["monolithic"][f"{t}_tokens_per_s"], 1e-9), 2
            )
        print(json.dumps(rec))
        return

    cfg, prompts, ntoks = _trace(smoke)
    max_len = 64 if smoke else 128
    params = lm.init(jax.random.PRNGKey(0), cfg)
    if path == "continuous":
        cold, warm, extra = _serve_continuous(
            cfg, params, prompts, ntoks, max_len, max_slots=4
        )
    else:
        cold, warm, extra = _serve_bucketed(cfg, params, prompts, ntoks, max_len)
    print(json.dumps(_path_record(path, sum(ntoks), cold, warm, extra)))


def _spawn(path: str, smoke: bool, n_devices: int = 0) -> dict:
    argv = ["-m", "benchmarks.bench_serve", "--run-one", path]
    if smoke:
        argv.append("--smoke")
    env_extra = None
    if n_devices:
        # Forced host devices must be set BEFORE the child imports jax.
        env_extra = {
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={n_devices}"
        }
    return run_child(argv, env_extra=env_extra,
                     label=f"bench_serve[{path}]")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (16 requests, short generations)")
    ap.add_argument("--out-root", default=str(REPO_ROOT))
    ap.add_argument("--run-one",
                    choices=["continuous", "bucketed", "prefix", "session",
                             "multitenant", "sharded"],
                    help=argparse.SUPPRESS)  # child-process mode
    args = ap.parse_args()

    if args.run_one:
        run_one(args.run_one, args.smoke)
        return 0

    import jax

    t0 = time.perf_counter()
    cont = _spawn("continuous", args.smoke)
    buck = _spawn("bucketed", args.smoke)
    pref = _spawn("prefix", args.smoke)
    sess = _spawn("session", args.smoke)
    mt = _spawn("multitenant", args.smoke)
    shard = _spawn("sharded", args.smoke, n_devices=8)
    _, prompts, _ = _trace(args.smoke)

    rec = {
        "arch": ARCH,
        "n_requests": len(prompts),
        "useful_tokens": cont["useful_tokens"],
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "continuous": cont,
        "bucketed": buck,
        "prefix_trace": pref,
        "warm_session": sess,
        "multitenant": mt,
        "sharded": shard,
        "warm_speedup": round(
            cont["warm_tokens_per_s"] / max(buck["warm_tokens_per_s"], 1e-9), 2
        ),
        "cold_speedup": round(
            cont["cold_tokens_per_s"] / max(buck["cold_tokens_per_s"], 1e-9), 2
        ),
        "tokens_identical": cont["tokens_key"] == buck["tokens_key"],
        "smoke": bool(args.smoke),
    }

    from repro.core.results import ResultStore

    store = ResultStore(args.out_root)
    path = store.put("BENCH_serve", rec, kind="benchmark",
                     wall_s=time.perf_counter() - t0)
    print(
        f"continuous={cont['warm_tokens_per_s']} tok/s "
        f"bucketed={buck['warm_tokens_per_s']} tok/s "
        f"(warm {rec['warm_speedup']}x, cold {rec['cold_speedup']}x) "
        f"p99 {cont['warm_latency']['p99_s']}s vs "
        f"{buck['warm_latency']['p99_s']}s "
        f"tokens_identical={rec['tokens_identical']} -> {path}"
    )
    print(
        f"prefix trace: reuse={pref['reuse']['warm_tokens_per_s']} tok/s "
        f"monolithic={pref['monolithic']['warm_tokens_per_s']} tok/s "
        f"(warm {pref['warm_speedup']}x) "
        f"hit_tokens={pref['reuse']['prefix_hit_tokens']} "
        f"tokens_identical={pref['tokens_identical']}"
    )
    print(
        f"warm session: trace2={sess['trace2']['tokens_per_s']} tok/s vs "
        f"trace1={sess['trace1']['tokens_per_s']} tok/s "
        f"({sess['warm_speedup']}x) "
        f"cross_trace_hit_tokens={sess['trace2']['cross_trace_hit_tokens']} "
        f"compiles_unchanged={sess['compiles_unchanged']} "
        f"tokens_identical={sess['tokens_identical']}"
    )
    p99s = " ".join(
        f"{k}={v['p99_s']}s"
        for k, v in mt["burst2"]["latency_by_class"].items()
    )
    print(
        f"multitenant: {mt['burst2']['tokens_per_s']} tok/s "
        f"shed={mt['burst1']['shed_requests']}+"
        f"{mt['burst2']['shed_requests']} "
        f"preemptions={mt['burst2']['preemptions']} "
        f"chunks={mt['burst2']['prefill_chunks']} p99 {p99s} "
        f"tokens_match_reference={mt['tokens_match_reference']} "
        f"compiles_within_budget={mt['compiles_within_budget']}"
    )
    if "skipped" in shard:
        print(shard["skipped"])
    else:
        tpn = shard[f"tp{shard['devices']}"]
        print(
            f"sharded: tp{shard['devices']}={tpn['warm_tokens_per_s']} tok/s "
            f"({tpn['warm_tokens_per_s_per_device']}/dev) vs "
            f"tp1={shard['tp1']['warm_tokens_per_s']} tok/s "
            f"programs={tpn['compiled_programs']} "
            f"tokens_identical={shard['tokens_identical']}"
        )
    if not rec["tokens_identical"]:
        print("ERROR: continuous and bucketed paths served different tokens")
        return 1
    if not pref["tokens_identical"]:
        print("ERROR: prefix reuse changed the served tokens")
        return 1
    if not sess["tokens_identical"]:
        print("ERROR: session persistence changed the served tokens")
        return 1
    if sess["trace2"]["cross_trace_hit_tokens"] <= 0:
        print("ERROR: warm-session trace recorded no cross-trace prefix hits")
        return 1
    if not sess["compiles_unchanged"]:
        print("ERROR: the warm-session trace compiled new programs")
        return 1
    if not mt["tokens_match_reference"]:
        print("ERROR: an admitted multitenant request lost tokens vs the "
              "uncontended reference serve")
        return 1
    if not mt["compiles_within_budget"]:
        print("ERROR: multitenant bursts compiled beyond the "
              "1 decode + one prefill per (bucket, width) budget")
        return 1
    if rec["warm_speedup"] <= 1.0:
        print("WARNING: continuous batching did not beat the bucketed path")
    if pref["warm_speedup"] <= 1.0:
        print("WARNING: prefix reuse did not beat the monolithic scheduler")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
