"""Kernel micro-benchmarks: Pallas (interpret on CPU) vs pure-jnp oracle.
On CPU these measure the XLA lowering of the kernel body; on TPU the same
entry points run the compiled Pallas kernels.

Persists ``BENCH_kernels.json`` at the repo root (one record per kernel
size, plus the composed FP-DCIM matmul accuracy figure); CI regenerates
it with ``--smoke`` on every PR::

  PYTHONPATH=src python -m benchmarks.bench_kernels --smoke

The sweep runs in a SUBPROCESS child (``common.run_child``) so the
timings are cold and, critically, so a crashing sweep fails the parent
instead of leaving last run's ``BENCH_kernels.json`` in place looking
current; ``--in-process`` keeps the old single-process path for
debugging under a debugger/profiler.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform

from .common import emit, run_child, time_fn

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# (full, smoke) problem sizes per kernel.
_PARETO_P = ((128, 512, 1024), (128, 256))
_MVM_MKN = (((128, 512, 128), (256, 2048, 256)), ((64, 256, 64),))
_PREALIGN = (((64, 16, 64), (256, 32, 128)), ((64, 16, 64),))
# (B, Hk, G, hd, page, nb): slots x kv-heads x group x head-dim, paged KV
_PAGED_DECODE = (((8, 8, 4, 128, 16, 32), (16, 8, 8, 128, 16, 64)),
                 ((4, 2, 4, 64, 16, 8),))
# (B, T, Hk, G, hd, L): burst width x tail x heads x context capacity
_PREFIX = (((8, 64, 8, 4, 128, 512),), ((4, 16, 2, 4, 64, 128),))


def run(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    kernels: dict = {}

    # pareto_rank: P x P dominance
    for P in _PARETO_P[smoke]:
        F = jnp.asarray(rng.normal(size=(P, 4)).astype(np.float32))
        us_k = time_fn(ops.dominance_matrix, F)
        us_r = time_fn(ref.dominance_matrix_ref, F)
        pairs = round(P * P / us_k * 1e6, 1)
        emit(f"pareto_rank.P{P}", us_k,
             f"ref_us={us_r:.1f} pairs_per_s={pairs:.3g}")
        kernels[f"pareto_rank.P{P}"] = {
            "us": round(us_k, 1), "ref_us": round(us_r, 1),
            "pairs_per_s": pairs,
        }

    # dcim_mvm: bit-serial exact int matmul
    for M, K, N in _MVM_MKN[smoke]:
        x = jnp.asarray(rng.integers(-128, 128, (M, K)).astype(np.int32))
        w = jnp.asarray(rng.integers(-128, 128, (K, N)).astype(np.int32))
        us_k = time_fn(lambda a, b: ops.dcim_mvm(a, b, B_x=8, B_w=8, k=4), x, w)
        us_r = time_fn(ref.dcim_mvm_ref, x, w)
        macs = M * K * N
        gmacs = round(macs / us_k * 1e-3, 2)
        emit(f"dcim_mvm.{M}x{K}x{N}", us_k,
             f"ref_us={us_r:.1f} gmacs_per_s={gmacs:.2f}")
        kernels[f"dcim_mvm.{M}x{K}x{N}"] = {
            "us": round(us_k, 1), "ref_us": round(us_r, 1),
            "gmacs_per_s": gmacs,
        }

    # fp_prealign — through the public dispatcher (XLA ref on CPU, the
    # compiled kernel on TPU), vs the ref timed directly.
    for shape in _PREALIGN[smoke]:
        M, G, H = shape
        x = jnp.asarray(rng.normal(size=(M, G * H)).astype(np.float32))
        xg = x.reshape(shape)
        us_k = time_fn(lambda a: ops.fp_prealign(a, H=H, B_M=8), x)
        us_r = time_fn(lambda a: ref.fp_prealign_ref(a, B_M=8), xg)
        name = f"fp_prealign.{'x'.join(map(str, shape))}"
        emit(name, us_k, f"ref_us={us_r:.1f}")
        kernels[name] = {"us": round(us_k, 1), "ref_us": round(us_r, 1)}

    # paged_decode: fused block-table attention vs the XLA gather+attend
    # baseline it replaces.  "us" is the auto dispatch (fused kernel on
    # TPU, XLA ref on CPU); "interp_us" times the kernel body through
    # the Pallas interpreter (parity-path cost, not a perf figure).
    for B, Hk, G, hd, page, nb in _PAGED_DECODE[smoke]:
        n_pages = 1 + B * nb
        S = nb * page
        kp = jnp.asarray(rng.standard_normal((1, n_pages, page, Hk, hd)),
                         jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((1, n_pages, page, Hk, hd)),
                         jnp.bfloat16)
        bt = jnp.asarray(
            rng.permutation(np.arange(1, n_pages))[: B * nb].reshape(B, nb),
            jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * G, hd)), jnp.float32)
        pos = jnp.asarray(rng.integers(S // 2, S, B), jnp.int32)
        us_k = time_fn(lambda *a: ops.paged_decode_gqa(*a, 0), q, kp, vp, bt, pos)
        us_r = time_fn(lambda *a: ops.paged_decode_gqa(*a, 0, backend="xla"),
                       q, kp, vp, bt, pos)
        us_i = time_fn(
            lambda *a: ops.paged_decode_gqa(*a, 0, backend="pallas_interpret"),
            q, kp, vp, bt, pos)
        toks = round(B / us_k * 1e6, 1)
        kv_bytes = 2 * B * S * Hk * hd * kp.dtype.itemsize   # K+V read
        name = f"paged_decode.B{B}xS{S}xH{Hk * G}x{hd}"
        emit(name, us_k, f"ref_us={us_r:.1f} interp_us={us_i:.1f} "
             f"tokens_per_s={toks:.4g}")
        kernels[name] = {
            "us": round(us_k, 1), "ref_us": round(us_r, 1),
            "interp_us": round(us_i, 1), "tokens_per_s": toks,
            "kv_bytes_per_step": kv_bytes,
        }

    # prefix_prefill: fused [ctx ; causal tail] vs concat+prefix_attention
    for B, T, Hk, G, hd, L in _PREFIX[smoke]:
        kc = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
        kt = jnp.asarray(rng.standard_normal((B, T, Hk, hd)), jnp.float32)
        vt = jnp.asarray(rng.standard_normal((B, T, Hk, hd)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((B, T, Hk * G, hd)), jnp.float32)
        ctx = jnp.asarray(rng.integers(0, L + 1, B), jnp.int32)
        us_k = time_fn(lambda *a: ops.prefix_prefill(*a), q, kc, vc, kt, vt, ctx)
        us_r = time_fn(lambda *a: ops.prefix_prefill(*a, backend="xla"),
                       q, kc, vc, kt, vt, ctx)
        us_i = time_fn(
            lambda *a: ops.prefix_prefill(*a, backend="pallas_interpret"),
            q, kc, vc, kt, vt, ctx)
        toks = round(B * T / us_k * 1e6, 1)
        score_bytes = 4 * B * Hk * G * T * (L + T)   # f32 scores the XLA path
        name = f"prefix_prefill.B{B}xT{T}xL{L}xH{Hk * G}x{hd}"
        emit(name, us_k, f"ref_us={us_r:.1f} interp_us={us_i:.1f} "
             f"tokens_per_s={toks:.4g}")
        kernels[name] = {
            "us": round(us_k, 1), "ref_us": round(us_r, 1),
            "interp_us": round(us_i, 1), "tokens_per_s": toks,
            "xla_score_bytes": score_bytes,
        }

    # composed FP-DCIM matmul vs f32 matmul accuracy+speed
    x = jnp.asarray(rng.normal(size=(64, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    us_k = time_fn(lambda a, b: ops.dcim_fp_matmul(a, b, H=64, B_M=8, B_w=8, k=4), x, w)
    got = np.asarray(ops.dcim_fp_matmul(x, w, H=64, B_M=8, B_w=8, k=4))
    want = np.asarray(ref.fp_matmul_f32_ref(x, w))
    rel = float(np.median(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
    emit("dcim_fp_matmul.64x256x64", us_k, f"median_rel_err={rel:.2e}")
    kernels["dcim_fp_matmul.64x256x64"] = {
        "us": round(us_k, 1), "median_rel_err": rel,
    }

    return {
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "smoke": bool(smoke),
        "kernels": kernels,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (smallest problem sizes only)")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_kernels.json"))
    ap.add_argument("--in-process", action="store_true",
                    help="run the sweep in this process (debugging)")
    ap.add_argument("--run-one", choices=["sweep"],
                    help=argparse.SUPPRESS)  # child-process mode
    args = ap.parse_args()

    if args.run_one:        # child: sweep, JSON record on the last line
        print(json.dumps(run(args.smoke)))
        return 0

    if args.in_process:
        rec = run(args.smoke)
    else:
        argv = ["-m", "benchmarks.bench_kernels", "--run-one", "sweep"]
        if args.smoke:
            argv.append("--smoke")
        rec = run_child(argv, label="bench_kernels[sweep]", echo=True)

    from repro.core.results import dump_json

    path = dump_json(args.out, rec)
    print(f"{len(rec['kernels'])} kernel size(s) "
          f"[{rec['backend']}] -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
