import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first initialization).  Everything below is normal.
"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, lower + compile the real
train/prefill/decode step against abstract inputs on the production mesh
(single-pod 16x16 and multi-pod 2x16x16), record memory_analysis() /
cost_analysis() / the post-SPMD collective schedule, and persist a JSON
record per cell for the roofline layer.

``--tp N`` switches decode cells to the tensor-parallel sharded SERVING
program (the paged decode ``serve.Scheduler(tp=N)`` runs) on a 1-D
N-wide ``("model",)`` mesh — cells keyed ``{arch}__{shape}__tpN``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k --mesh both --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape decode_32k --tp 8 --out results/dryrun
"""
# (no __future__ import: the XLA_FLAGS lines must be the first statements)
import argparse
import pathlib
import re
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.results import ResultStore
from repro.dist import sharding as shd
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.models.config import LMConfig
from repro.optim import get_optimizer
from repro.train.train_step import make_train_step

# Archs whose parameter count makes full-Adam moments unaffordable at
# 512 chips -> factored second moments (see DESIGN.md §5).
ADAFACTOR_ARCHS = {"qwen2-vl-72b", "deepseek-v3-671b", "jamba-v0.1-52b"}


# ----------------------------- sharding helpers ------------------------------
_CACHE_LOGICAL = {
    "k": (None, "batch", None, "kv_heads", "head_dim"),
    "v": (None, "batch", None, "kv_heads", "head_dim"),
    "c_kv": (None, "batch", None, "tp"),
    "k_rope": (None, "batch", None, None),
    "h": (None, "batch", "d_inner", None),
    "conv": (None, "batch", None, "d_inner"),
}

_BATCH_LOGICAL = {
    "tokens": ("batch", None),
    "targets": ("batch", None),
    "loss_mask": ("batch", None),
    "embeds": ("batch", "seq_sp", None),
    "position_ids": (None, "batch", None),
}


def _leaf_key(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def cache_shardings(cache_shapes, ctx: shd.MeshContext):
    def one(path, leaf):
        logical = _CACHE_LOGICAL.get(_leaf_key(path), (None,) * len(leaf.shape))
        return ctx.sharding(logical, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def batch_shardings(batch_shapes, ctx: shd.MeshContext):
    def one(path, leaf):
        logical = _BATCH_LOGICAL.get(_leaf_key(path), (None,) * len(leaf.shape))
        return ctx.sharding(logical, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, batch_shapes)


_MOMENT_SUFFIX = re.compile(r"/(m|v|err)$")
_FACTORED_ROW = re.compile(r"/vr$")
_FACTORED_COL = re.compile(r"/vc$")
_QUANT = re.compile(r"/(m_q|m_s|v_q|v_s)$")


def state_shardings(state_shapes, ctx: shd.MeshContext):
    """Shardings for the full TrainState: params by PARAM_RULES; optimizer
    moments inherit their parameter's logical axes (factored moments drop
    the corresponding reduced dim)."""

    def one(path, leaf):
        pstr = shd._path_str(path)
        ndim = len(leaf.shape)
        base = pstr
        transform = None
        if _QUANT.search(pstr):
            return ctx.sharding((None,) * ndim, leaf.shape)
        if _FACTORED_ROW.search(pstr):
            base = _FACTORED_ROW.sub("", pstr)
            transform = "row"
        elif _FACTORED_COL.search(pstr):
            base = _FACTORED_COL.sub("", pstr)
            transform = "col"
        elif _MOMENT_SUFFIX.search(pstr):
            base = _MOMENT_SUFFIX.sub("", pstr)
        base = base.replace("/mu/", "/params/")
        logical = shd.logical_for_path(
            base, ndim if transform is None else ndim + 1
        )
        if transform == "row":          # vr: param shape minus last dim
            logical = logical[:-1]
        elif transform == "col":        # vc: minus second-to-last dim
            logical = logical[:-2] + logical[-1:]
        if len(logical) != ndim:
            logical = (None,) * ndim
        return ctx.sharding(logical, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, state_shapes)


# ----------------------------- cell construction -------------------------------
def build_train_cell(cfg: LMConfig, shape, mesh):
    ctx = shd.MeshContext(mesh)
    opt = get_optimizer(
        "adafactor" if cfg.name in ADAFACTOR_ARCHS else "adamw", 1e-4
    )
    step = make_train_step(cfg, opt)

    def init_fn(key):
        params = lm.init(key, cfg)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch_shapes = specs_mod.batch_struct(cfg, "train", shape.global_batch, shape.seq_len)
    in_sh = (state_shardings(state_shapes, ctx), batch_shardings(batch_shapes, ctx))
    out_sh = (in_sh[0], None)
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0,))
    return fn, (state_shapes, batch_shapes)


def build_prefill_cell(cfg: LMConfig, shape, mesh):
    ctx = shd.MeshContext(mesh)
    params_shapes = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    batch_shapes = specs_mod.batch_struct(cfg, "prefill", shape.global_batch, shape.seq_len)
    p_sh = state_shardings(params_shapes, ctx)
    b_sh = batch_shardings(batch_shapes, ctx)

    def prefill_fn(params, batch):
        return lm.prefill(params, batch, cfg, max_len=shape.seq_len)

    fn = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh))
    return fn, (params_shapes, batch_shapes)


def build_decode_cell(cfg: LMConfig, shape, mesh):
    ctx = shd.MeshContext(mesh)
    B, S = shape.global_batch, shape.seq_len
    params_shapes = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    cache_shapes = jax.eval_shape(partial(lm.init_cache, cfg, B, S))
    inputs_shapes = specs_mod.batch_struct(cfg, "decode", B, S)
    p_sh = state_shardings(params_shapes, ctx)
    c_sh = cache_shardings(cache_shapes, ctx)
    i_sh = batch_shardings(inputs_shapes, ctx)
    pos_sh = NamedSharding(mesh, P())

    def serve_step(params, inputs, pos, caches):
        return lm.decode_step(params, inputs, pos, caches, cfg)

    fn = jax.jit(
        serve_step,
        in_shardings=(p_sh, i_sh, pos_sh, c_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(3,),
    )
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (params_shapes, inputs_shapes, pos_shape, cache_shapes)


def build_decode_tp_cell(cfg: LMConfig, shape, mesh, page_size: int = 16):
    """The tensor-parallel PAGED serving decode program — the program
    ``serve.Scheduler(tp=N)`` actually runs — on a 1-D ``("model",)``
    mesh: params laid out by the serving rules (column- and
    row-parallel projections, vocab-split embedding and head), K/V
    pages head-sharded, block tables / positions / inputs replicated
    (they are host-driven state), and the returned pool pinned back to
    its input layout so donation aliases without a relayout.

    Must be traced under ``shd.serving_context(mesh)`` (run_cell does
    this) so the in-model serving collectives are live: the row-parallel
    all-reduces, the embedding lookup and the logits gather."""
    B, S = shape.global_batch, shape.seq_len
    n_pages = 1 + B * (S // page_size)
    params_shapes = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    pool_shapes = jax.eval_shape(
        partial(lm.init_paged_pool, cfg, B, n_pages, page_size)
    )
    inputs_shapes = specs_mod.batch_struct(cfg, "decode", B, S)
    p_sh = shd.serve_param_sharding_tree(params_shapes, mesh)
    pool_sh = shd.serve_pool_sharding_tree(pool_shapes, mesh)
    repl = NamedSharding(mesh, P())
    i_sh = jax.tree.map(lambda _: repl, inputs_shapes)

    def serve_step(params, inputs, pos, pool, block_tables):
        return lm.decode_step_paged(params, inputs, pos, pool,
                                    block_tables, cfg)

    fn = jax.jit(
        serve_step,
        in_shardings=(p_sh, i_sh, repl, pool_sh, repl),
        out_shardings=(repl, pool_sh),
        donate_argnums=(3,),
    )
    pos_shape = jax.ShapeDtypeStruct((B,), jnp.int32)
    bt_shape = jax.ShapeDtypeStruct((B, S // page_size), jnp.int32)
    return fn, (params_shapes, inputs_shapes, pos_shape, pool_shapes,
                bt_shape)


# ----------------------------- analysis ----------------------------------------
# Shape/dtype parsing and the collective taxonomy live in
# repro.launch.hlo_analysis (shared with repro.analysis); this module
# keeps only the naive whole-text scan for the "collectives_naive"
# record field.
from repro.launch.hlo_analysis import _COLLECTIVES, op_output_bytes


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Per-collective-type op counts + output bytes (per-device, post-SPMD).

    Naive: every op line counts once, regardless of loop trip counts —
    ``rec["analysis"]`` (``analyze_hlo``) holds the trip-aware totals."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)\s*)?([a-z0-9-]+)", ls)
        if not m:
            continue
        op = m.group(1)
        # normalize: all-gather-start, all-reduce-done, etc.
        for coll in _COLLECTIVES:
            if op == coll or op.startswith(coll + "-"):
                if op.endswith("-done"):
                    break  # counted at -start
                stats[coll]["count"] += 1
                stats[coll]["bytes"] += op_output_bytes(ls)
                break
    return stats


def analyze_compiled(lowered, compiled, hlo_path: Optional[pathlib.Path] = None) -> Dict[str, Any]:
    rec: Dict[str, Any] = {}
    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec["cost"] = {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
            "transcendentals": float(cost.get("transcendentals", -1)),
        }
    except Exception as e:  # pragma: no cover
        rec["cost"] = {"error": str(e)}
    try:
        from repro.launch.hlo_analysis import analyze_hlo

        hlo = compiled.as_text()
        rec["hlo_ops"] = len(hlo.splitlines())
        rec["collectives_naive"] = collective_stats(hlo)
        # Trip-count-aware analysis (cost_analysis counts while bodies once).
        rec["analysis"] = analyze_hlo(hlo)
    except Exception as e:  # pragma: no cover
        rec["analysis"] = {"error": str(e)}
        return rec
    if hlo_path is not None:
        # Persist compressed HLO so §Perf iterations can re-analyze
        # offline without recompiling.  Persistence is best-effort and
        # must never clobber the computed analysis: zstandard is an
        # optional dependency (the ``hlo`` extra) and the write can fail.
        try:
            import zstandard

            hlo_path.write_bytes(
                zstandard.ZstdCompressor(level=6).compress(hlo.encode())
            )
        except Exception as e:  # pragma: no cover
            rec["hlo_persist_error"] = f"{type(e).__name__}: {e}"
    return rec


# ----------------------------- runner -------------------------------------------
# Stderr capture moved to repro.analysis.remat (shared with the lint's
# collectives/remat check); dryrun keeps the per-cell remat_warnings
# count and the stderr tail on FAILED cells.
from repro.analysis.remat import REMAT_WARNING
from repro.analysis.remat import capture_fd_stderr as _capture_fd_stderr


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, force: bool = False,
             overrides: Optional[Dict[str, Any]] = None,
             tp: int = 0) -> Dict[str, Any]:
    mesh_tag = f"tp{tp}" if tp else ("pod2x16x16" if multi_pod else "pod16x16")
    store = ResultStore(out_dir)
    name = f"{arch}__{shape_name}__{mesh_tag}"
    if name in store and not force:
        return store.get(name)

    entry = configs.entry(arch)
    shape = configs.SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch, "status": "pending",
    }
    if shape_name not in entry.shape_names():
        rec["status"] = "skipped:full-attention-500k"
        store.put(name, rec, kind="dryrun")
        return rec
    if tp and shape.kind != "decode":
        rec["status"] = "skipped:tp-decode-only"
        store.put(name, rec, kind="dryrun")
        return rec

    if tp:
        mesh = shd.make_mesh((tp,), ("model",))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = entry.config(**(overrides or {}))
    if overrides:
        rec["overrides"] = dict(overrides)
    t_cell = time.time()
    try:
        captured: Dict[str, str] = {"text": ""}
        trace_ctx = shd.serving_context(mesh) if tp else mesh
        with shd.use_mesh(trace_ctx), _capture_fd_stderr(captured):
            t0 = time.time()
            if tp:
                fn, args = build_decode_tp_cell(cfg, shape, mesh)
            elif shape.kind == "train":
                fn, args = build_train_cell(cfg, shape, mesh)
            elif shape.kind == "prefill":
                fn, args = build_prefill_cell(cfg, shape, mesh)
            else:
                fn, args = build_decode_cell(cfg, shape, mesh)
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        rec["remat_warnings"] = captured["text"].count(REMAT_WARNING)
        rec.update(
            analyze_compiled(
                lowered, compiled,
                hlo_path=store.path(name).with_suffix(".hlo.zst"),
            )
        )
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["n_devices"] = mesh.devices.size
        rec["status"] = "ok"
        print(compiled.memory_analysis())
        cost = rec.get("cost", {})
        print(f"[{arch} x {shape_name} x {mesh_tag}] OK "
              f"flops={cost.get('flops'):.3e} lower={rec['lower_s']}s "
              f"compile={rec['compile_s']}s")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        rec["remat_warnings"] = captured["text"].count(REMAT_WARNING)
        if captured["text"]:
            rec["stderr_tail"] = captured["text"][-4000:]
        print(f"[{arch} x {shape_name} x {mesh_tag}] FAILED: {rec['error']}")
    store.put(name, rec, kind="dryrun", wall_s=time.time() - t_cell)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--fail-on-remat", action="store_true",
                    help="exit nonzero if any cell compiled with XLA "
                         "'Involuntary full rematerialization' warnings "
                         "(missing/contradictory sharding annotations)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. ssm_impl=pallas)")
    ap.add_argument("--tp", type=int, default=0,
                    help="compile decode cells as tensor-parallel sharded "
                         "serving programs (the Scheduler(tp=N) paged "
                         "decode) on a 1-D N-wide ('model',) mesh instead "
                         "of the production pod meshes; non-decode shapes "
                         "are skipped")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else configs.ARCH_NAMES
    if args.tp:
        meshes = [False]        # one tp-mesh pass; --mesh is pod-only
    else:
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = n_remat = 0
    for arch in archs:
        shapes = [args.shape] if args.shape else list(configs.SHAPES)
        for shape_name in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape_name, mp, out_dir,
                               force=args.force, overrides=overrides,
                               tp=args.tp)
                s = rec["status"]
                n_ok += s == "ok"
                n_fail += s == "error"
                n_skip += s.startswith("skipped")
                w = rec.get("remat_warnings", 0)
                if w:
                    n_remat += w
                    print(f"[{rec['arch']} x {rec['shape']} x {rec['mesh']}] "
                          f"{w} involuntary-rematerialization warning(s)")
    print(f"dry-run summary: ok={n_ok} failed={n_fail} skipped={n_skip} "
          f"remat_warnings={n_remat}")
    if args.fail_on_remat and n_remat:
        print("FAIL: involuntary full rematerializations — enrich the "
              "sharding annotations (see ROADMAP dry-run notes)")
        return 1
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
