"""Compile a serving configuration's decode and prefill programs at the
cell's shapes for a described TPU v5e chip, with no chip attached, and
print each program's memory analysis.  What the chip's compiler would
refuse (VMEM, tiling, memory) shows here at no chip time.

    JAX_PLATFORMS=cpu python bench/tools/rehearse.py qwen2.5-3b [bucket ...]

Buckets default to the configuration's prefill buckets.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness, serve  # noqa: E402


def main(name: str, buckets=None) -> None:
    from repro.models import lm
    from repro.serve import scheduler as sch

    conf = harness.read_json("configs", f"{name}.json")
    cfg = serve.program_config(conf, {"attn_backend": "pallas"})
    s = conf["serve"]
    S, page, max_len = s["max_slots"], s["page_size"], s["max_len"]
    nb = max_len // page
    n_pages = s.get("n_pages") or S * nb + 1
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
                            tree)

    params = shaped(jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0)))
    pool = shaped(jax.eval_shape(lambda: lm.init_paged_pool(cfg, S, n_pages, page)))
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=dev)  # noqa: E731
    arr = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=dev)  # noqa: E731
    out = {}
    t = time.perf_counter()
    dec = jax.jit(partial(sch._decode_paged_fn, cfg=cfg), donate_argnums=(1,))
    c = dec.lower(params, pool, i32(S), i32(S), arr((S,), jnp.bool_), i32(S, nb),
                  arr((S, 2), jnp.uint32), i32(S), arr((S,), jnp.float32)).compile()
    out["decode"] = _mem(c, time.perf_counter() - t)
    print("decode", out["decode"], flush=True)
    for b in buckets or s["prefill_buckets"]:
        t = time.perf_counter()
        pre = jax.jit(partial(sch._burst_prefill_fn, cfg=cfg, page_size=page,
                              use_context=s.get("prefix_reuse", True)),
                      donate_argnums=(1,))
        c = pre.lower(params, pool, i32(1, b), i32(1, nb), i32(1), i32(1), i32(1),
                      arr((1, 2), jnp.uint32), arr((1,), jnp.float32)).compile()
        out[f"prefill_{b}"] = _mem(c, time.perf_counter() - t)
        print(f"prefill_{b}", out[f"prefill_{b}"], flush=True)
    print(json.dumps(out))


def _mem(compiled, seconds: float) -> dict:
    m = compiled.memory_analysis()
    return {"compile_s": round(seconds, 1),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "has_kernel": "tpu_custom_call" in compiled.as_text()}


if __name__ == "__main__":
    main(sys.argv[1], [int(b) for b in sys.argv[2:]] or None)
