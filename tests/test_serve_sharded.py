"""Tensor/expert-parallel sharded serving: bitwise exactness vs the
single-device scheduler.

The headline invariant: a ``Scheduler(tp=N)`` on the forced-8-device
host platform produces greedy tokens BITWISE-IDENTICAL to the
single-device scheduler for every served architecture family, at
tp=2/4/8, with the compile budget (one decode program + one prefill per
(bucket, width) key) unchanged by sharding.  Exactness is by
construction — the serving rules shard only non-contracting output dims
and ``repl_act`` gathers before every contraction, so the partitioned
program computes every dot product at full length in the same order —
and these tests are the enforcement.

The tp=1 test runs in tier-1 on the ordinary single-device host: it
drives the whole mesh code path (param/pool device_put, ``use_mesh``
around every trace, ``constrain_pool``) without a subprocess.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.dist import sharding as shd
from repro.models import lm
from repro.serve.scheduler import Request, Scheduler

# Keep in sync with tests/test_serve_concurrent.py::SERVED_ARCHS.
SERVED_ARCHS = [
    "qwen2.5-3b", "phi4-mini-3.8b", "mistral-nemo-12b", "musicgen-large",
    "falcon-mamba-7b", "jamba-v0.1-52b", "deepseek-v3-671b",
    "moonshot-v1-16b-a3b",
]

# One subprocess per family: reference serve + tp=2/4/8 re-serves of the
# same trace, token lists compared bitwise in the child, budget asserted
# from the live jit cache sizes.  ``%(arch)s`` is the only template hole.
_EXACTNESS_SNIPPET = r"""
import dataclasses, json
import jax, numpy as np
from repro import configs
from repro.models import lm
from repro.serve.scheduler import Request, Scheduler

assert jax.device_count() == 8, jax.devices()
cfg = configs.get_smoke_config("%(arch)s")
# Lossless cache dtype turns ON every exactness-gated feature the
# architecture permits (prefix reuse, preemption, chunked prefill).
cfg = dataclasses.replace(cfg, cache_dtype="float32")
params = lm.init(jax.random.PRNGKey(0), cfg)

rng = np.random.default_rng(0)
reqs = [Request(prompt=rng.integers(1, 64, p).astype(np.int32),
                n_tokens=t, rid=i, arrival=a)
        for i, (p, t, a) in enumerate(
            [(3, 4, 0), (9, 3, 0), (17, 5, 1), (5, 2, 1), (12, 3, 2)])]
kw = dict(max_slots=3, max_len=32, page_size=8, prefill_chunk=8)

ref_sched = Scheduler(cfg, params, **kw)
ref = [list(map(int, r.tokens)) for r in ref_sched.serve(reqs)]
ref_counts = ref_sched.compile_counts()

for tp in (2, 4, 8):
    s = Scheduler(cfg, params, tp=tp, **kw)
    got = [list(map(int, r.tokens)) for r in s.serve(reqs)]
    c = s.compile_counts()
    print(json.dumps({
        "tp": tp,
        "bitwise": got == ref,
        "decode_compiles": c["decode"],
        "prefill_compiles": sum(c["prefill"].values()),
        "prefill_keys": len(c["prefill"]),
        "ref_decode_compiles": ref_counts["decode"],
        "ref_total": ref_counts["total"],
        "total": c["total"],
    }))
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_tp_serving_bitwise_exact_8dev(arch, run_in_8dev_subprocess):
    records = run_in_8dev_subprocess(
        _EXACTNESS_SNIPPET % {"arch": arch}, timeout=600
    )
    assert [r["tp"] for r in records] == [2, 4, 8]
    for r in records:
        assert r["bitwise"], f"{arch} tp={r['tp']} tokens diverged: {r}"
        # Compile budget: sharding must not add programs — exactly one
        # decode, one prefill per (bucket, width) key actually used,
        # and the same total as the single-device reference.
        assert r["decode_compiles"] == 1, r
        assert r["prefill_compiles"] == r["prefill_keys"], r
        assert r["total"] == r["ref_total"], r


def test_tp1_mesh_serving_exact_single_device():
    """The mesh path itself (device_put layouts, use_mesh around every
    trace, constrain_pool) on a 1-device ("model",) mesh — tier-1
    coverage of the sharded code path without forcing host devices."""
    cfg = configs.get_smoke_config("qwen2.5-3b")
    cfg = dataclasses.replace(cfg, cache_dtype="float32")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(1, 64, p).astype(np.int32),
                    n_tokens=t, rid=i)
            for i, (p, t) in enumerate([(3, 4), (9, 3), (17, 5)])]
    kw = dict(max_slots=3, max_len=32, page_size=8, prefill_chunk=8)
    ref = [list(map(int, r.tokens))
           for r in Scheduler(cfg, params, **kw).serve(reqs)]
    sched = Scheduler(cfg, params, tp=1, **kw)
    got = [list(map(int, r.tokens)) for r in sched.serve(reqs)]
    assert got == ref
    assert sched.compile_counts()["decode"] == 1
    assert sched.mesh is not None and sched.mesh_ctx.exact


def test_tp_knob_validation():
    cfg = configs.get_smoke_config("qwen2.5-3b")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="not both"):
        Scheduler(cfg, params, tp=1,
                  mesh=shd.make_mesh((1,), ("model",)))
    with pytest.raises(ValueError, match="exceeds"):
        Scheduler(cfg, params, tp=jax.device_count() + 1)
    with pytest.raises(ValueError, match=">= 1"):
        Scheduler(cfg, params, tp=0)


def test_serving_param_rules_output_dims_only():
    """Every serving param rule shards only output dims: resolving the
    full smoke param tree must leave each matmul's contracting dim
    replicated (spec entry None at dim 0 of 2-dim leaves)."""
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as shd

    cfg = configs.get_smoke_config("deepseek-v3-671b")  # MLA + MoE + MTP
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    mesh = shd.make_mesh((1,), ("model",))
    tree = shd.serve_param_sharding_tree(shapes, mesh)
    assert len(jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "spec"))) \
        == len(jax.tree.leaves(shapes))

    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        p = shd._path_str(path)
        logical = shd.serve_logical_for_path(p, len(leaf.shape))
        # contraction safety: dense /w leaves never shard their input dim
        if p.endswith("/w") and len(leaf.shape) >= 2:
            assert logical[-2] is None, (p, logical)
        # MLA factors, mamba and wo/w_down/embed stay fully replicated
        for frag in ("wo/w", "q_a/w", "q_b/w", "kv_a/w", "kv_b/w",
                     "embed/w", "in_proj/w", "out_proj/w", "x_proj/w"):
            if p.endswith(frag):
                assert logical == (None,) * len(leaf.shape), (p, logical)
    # spot-check the sharded ones
    assert shd.serve_logical_for_path("blocks/0/mixer/wq/w", 2) == \
        (None, "heads")
    assert shd.serve_logical_for_path("blocks/0/ffn/w_gate", 3) == \
        ("experts", None, "ff")
    assert shd.serve_logical_for_path("blocks/0/ffn/w_down", 3) == \
        ("experts", None, None)
    assert shd.serve_logical_for_path("head/w", 2) == (None, "vocab")
    assert shd.serve_logical_for_path("blocks/ffn/w_up", 4) == \
        (None, "experts", None, "ff")


def test_repl_act_noop_outside_exact_context():
    import jax.numpy as jnp

    from repro.dist import sharding as shd

    x = jnp.ones((4, 4))
    assert shd.repl_act(x) is x                      # no context
    mesh = shd.make_mesh((1,), ("model",))
    with shd.use_mesh(mesh):                         # training ctx: not exact
        assert shd.repl_act(x) is x
    with shd.use_mesh(shd.serving_context(mesh)):
        y = shd.repl_act(x)                          # exact ctx: constrained
        assert y.shape == x.shape
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
