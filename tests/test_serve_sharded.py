"""Tensor/expert-parallel sharded serving.

``Scheduler(tp=N)`` serves the same paged programs over a 1-D
``("model",)`` mesh laid out by the serving rules: column-parallel
Q/K/V and up/gate projections, row-parallel ``wo`` and dense ``w_down``
whose partial sums one all-reduce adds, embedding and LM head split by
vocabulary, MoE banks by experts.  The all-reduce adds in a different
order than one device's dot, so tp is not bitwise equal to tp=1: the
tests hold it to tp=1's logits within a stated tolerance, and to bitwise
equality where the program fixes the order itself — one tp program under
different arrivals and batching.  ``tests/test_serve_tp_reference.py``
holds tp=4 to the plain float32 reference.

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

# Logits at tp=N against tp=1: the smoke configs compute in float32, and
# tp reorders sums (the row-parallel all-reduce over d_model / N partial
# products, the vocab-split embedding adds zeros).  A few float32 ulps
# (2^-24) of reordering through two layers stays far inside 2^-14 of the
# largest logit; a real divergence (a lost shard, a doubled partial sum)
# moves logits by O(1).
TP_LOGIT_TOL_REL = 2.0 ** -14

# One subprocess per family: teacher-forced paged decode logits at
# tp=2/4/8 against tp=1 from an empty pool, then one tp Scheduler serving
# the same requests under two arrival orders (different admission,
# batching and chunking), token lists compared bitwise, and the compile
# budget asserted from the live jit cache sizes.  ``%(arch)s`` is the
# only template hole.
_TP_SNIPPET = r"""
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.dist import sharding as shd
from repro.models import lm
from repro.serve.scheduler import Request, Scheduler

assert jax.device_count() == 8, jax.devices()
cfg = configs.get_smoke_config("%(arch)s")
# Lossless cache dtype turns ON every exactness-gated feature the
# architecture permits (prefix reuse, preemption, chunked prefill).
cfg = dataclasses.replace(cfg, cache_dtype="float32")
params = lm.init(jax.random.PRNGKey(0), cfg)
B, T, PAGE, MAX_LEN = 3, 12, 8, 32
nb = MAX_LEN // PAGE
bt = jnp.arange(1, 1 + B * nb, dtype=jnp.int32).reshape(B, nb)
toks = np.random.default_rng(1).integers(1, 64, (T, B)).astype(np.int32)
decode = jax.jit(lambda p, pool, t, pos: lm.decode_step_paged(
    p, {"tokens": t[:, None]}, pos, pool, bt, cfg))


def lockstep(mesh):
    p = params if mesh is None else jax.device_put(
        params, shd.serve_param_sharding_tree(params, mesh))
    pool = lm.init_paged_pool(cfg, B, B * nb + 1, PAGE, mesh=mesh)
    out = []
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        if mesh is None:
            lg, pool = decode(p, pool, jnp.asarray(toks[t]), pos)
        else:
            with shd.use_mesh(shd.serving_context(mesh)):
                lg, pool = decode(p, pool, jnp.asarray(toks[t]), pos)
        out.append(np.asarray(lg[:, -1], np.float32))
    return np.stack(out)


rng = np.random.default_rng(0)
shape = [(3, 4, 0), (9, 3, 0), (17, 5, 1), (5, 2, 1), (12, 3, 2)]
prompts = [rng.integers(1, 64, p).astype(np.int32) for p, _, _ in shape]
reqs = [Request(prompt=pr, n_tokens=t, rid=i, arrival=a)
        for i, (pr, (_, t, a)) in enumerate(zip(prompts, shape))]
# The same requests, all due at once and submitted in reverse.
burst = [Request(prompt=pr, n_tokens=t, rid=i, arrival=0)
         for i, (pr, (_, t, _)) in reversed(list(enumerate(zip(prompts, shape))))]
kw = dict(max_slots=3, max_len=MAX_LEN, page_size=PAGE, prefill_chunk=8)

# The single-device reference serves both orders too, so its compile
# count is the budget the tp program must match.
ref_sched = Scheduler(cfg, params, **kw)
ref_sched.serve(reqs)
ref_sched.serve(burst)
ref_counts = ref_sched.compile_counts()
want = lockstep(None)

for tp in (2, 4, 8):
    mesh = shd.make_mesh((tp,), ("model",))
    got = lockstep(mesh)
    s = Scheduler(cfg, params, tp=tp, **kw)
    a = {r.rid: list(map(int, r.tokens)) for r in s.serve(reqs)}
    b = {r.rid: list(map(int, r.tokens)) for r in s.serve(burst)}
    c = s.compile_counts()
    print(json.dumps({
        "tp": tp,
        "max_logit_diff": float(np.abs(got - want).max()),
        "max_abs_logit": float(np.abs(want).max()),
        "bitwise_across_scheduling": a == b,
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
    """tp=2/4/8 agree with tp=1 within ``TP_LOGIT_TOL_REL``, and one tp
    program serves bitwise the same tokens under different arrivals."""
    records = run_in_8dev_subprocess(_TP_SNIPPET % {"arch": arch}, timeout=600)
    assert [r["tp"] for r in records] == [2, 4, 8]
    for r in records:
        assert r["max_logit_diff"] <= TP_LOGIT_TOL_REL * r["max_abs_logit"], (arch, r)
        assert r["bitwise_across_scheduling"], f"{arch} tp={r['tp']}: {r}"
        # Compile budget: sharding must not add programs — exactly one
        # decode, one prefill per (bucket, width) key actually used.
        assert r["decode_compiles"] == 1, r
        assert r["prefill_compiles"] == r["prefill_keys"], r
        # and the same total as the single-device reference.
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
    assert sched.mesh is not None and sched.mesh_ctx.serving


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
    """The serving layout is row-parallel where it should be: each
    column-parallel projection splits its output dim, and the
    row-parallel projection that consumes it splits its input rows by the
    same logical name, so the activation between them stays on its
    device; the embedding and LM head split the vocabulary; MLA factors
    and mamba parameters stay whole (their contractions need whole
    operands), and MoE banks split the experts."""
    cfg = configs.get_smoke_config("deepseek-v3-671b")  # MLA + MoE + MTP
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    mesh = shd.make_mesh((1,), ("model",))
    tree = shd.serve_param_sharding_tree(shapes, mesh)
    assert len(jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "spec"))) \
        == len(jax.tree.leaves(shapes))

    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        p = shd._path_str(path)
        logical = shd.serve_logical_for_path(p, len(leaf.shape))
        if p.endswith(("q_a/w", "q_b/w", "kv_a/w", "kv_b/w", "in_proj/w",
                       "out_proj/w", "x_proj/w", "mtp/proj/w")):
            assert logical == (None,) * len(leaf.shape), (p, logical)
        if p.endswith(("wo/w", "w_down/w")):
            assert logical[-2] in ("heads", "ff") and logical[-1] is None, (p, logical)
        if p.endswith(("wq/w", "w_up/w", "w_gate/w", "head/w")):
            assert logical[-2] is None and logical[-1] is not None, (p, logical)
    # column-parallel producer and row-parallel consumer share the split
    rule = shd.serve_logical_for_path
    assert rule("blocks/0/mixer/wq/w", 2) == (None, "heads")
    assert rule("blocks/0/mixer/wo/w", 2) == ("heads", None)
    assert rule("blocks/0/mixer/wk/w", 2) == (None, "kv_heads")
    assert rule("blocks/0/ffn/w_up/w", 2) == (None, "ff")
    assert rule("blocks/0/ffn/w_down/w", 2) == ("ff", None)
    assert rule("blocks/0/ffn/shared/w_down/w", 3) == (None, "ff", None)
    assert rule("embed/w", 2) == ("vocab", None)
    assert rule("head/w", 2) == (None, "vocab")
    # MoE banks: expert parallelism, as before
    assert rule("blocks/0/ffn/w_gate", 3) == ("experts", None, "ff")
    assert rule("blocks/0/ffn/w_down", 3) == ("experts", None, None)
    assert rule("blocks/ffn/w_up", 4) == (None, "experts", None, "ff")
    # norms and biases replicate
    assert rule("blocks/0/ln1/scale", 1) == (None,)
    assert rule("blocks/0/mixer/wq/b", 1) == (None,)


def test_repl_act_noop_outside_exact_context():
    import jax.numpy as jnp

    from repro.dist import sharding as shd

    x = jnp.ones((4, 4))
    assert shd.repl_act(x) is x                      # no context
    mesh = shd.make_mesh((1,), ("model",))
    with shd.use_mesh(mesh):                         # training ctx
        assert shd.repl_act(x) is x
    with shd.use_mesh(shd.serving_context(mesh)):
        y = shd.repl_act(x)                          # serving ctx: constrained
        assert y.shape == x.shape
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
