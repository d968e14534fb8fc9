"""Distribution layer tests: logical-axis resolution, divisibility
fallback, param rules, HLO analyzer, and (via the shared
``run_in_8dev_subprocess`` harness in conftest) sharded train-step
execution + compressed ring all-reduce."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist import sharding as shd
from repro.launch.hlo_analysis import analyze_hlo, parse_computations


class TestMeshContext:
    def _mesh(self):
        dev = np.array(jax.devices())
        return Mesh(dev.reshape(1, 1), ("data", "model"))

    def test_divisibility_fallback_replicates(self):
        mesh = self._mesh()
        ctx = shd.MeshContext(
            mesh, {"batch": ("data",), "heads": ("model",)}
        )
        # dims divisible by 1 -> sharded on the (trivial) axis
        assert ctx.spec(("batch", "heads"), (4, 8)) == P("data", "model")

    def test_fallback_on_indivisible(self):
        # Fake a bigger mesh via rules resolution logic only.
        dev = np.array(jax.devices())
        mesh = Mesh(dev.reshape(1, 1), ("data", "model"))

        class Fake(shd.MeshContext):
            def __init__(self):
                self.mesh = mesh
                self.rules = {"kv_heads": ("model",), "head_dim": ("model",)}

            def axes_for(self, logical, dim):
                axes = self.rules.get(logical)
                if not axes:
                    return None
                size = 16  # pretend model axis is 16-wide
                if dim % size != 0:
                    return None
                return axes

        ctx = Fake()
        # kv_heads=8 indivisible by 16 -> None; head_dim=128 -> model
        spec = ctx.spec((None, "kv_heads", "head_dim"), (2, 8, 128))
        assert spec == P(None, None, "model")

    def test_axis_used_once(self):
        mesh = self._mesh()
        ctx = shd.MeshContext(mesh, {"a": ("model",), "b": ("model",)})
        spec = ctx.spec(("a", "b"), (4, 4))
        assert spec == P("model", None)  # second use of model blocked

    def test_multi_axis_prefix_fallback(self):
        dev = np.array(jax.devices())
        mesh = Mesh(dev.reshape(1, 1, 1), ("pod", "data", "model"))
        ctx = shd.MeshContext(mesh)
        assert ctx.rules["batch"] == ("pod", "data")

    def test_shard_act_noop_without_context(self):
        x = jnp.ones((4, 4))
        y = shd.shard_act(x, ("batch", None))
        assert y is x


class TestParamRules:
    def test_attention_weights(self):
        assert shd.logical_for_path("blocks/mixer/wq/w", 2) == ("fsdp", "tp")
        assert shd.logical_for_path("blocks/0/mixer/wo/w", 3) == (None, "tp", "fsdp")

    def test_moe_experts(self):
        # fully-sharded expert weights: E on model, d_ff on data (§Perf I6)
        assert shd.logical_for_path("blocks/0/ffn/w_gate", 3) == ("experts", None, "fsdp")
        assert shd.logical_for_path("blocks/0/ffn/w_down", 3) == ("experts", "fsdp", None)
        # scan-stacked gets a leading None
        assert shd.logical_for_path("blocks/ffn/w_up", 4) == (None, "experts", None, "fsdp")
        # optimizer moments inherit via suffix stripping (dryrun.state_shardings)
        assert shd.logical_for_path("blocks/0/ffn/w_gate/m", 3) == (None, None, None)  # raw path w/o strip
        # dense FFN leaves (with /w) still hit the dense rules
        assert shd.logical_for_path("blocks/0/ffn/w_up/w", 2) == ("fsdp", "ff")

    def test_norms_replicated(self):
        assert shd.logical_for_path("ln1/scale", 1) == (None,)

    def test_embed_head(self):
        assert shd.logical_for_path("embed/w", 2) == ("vocab", "fsdp")
        assert shd.logical_for_path("head/w", 2) == ("fsdp", "vocab")

    def test_param_sharding_tree_runs(self):
        from repro import configs
        from repro.models import lm

        cfg = configs.get_smoke_config("qwen2.5-3b")
        shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
        dev = np.array(jax.devices())
        mesh = Mesh(dev.reshape(1, 1), ("data", "model"))
        tree = shd.param_sharding_tree(shapes, mesh)
        assert len(jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "spec"))) \
            == len(jax.tree.leaves(shapes))


HLO_SAMPLE = textwrap.dedent("""\
    %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,8] get-tuple-element(%p), index=1
      %w = f32[8,8] constant({...})
      %d = f32[8,8] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,8] all-reduce(%d), to_apply=%sum
      %one = s32[] constant(1)
      %ni = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,8]) tuple(%ni, %ar)
    }
    %cond (p2: (s32[], f32[8,8])) -> pred[] {
      %p2 = (s32[], f32[8,8]) parameter(0)
      %i2 = s32[] get-tuple-element(%p2), index=0
      %n = s32[] constant(10)
      ROOT %lt = pred[] compare(%i2, %n), direction=LT
    }
    ENTRY %main (a: f32[8,8]) -> f32[8,8] {
      %a = f32[8,8] parameter(0)
      %z = s32[] constant(0)
      %tup = (s32[], f32[8,8]) tuple(%z, %a)
      %w2 = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body
      ROOT %out = f32[8,8] get-tuple-element(%w2), index=1
    }
""")


class TestHloAnalyzer:
    def test_trip_count_multiplies_flops(self):
        res = analyze_hlo(HLO_SAMPLE)
        # dot: 2*8*8*8 = 1024 flops, x10 trips
        assert res["dot_flops"] == pytest.approx(10240)

    def test_collectives_multiplied(self):
        res = analyze_hlo(HLO_SAMPLE)
        ar = res["collectives"]["all-reduce"]
        assert ar["count"] == 10
        assert ar["bytes"] == pytest.approx(10 * 8 * 8 * 4)

    def test_parse_computations(self):
        comps = parse_computations(HLO_SAMPLE)
        assert set(comps) == {"body", "cond", "main"}
        assert len(comps["body"].ops) == 9

    def test_real_compiled_module(self):
        """End-to-end on an actual compiled jitted scan."""

        def f(x):
            def body(c, _):
                return c @ c * 0.5, None

            y, _ = jax.lax.scan(body, x, None, length=7)
            return y

        compiled = jax.jit(f).lower(jnp.ones((16, 16))).compile()
        res = analyze_hlo(compiled.as_text())
        # 7 iterations x 2*16^3 flops
        assert res["dot_flops"] == pytest.approx(7 * 2 * 16**3, rel=0.01)


SUBPROC_SNIPPET = r"""
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.dist import sharding as shd
from repro import configs
from repro.models import lm
from repro.launch.specs import concrete_batch

mesh = shd.make_mesh((4, 2), ("data", "model"))
cfg = configs.get_smoke_config("qwen2.5-3b")

with shd.use_mesh(mesh):
    params = lm.init(jax.random.PRNGKey(0), cfg)
    p_sh = shd.param_sharding_tree(jax.eval_shape(lambda: params), mesh)
    params = jax.device_put(params, p_sh)
    batch = concrete_batch(cfg, "train", 4, 16, seed=0)
    b_sh = {k: NamedSharding(mesh, P("data")) for k in batch}
    batch = jax.device_put(batch, b_sh)
    loss, _ = jax.jit(lambda p, b: lm.loss_fn(p, b, cfg))(params, batch)
    loss_sharded = float(loss)

# unsharded reference
params_r = jax.device_get(params)
batch_r = jax.device_get(batch)
loss_ref, _ = jax.jit(lambda p, b: lm.loss_fn(p, b, cfg))(params_r, batch_r)
print(json.dumps({"sharded": loss_sharded, "ref": float(loss_ref)}))

# compressed ring all-reduce numerics on 8 devices
from repro.optim.grad_compress import ring_allreduce_int8
x = np.random.default_rng(0).normal(size=(8, 1000)).astype(np.float32)
ring_mesh = shd.make_mesh((8,), ("d",))
def body(v):
    return ring_allreduce_int8(v[0], "d", 8)[None]
out = jax.jit(jax.shard_map(body, mesh=ring_mesh, in_specs=P("d"),
                        out_specs=P("d"), check_vma=False))(x)
got = np.asarray(out)[0]
want = x.sum(0)
err = np.abs(got - want) / np.maximum(np.abs(want), 1e-3)
print(json.dumps({"ring_median_rel": float(np.median(err)),
                  "ring_p99_rel": float(np.percentile(err, 99))}))
"""


@pytest.mark.slow
def test_sharded_execution_8dev_subprocess(run_in_8dev_subprocess):
    """Run a sharded train loss on a forced 8-device host platform and
    compare against the unsharded value; also checks the int8 ring
    all-reduce numerics on a real 8-way mesh."""
    r1, r2 = run_in_8dev_subprocess(SUBPROC_SNIPPET)
    assert r1["sharded"] == pytest.approx(r1["ref"], rel=2e-3)
    assert r2["ring_median_rel"] < 0.02, r2
