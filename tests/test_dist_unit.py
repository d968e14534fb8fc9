"""Fast unit tests for ``repro.dist.sharding`` edge cases not covered by
the seed spec in ``test_sharding_dist.py``: empty rules, 1-D params,
rank-mismatch errors, context nesting, the no-mesh ``shard_act``
identity property, and property-based checks of the resolution rules
(``_divisible_prefix`` / ``axes_for`` / ``spec``) that now gate serving
correctness, not just training layouts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd


def _mesh2():
    dev = np.array(jax.devices())
    return Mesh(dev.reshape(1, 1), ("data", "model"))


class TestSpecEdges:
    def test_empty_rules_replicates_everything(self):
        ctx = shd.MeshContext(_mesh2(), {})
        assert ctx.spec(("batch", "heads", "ff"), (4, 8, 16)) == P(None, None, None)
        assert ctx.axes_for("batch", 4) is None

    def test_unknown_logical_replicates(self):
        ctx = shd.MeshContext(_mesh2())
        assert ctx.spec(("no_such_axis",), (4,)) == P(None)

    def test_one_dim_param(self):
        ctx = shd.MeshContext(_mesh2(), {"ff": ("model",)})
        assert ctx.spec(("ff",), (8,)) == P("model")
        assert ctx.spec((None,), (8,)) == P(None)

    def test_rank_mismatch_raises(self):
        ctx = shd.MeshContext(_mesh2())
        with pytest.raises(ValueError, match="rank mismatch"):
            ctx.spec(("batch",), (4, 4))
        with pytest.raises(ValueError, match="rank mismatch"):
            ctx.spec(("batch", None, None), (4, 4))

    def test_rule_axis_absent_from_mesh_replicates(self):
        ctx = shd.MeshContext(_mesh2(), {"batch": ("pod", "data")})
        # "pod" is not on this 2-axis mesh -> resolution keeps only "data"
        assert ctx.spec(("batch",), (4,)) == P("data")
        ctx2 = shd.MeshContext(_mesh2(), {"batch": ("pod",)})
        assert ctx2.spec(("batch",), (4,)) == P(None)

    def test_multi_axis_prefix_divisibility(self):
        mesh = _mesh2()

        class Fake(shd.MeshContext):
            """Pretend pod=2, data=4 so prefix fallback is observable."""

            def __init__(self):
                self.mesh = mesh
                self.rules = {"batch": ("pod", "data")}

            def _axis_size(self, axis):
                return {"pod": 2, "data": 4}[axis]

            def axes_for(self, logical, dim):
                axes = self.rules.get(logical)
                if not axes:
                    return None
                return self._divisible_prefix(axes, dim) or None

        ctx = Fake()
        assert ctx.axes_for("batch", 16) == ("pod", "data")   # 16 % 8 == 0
        assert ctx.axes_for("batch", 4) == ("pod",)           # prefix fallback
        assert ctx.axes_for("batch", 3) is None               # replicate

    def test_sharding_returns_named_sharding(self):
        ctx = shd.MeshContext(_mesh2())
        s = ctx.sharding(("batch", None), (4, 4))
        assert isinstance(s, NamedSharding)
        # trailing replicated dims are canonicalised away so device_put
        # placements compare equal to jit-emitted output shardings
        assert s.spec == P("data")


class TestContext:
    def test_use_mesh_nesting_restores(self):
        mesh = _mesh2()
        assert shd.current() is None
        with shd.use_mesh(mesh) as outer:
            assert shd.current() is outer
            with shd.use_mesh(shd.MeshContext(mesh, {})) as inner:
                assert shd.current() is inner
            assert shd.current() is outer
        assert shd.current() is None

    def test_use_mesh_pops_on_exception(self):
        with pytest.raises(RuntimeError):
            with shd.use_mesh(_mesh2()):
                raise RuntimeError("boom")
        assert shd.current() is None

    def test_shard_act_identity_property_without_mesh(self):
        """No installed context -> shard_act returns its argument object
        unchanged for any shape/annotation pair."""
        assert shd.current() is None
        rng = np.random.default_rng(0)
        for nd in range(1, 5):
            shape = tuple(int(rng.integers(1, 5)) for _ in range(nd))
            x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
            logical = tuple(
                rng.choice([None, "batch", "heads", "ff"]) for _ in range(nd)
            )
            assert shd.shard_act(x, logical) is x

    def test_shard_act_constrains_under_mesh(self):
        """Under a mesh the constraint must appear in the jitted HLO and
        preserve values (on 1 device the eager path may be identity)."""
        x = jnp.ones((4, 8))
        with shd.use_mesh(_mesh2()):
            y = shd.shard_act(x, ("batch", None))
            hlo = (
                jax.jit(lambda a: shd.shard_act(a, ("batch", None)))
                .lower(x).as_text()
            )
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert "sharding" in hlo


class TestParamRulesEdges:
    def test_bias_and_norm_leaves_replicate(self):
        assert shd.logical_for_path("blocks/mixer/wq/b", 1) == (None,)
        assert shd.logical_for_path("ln_f/bias", 1) == (None,)

    def test_rank_mismatch_falls_to_replicated(self):
        # matched rule, but rank neither base nor base+1
        assert shd.logical_for_path("embed/w", 4) == (None, None, None, None)

    def test_router_and_mamba_rules(self):
        assert shd.logical_for_path("blocks/ffn/router/w", 2) == ("fsdp", None)
        assert shd.logical_for_path("blocks/mixer/out_proj/w", 3) == (None, "tp", "fsdp")
        assert shd.logical_for_path("blocks/mixer/conv_w", 2) == ("tp", None)

    def test_param_sharding_tree_structure_and_fallback(self):
        mesh = _mesh2()
        tree = {
            "embed": {"w": jax.ShapeDtypeStruct((32, 16), jnp.float32)},
            "ln": {"scale": jax.ShapeDtypeStruct((16,), jnp.float32)},
        }
        out = shd.param_sharding_tree(tree, mesh)
        assert out["embed"]["w"].spec == P("model", "data")
        assert out["ln"]["scale"].spec == P()


def _fake_ctx(sizes, rules):
    """A MeshContext whose axis sizes are simulated (the host has one
    device); resolution logic — _divisible_prefix / axes_for / spec — is
    the REAL implementation."""
    mesh = _mesh2() if set(sizes) <= {"data", "model"} else None
    assert mesh is not None, sizes

    class Fake(shd.MeshContext):
        def __init__(self):
            self.mesh = mesh
            self.rules = dict(rules)
            self.serving = False

        def _axis_size(self, axis):
            return sizes[axis]

    return Fake()


class TestResolutionProperties:
    """Property-based invariants of the rule resolution that exact
    sharded serving stands on."""

    @given(d=st.integers(1, 16), m=st.integers(1, 16),
           dim=st.integers(1, 4096))
    @settings(max_examples=60, deadline=None)
    def test_divisible_prefix_is_longest_and_divides(self, d, m, dim):
        ctx = _fake_ctx({"data": d, "model": m}, {})
        axes = ("data", "model")
        got = ctx._divisible_prefix(axes, dim)
        size = 1
        for a in got:
            size *= {"data": d, "model": m}[a]
        assert dim % size == 0                      # result divides
        if len(got) < len(axes):                    # and is the LONGEST
            nxt = size * {"data": d, "model": m}[axes[len(got)]]
            assert dim % nxt != 0

    @given(m=st.integers(1, 16), dim=st.integers(1, 64))
    @settings(max_examples=60, deadline=None)
    def test_axes_for_divisibility_and_rule_miss(self, m, dim):
        ctx = _fake_ctx({"data": 1, "model": m},
                        {"heads": ("model",)})
        got = ctx.axes_for("heads", dim)
        if dim % m == 0:
            assert got == ("model",)                # 1-sized axes divide all
        else:
            assert got is None                      # indivisible -> replicate
        # rule miss and empty rule always replicate
        assert ctx.axes_for("no_such_logical", dim) is None
        ctx.rules["empty"] = ()
        assert ctx.axes_for("empty", dim) is None

    @given(d=st.integers(1, 8), m=st.integers(1, 8),
           dims=st.tuples(st.integers(1, 64), st.integers(1, 64),
                          st.integers(1, 64)),
           names=st.tuples(st.sampled_from([None, "batch", "heads", "x"]),
                           st.sampled_from([None, "batch", "heads", "x"]),
                           st.sampled_from([None, "batch", "heads", "x"])))
    @settings(max_examples=80, deadline=None)
    def test_spec_never_reuses_axes_and_always_divides(self, d, m, dims,
                                                       names):
        sizes = {"data": d, "model": m}
        ctx = _fake_ctx(sizes, {"batch": ("data",), "heads": ("model",)})
        spec = ctx.spec(names, dims)
        flat = []
        for entry, dim in zip(tuple(spec), dims):
            axes = () if entry is None else (
                (entry,) if isinstance(entry, str) else tuple(entry)
            )
            size = 1
            for a in axes:
                size *= sizes[a]
            assert dim % size == 0                  # every dim stays divisible
            flat.extend(axes)
        assert len(flat) == len(set(flat))          # each mesh axis used once
        # unknown ("x") and None entries must be replicated
        for entry, name in zip(tuple(spec), names):
            if name in (None, "x"):
                assert entry is None
