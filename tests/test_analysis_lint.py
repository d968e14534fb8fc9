"""Seeded-bad fixtures for the program-contract lint: every check must
FAIL on a program constructed to violate exactly its contract, and stay
quiet on the matching clean fixture.  The clean-repo pass itself is the
``python -m repro.analysis.lint --all`` gate in scripts/check.sh / CI;
here we pin down what each check detects."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import load_builtin_checks
from repro.analysis.registry import (
    CHECKS,
    Built,
    CompiledUnit,
    PallasTrace,
    PrecisionPolicy,
    Replay,
)
from repro.analysis.jaxpr_tools import (
    canonical_signature,
    compile_unit,
    strip_weak,
)
from repro.launch.hlo_analysis import collective_sites, op_output_bytes

load_builtin_checks()


def _errors(findings):
    return [f for f in findings if f.severity == "error"]


# --------------------------- donation ----------------------------------------
def test_donation_dropped_fixture():
    # Output is a scalar: XLA cannot alias the donated (256,256) input,
    # drops the donation silently (warning only) — the check must error.
    f = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
    x = jnp.ones((256, 256), jnp.float32)
    unit = compile_unit("bad_donate", f, (x,), donate_argnums=(0,))
    findings = CHECKS["donation"]("fixture", Built(compiled=[unit]))
    errs = _errors(findings)
    assert len(errs) == 1
    assert "dropped" in errs[0].message
    assert errs[0].data["dropped"][0]["nbytes"] == 256 * 256 * 4


def test_donation_clean_fixture():
    # Same-shape output: the donation aliases, no findings at all.
    f = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    x = jnp.ones((256, 256), jnp.float32)
    unit = compile_unit("good_donate", f, (x,), donate_argnums=(0,))
    findings = CHECKS["donation"]("fixture", Built(compiled=[unit]))
    assert not _errors(findings)


# --------------------------- transfers ---------------------------------------
def test_transfers_implicit_fixture():
    # A raw numpy array handed straight to a jitted program is an
    # implicit host-to-device transfer: the guard raises, the check errors.
    f = jax.jit(lambda x: x + 1.0)
    f(jnp.zeros(8, jnp.float32))  # warm: only the replay runs guarded
    built = Built(hot=lambda: f(np.zeros(8, np.float32)),
                  hot_label="raw-numpy call")
    errs = _errors(CHECKS["transfers"]("fixture", built))
    assert len(errs) == 1
    assert "implicit transfer" in errs[0].message


def test_transfers_clean_fixture():
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(8, jnp.float32)
    f(x)
    built = Built(hot=lambda: jax.block_until_ready(f(x)))
    assert not CHECKS["transfers"]("fixture", built)


def test_transfers_host_callback_fixture():
    # A pure_callback inside the traced hot program is a per-step host
    # sync — flagged from the jaxpr walk alone, nothing is executed.
    def g(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)
        return y + 1.0

    jaxpr = jax.make_jaxpr(g)(jnp.zeros(4, jnp.float32))
    built = Built(hot_jaxprs=[("g", jaxpr)])
    errs = _errors(CHECKS["transfers"]("fixture", built))
    assert len(errs) == 1
    assert "pure_callback" in errs[0].message


# --------------------------- recompile ---------------------------------------
def test_recompile_weak_type_drift_fixture():
    # Same program called with a committed array and a Python-scalar-weak
    # aval: signatures differ only in the weak bit.
    committed = canonical_signature((jnp.float32(1.0) * jnp.ones(()),))
    weak = canonical_signature((jnp.asarray(1.0),))
    if strip_weak(committed) == strip_weak(weak) and committed != weak:
        sigs = [("step", committed), ("step", weak)]
    else:  # fallback: handcrafted signatures with the same invariant
        sigs = [("step", "T::float32[]|w0"), ("step", "T::float32[]|w1")]
    replay = Replay(signatures=sigs, max_programs={"step": 1})
    errs = _errors(CHECKS["recompile"]("fixture", Built(replay=replay)))
    assert any("weak-type drift" in e.message for e in errs)
    assert any("retraces" in e.message for e in errs)


def test_recompile_budget_and_live_cache_fixture():
    replay = Replay(
        signatures=[("step", "T::float32[2]|w0"),
                    ("step", "T::float32[4]|w0")],
        max_programs={"step": 1},
        live_counts={"step": 3},
        live_budget={"step": 1},
    )
    errs = _errors(CHECKS["recompile"]("fixture", Built(replay=replay)))
    assert any("2 distinct abstract signatures" in e.message for e in errs)
    assert any("holds 3 compiled programs" in e.message for e in errs)
    assert not any("weak-type" in e.message for e in errs)


def test_recompile_clean_fixture():
    replay = Replay(
        signatures=[("step", "T::float32[2]|w0")] * 3,
        max_programs={"step": 1},
        live_counts={"step": 1}, live_budget={"step": 1},
    )
    assert not CHECKS["recompile"]("fixture", Built(replay=replay))


# --------------------------- collectives -------------------------------------
# Hand-written post-SPMD module: an all-gather inside a while loop whose
# condition bounds the counter at 8 — the site must be reported with its
# byte size AND the x8 trip multiplier.
_BAD_HLO = """\
HloModule fixture

%body (param.1: (s32[], f32[16,16])) -> (s32[], f32[16,16]) {
  %param.1 = (s32[], f32[16,16]) parameter(0)
  %gte.0 = s32[] get-tuple-element((s32[], f32[16,16]) %param.1), index=0
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %gte.0, s32[] %one)
  %gte.1 = f32[16,16] get-tuple-element((s32[], f32[16,16]) %param.1), index=1
  %ag = f32[16,16] all-gather(f32[2,16] %gte.1), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  ROOT %tup = (s32[], f32[16,16]) tuple(s32[] %next, f32[16,16] %ag)
}

%cond (param.2: (s32[], f32[16,16])) -> pred[] {
  %param.2 = (s32[], f32[16,16]) parameter(0)
  %gte.2 = s32[] get-tuple-element((s32[], f32[16,16]) %param.2), index=0
  %trips = s32[] constant(8)
  ROOT %lt = pred[] compare(s32[] %gte.2, s32[] %trips), direction=LT
}

ENTRY %main (arg: f32[16,16]) -> f32[16,16] {
  %arg = f32[16,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[16,16]) tuple(s32[] %zero, f32[16,16] %arg)
  %loop = (s32[], f32[16,16]) while((s32[], f32[16,16]) %init), condition=%cond, body=%body
  ROOT %res = f32[16,16] get-tuple-element((s32[], f32[16,16]) %loop), index=1
}
"""


def test_collectives_oversized_fixture():
    unit = CompiledUnit(label="bad_spmd", hlo=_BAD_HLO,
                        collective_budget={"all-gather": 512})
    errs = _errors(CHECKS["collectives"]("fixture", Built(compiled=[unit])))
    assert len(errs) == 1
    site = errs[0].data["site"]
    assert site["collective"] == "all-gather"
    assert site["bytes"] == 16 * 16 * 4          # 1024 > 512 budget
    assert site["trip_mult"] == 8                # while trips attached


def test_collectives_forbidden_and_clean_fixture():
    unit0 = CompiledUnit(label="forbid", hlo=_BAD_HLO,
                         collective_budget={"all-gather": 0})
    errs = _errors(CHECKS["collectives"]("fixture", Built(compiled=[unit0])))
    assert len(errs) == 1 and "forbidden" in errs[0].message

    unit1 = CompiledUnit(label="roomy", hlo=_BAD_HLO,
                         collective_budget={"all-gather": 1 << 20})
    findings = CHECKS["collectives"]("fixture", Built(compiled=[unit1]))
    assert not _errors(findings)
    assert any("within budget" in f.message for f in findings)


# --------------------------- pallas ------------------------------------------
def _bad_pallas_trace():
    import jax.experimental.pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((64, 700), x.dtype),
            grid=(4,),
            in_specs=[pl.BlockSpec((16, 100), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((16, 100), lambda i: (i, 0)),
            interpret=True,
        )(x)

    return jax.make_jaxpr(bad)(jnp.zeros((64, 700), jnp.float32))


def test_pallas_misaligned_and_short_grid_fixture():
    trace = PallasTrace(label="bad_kernel",
                        closed_jaxpr=_bad_pallas_trace())
    findings = CHECKS["pallas"]("fixture", Built(pallas=[trace]))
    errs = _errors(findings)
    # Last block dim 100: neither the full 700 nor a multiple of 128.
    assert any("lane tile" in e.message for e in errs)
    # Grid (4,) x block (16,100) via (i, 0) covers 100 of 700 in dim 1.
    assert any("never visited" in e.message for e in errs)


def test_pallas_clean_repo_kernels():
    # The real kernels' contract must lint clean: errors here mean either
    # a kernel regressed or the tiling rules drifted from reality.
    from repro.analysis.lint import run_lint

    report = run_lint(checks=["pallas"], contracts=["kernels.pallas"])
    assert report.ok, [f.message for f in report.findings]
    assert "kernels.pallas" in report.contracts_executed


# --------------------------- precision ---------------------------------------
def _precision(built):
    return CHECKS["precision"]("fixture", built)


def test_precision_hidden_f64_fixture():
    # x64 enabled during tracing: a single f64 constant promotes the
    # whole chain — the forbidden-dtype rule must name the dtype.
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x * np.float64(2.0)
        )(jnp.zeros(4, jnp.float64))
    built = Built(hot_jaxprs=[("f64", jaxpr)],
                  precision=PrecisionPolicy(compute_dtype="float32"))
    errs = _errors(_precision(built))
    assert any("float64" in e.message for e in errs)


def test_precision_widening_needs_island_fixture():
    from repro.models import common

    def bad(x):
        return x.astype(jnp.float32).sum()

    def good(x):
        with common.precision_island("logits"):
            return x.astype(jnp.float32).sum()

    x = jnp.zeros(4, jnp.bfloat16)
    policy = PrecisionPolicy(compute_dtype="bfloat16")
    errs = _errors(_precision(Built(
        hot_jaxprs=[("p", jax.make_jaxpr(bad)(x))], precision=policy)))
    assert len(errs) == 1 and "widening cast" in errs[0].message
    assert not _errors(_precision(Built(
        hot_jaxprs=[("p", jax.make_jaxpr(good)(x))], precision=policy)))


def test_precision_dot_accumulation_fixture():
    x = jnp.zeros((4, 8), jnp.bfloat16)
    w = jnp.zeros((8, 2), jnp.bfloat16)
    policy = PrecisionPolicy(compute_dtype="bfloat16")
    bad = jax.make_jaxpr(lambda a, b: jax.lax.dot(a, b))(x, w)
    errs = _errors(_precision(Built(
        hot_jaxprs=[("p", bad)], precision=policy)))
    assert len(errs) == 1
    assert "preferred_element_type=float32" in errs[0].message
    good = jax.make_jaxpr(lambda a, b: jnp.matmul(
        a, b, preferred_element_type=jnp.float32))(x, w)
    assert not _errors(_precision(Built(
        hot_jaxprs=[("p", good)], precision=policy)))


def test_precision_dcim_bypassed_dense_fixture():
    # A raw float matmul inside the dense island while the policy says
    # this program routes through the DCIM sim: structural bypass.
    from repro.models import common

    def bypass(x, w):
        with common.precision_island("dense"):
            return jnp.matmul(x, w, preferred_element_type=jnp.float32)

    jaxpr = jax.make_jaxpr(bypass)(
        jnp.zeros((4, 8), jnp.float32), jnp.zeros((8, 2), jnp.float32))
    built = Built(
        hot_jaxprs=[("decode", jaxpr)],
        precision=PrecisionPolicy(
            compute_dtype="float32", dcim_programs={"decode": "int8"}),
    )
    errs = _errors(_precision(built))
    assert any("bypasses the installed DCIM numerics" in e.message
               for e in errs)
    assert any("never calls" in e.message for e in errs)


def test_precision_asymmetric_clip_fixture():
    # The historical quantizer bug: clip to [-qmax-1, qmax] while the
    # scale is amax/qmax.  B-recovery from the clip constants flags it.
    from repro.kernels import ops
    from repro.models import common

    def bad_mvm(x, w):
        with common.precision_island("dense"):
            qmax = 127
            sx = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / qmax
            sw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12) / qmax
            qx = jnp.clip(jnp.round(x / sx), -qmax - 1, qmax)
            qw = jnp.clip(jnp.round(w / sw), -qmax, qmax)
            y = ops.dcim_mvm(qx.astype(jnp.int32), qw.astype(jnp.int32),
                             B_x=8, B_w=8, k=4)
            return y.astype(jnp.float32) * (sx * sw)

    jaxpr = jax.make_jaxpr(bad_mvm)(
        jnp.zeros((4, 8), jnp.float32), jnp.zeros((8, 4), jnp.float32))
    built = Built(
        hot_jaxprs=[("decode", jaxpr)],
        precision=PrecisionPolicy(
            compute_dtype="float32", dcim_programs={"decode": "int8"}),
    )
    errs = _errors(_precision(built))
    assert any("asymmetric quantizer clip [-128.0, 127.0]" in e.message
               for e in errs)
    # The symmetric clip still recovers B=8 — only the bad one errors.
    assert not any("recovers bit widths" in e.message for e in errs)


def test_precision_gate_lossy_and_unmatched_fixture():
    # The gate re-derives from traced pool leaves, not config flags: a
    # bf16 pool behind an enabled gate under f32 compute must error, as
    # must a leaf the program never takes as input.
    from repro.analysis.jaxpr_tools import pytree_leaf_specs
    from repro.analysis.registry import ExactnessGate

    pool = {"k": jnp.zeros((2, 4), jnp.bfloat16),
            "v": jnp.zeros((2, 4), jnp.bfloat16)}
    jaxpr = jax.make_jaxpr(
        lambda p, x: (p["k"].sum(), p["v"].sum(), x)
    )(pool, jnp.zeros((), jnp.float32))
    leaves = pytree_leaf_specs(pool)
    built = Built(
        hot_jaxprs=[("decode", jaxpr)],
        precision=PrecisionPolicy(
            compute_dtype="float32", audit_widening=False,
            gates=[
                ExactnessGate("prefix_reuse", True, "decode", leaves),
                ExactnessGate("preempt", True, "decode",
                              [("['missing']", "float32", (9, 9))]),
                ExactnessGate("orphan", True, "never_traced", leaves),
            ]),
    )
    errs = _errors(_precision(built))
    assert any("claimed ENABLED" in e.message and "lossy" in e.message
               for e in errs)
    assert any("not an input of the traced" in e.message for e in errs)
    assert any("did not trace" in e.message for e in errs)


def test_precision_gate_verified_fixture():
    from repro.analysis.jaxpr_tools import pytree_leaf_specs
    from repro.analysis.registry import ExactnessGate

    pool = {"k": jnp.zeros((2, 4), jnp.float32)}
    jaxpr = jax.make_jaxpr(lambda p: p["k"].sum())(pool)
    built = Built(
        hot_jaxprs=[("decode", jaxpr)],
        precision=PrecisionPolicy(
            compute_dtype="float32",
            gates=[ExactnessGate("prefix_reuse", True, "decode",
                                 pytree_leaf_specs(pool))]),
    )
    findings = _precision(built)
    assert not _errors(findings)
    assert any("verified" in f.message for f in findings)


def test_precision_clean_repo_contracts():
    # The dcim-serve contract must lint clean end-to-end AND positively
    # verify the routing (info findings, not silence).
    from repro.analysis.lint import run_lint

    report = run_lint(checks=["precision"], contracts=["sim.dcim_serve"])
    assert report.ok, [f.message for f in report.findings]
    msgs = [f.message for f in report.findings]
    assert any("DCIM int routing verified" in m for m in msgs)
    assert any("DCIM fp routing verified" in m for m in msgs)


def test_lint_runtime_budget(tmp_path):
    from repro.analysis.findings import Report
    from repro.analysis.lint import check_runtime_budget

    bench = tmp_path / "BENCH_lint.json"
    r = Report(timings={"c:build": 1.0})
    # First run records the baseline ...
    assert check_runtime_budget(r, 10.0, str(bench)) is None
    assert bench.exists()
    # ... within 2x passes, beyond 2x fails.
    assert check_runtime_budget(r, 19.0, str(bench)) is None
    msg = check_runtime_budget(r, 21.0, str(bench))
    assert msg is not None and "exceeds budget" in msg


# --------------------------- fp8 byte accounting (satellite) ------------------
def test_fp8_hlo_byte_accounting():
    line = ("  %ag = f8e4m3fn[2048]{0} all-gather(f8e4m3fn[256]{0} %x), "
            "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}")
    assert op_output_bytes(line) == 2048          # 1 byte/element

    hlo = _BAD_HLO.replace("f32[16,16]", "f8e5m2[16,16]").replace(
        "f32[2,16]", "f8e5m2[2,16]")
    (site,) = collective_sites(hlo)
    assert site["bytes"] == 16 * 16               # fp8: 1 byte, not 4
    assert site["trip_mult"] == 8


def test_op_output_bytes_parses_result_not_name():
    # Regression: the byte counter must read the RESULT shape (after
    # '='), including tuple results — not the op name.
    dot = ("  ROOT %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, "
           "f32[8,8]{1,0} %b), lhs_contracting_dims={1}")
    assert op_output_bytes(dot) == 8 * 8 * 4
    tup = "  %t = (f32[64]{0}, s32[]) tuple(%a, %b)"
    assert op_output_bytes(tup) == 64 * 4 + 4


# --------------------------- runner ------------------------------------------
def test_lint_cli_list_and_unknown():
    from repro.analysis.lint import main, run_lint

    assert main(["--list"]) == 0
    with pytest.raises(ValueError, match="unknown"):
        run_lint(checks=["nope"])
