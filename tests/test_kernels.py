"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
sweeping shapes/dtypes/bit-widths, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pareto
from repro.kernels import ops, ref
from repro.kernels.dcim_mvm import dcim_mvm_pallas
from repro.kernels.fp_prealign import fp_prealign_pallas
from repro.kernels.pareto_rank import dominance_matrix_pallas


class TestParetoRankKernel:
    @pytest.mark.parametrize("P", [1, 7, 128, 131, 300])
    @pytest.mark.parametrize("M", [2, 4])
    def test_matches_ref_shapes(self, P, M):
        rng = np.random.default_rng(P * 10 + M)
        F = jnp.asarray(rng.normal(size=(P, M)).astype(np.float32))
        got = np.asarray(ops.dominance_matrix(F))
        want = np.asarray(ref.dominance_matrix_ref(F))
        np.testing.assert_array_equal(got, want)

    def test_constrained_matches_ref(self):
        rng = np.random.default_rng(0)
        F = jnp.asarray(rng.normal(size=(90, 4)).astype(np.float32))
        v = jnp.asarray(
            (rng.random(90) < 0.4) * rng.random(90).astype(np.float32)
        )
        got = np.asarray(ops.dominance_matrix(F, v))
        want = np.asarray(ref.dominance_matrix_ref(F, v))
        np.testing.assert_array_equal(got, want)

    def test_matches_core_pareto(self):
        rng = np.random.default_rng(3)
        F = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
        got = np.asarray(ops.dominance_matrix(F))
        want = np.asarray(pareto.dominance_matrix(F))
        np.testing.assert_array_equal(got, want)

    def test_duplicate_rows_no_self_domination(self):
        F = jnp.ones((5, 4), jnp.float32)
        D = np.asarray(ops.dominance_matrix(F))
        assert not D.any()

    @settings(max_examples=20, deadline=None)
    @given(P=st.integers(2, 40), seed=st.integers(0, 2**16))
    def test_antisymmetry_property(self, P, seed):
        rng = np.random.default_rng(seed)
        F = jnp.asarray(rng.normal(size=(P, 4)).astype(np.float32))
        D = np.asarray(ops.dominance_matrix(F))
        assert not np.any(D & D.T), "dominance must be antisymmetric"
        assert not np.any(np.diag(D)), "no self-domination"

    @pytest.mark.parametrize("P", [5, 100, 127, 129, 250, 300, 511])
    def test_interpreter_matches_jnp_non_multiple_of_block(self, P):
        """Pallas-interpreter dominance parity with the jnp path on
        population sizes that are NOT multiples of the 128 block grid —
        the padding rows must never leak into the sliced result."""
        rng = np.random.default_rng(P)
        F = jnp.asarray(rng.normal(size=(P, 4)).astype(np.float32))
        v = jnp.asarray(
            (rng.random(P) < 0.4) * rng.random(P).astype(np.float32)
        )
        got = np.asarray(
            dominance_matrix_pallas(F, v, interpret=True)
        ).astype(bool)
        want = np.asarray(pareto.dominance_matrix(F, v))
        np.testing.assert_array_equal(got, want)
        # Unconstrained variant too.
        got0 = np.asarray(
            dominance_matrix_pallas(F, interpret=True)
        ).astype(bool)
        want0 = np.asarray(pareto.dominance_matrix(F))
        np.testing.assert_array_equal(got0, want0)

    def test_default_path_matches_forced_interpreter(self):
        """ops.dominance_matrix on CPU (XLA fallback) == forced Pallas
        interpreter == jnp reference: all three produce one truth."""
        rng = np.random.default_rng(7)
        F = jnp.asarray(rng.normal(size=(130, 4)).astype(np.float32))
        v = jnp.asarray(rng.random(130).astype(np.float32) * 0.5)
        a = np.asarray(ops.dominance_matrix(F, v))             # auto (CPU->XLA)
        b = np.asarray(ops.dominance_matrix(F, v, interpret=True))  # kernel
        c = np.asarray(pareto.dominance_matrix(F, v))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


class TestDcimMvmKernel:
    @pytest.mark.parametrize("shape", [(1, 1, 1), (3, 5, 7), (50, 300, 70),
                                       (128, 128, 128), (129, 257, 65)])
    def test_exact_int8(self, shape):
        M, K, N = shape
        rng = np.random.default_rng(sum(shape))
        x = jnp.asarray(rng.integers(-128, 128, size=(M, K)).astype(np.int32))
        w = jnp.asarray(rng.integers(-128, 128, size=(K, N)).astype(np.int32))
        got = np.asarray(ops.dcim_mvm(x, w, B_x=8, B_w=8, k=4))
        np.testing.assert_array_equal(got, np.asarray(ref.dcim_mvm_ref(x, w)))

    @pytest.mark.parametrize("B_x,B_w,k", [
        (2, 2, 1), (2, 2, 2), (4, 4, 2), (4, 8, 4), (8, 4, 1),
        (8, 8, 8), (8, 8, 3), (16, 8, 4), (16, 16, 8),
    ])
    def test_bitwidth_sweep(self, B_x, B_w, k):
        """Sweep (B_x, B_w, k) incl. non-dividing k (ceil slices)."""
        rng = np.random.default_rng(B_x * 100 + B_w * 10 + k)
        lo_x, hi_x = -(2 ** (B_x - 1)), 2 ** (B_x - 1)
        lo_w, hi_w = -(2 ** (B_w - 1)), 2 ** (B_w - 1)
        # int32 envelope: K * 2^(B_x-1) * 2^(B_w-1) < 2^31
        K = min(64, 2 ** max(31 - B_x - B_w, 0))
        x = jnp.asarray(rng.integers(lo_x, hi_x, size=(9, K)).astype(np.int32))
        w = jnp.asarray(rng.integers(lo_w, hi_w, size=(K, 11)).astype(np.int32))
        got = np.asarray(ops.dcim_mvm(x, w, B_x=B_x, B_w=B_w, k=k))
        np.testing.assert_array_equal(got, np.asarray(ref.dcim_mvm_ref(x, w)))

    @pytest.mark.parametrize("x_signed,w_signed", [
        (False, False), (True, False), (False, True), (True, True),
    ])
    def test_signedness(self, x_signed, w_signed):
        rng = np.random.default_rng(int(x_signed) * 2 + int(w_signed))
        lo_x = -8 if x_signed else 0
        lo_w = -8 if w_signed else 0
        x = jnp.asarray(rng.integers(lo_x, 8 if x_signed else 16, size=(7, 33)).astype(np.int32))
        w = jnp.asarray(rng.integers(lo_w, 8 if w_signed else 16, size=(33, 5)).astype(np.int32))
        got = np.asarray(
            ops.dcim_mvm(x, w, B_x=4, B_w=4, k=2, x_signed=x_signed, w_signed=w_signed)
        )
        np.testing.assert_array_equal(got, np.asarray(ref.dcim_mvm_ref(x, w)))

    def test_structural_ref_matches_kernel(self):
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.integers(-128, 128, size=(21, 130)).astype(np.int32))
        w = jnp.asarray(rng.integers(-128, 128, size=(130, 17)).astype(np.int32))
        a = np.asarray(ops.dcim_mvm(x, w, B_x=8, B_w=8, k=2))
        b = np.asarray(ref.dcim_mvm_structural_ref(x, w, B_x=8, B_w=8, k=2))
        np.testing.assert_array_equal(a, b)

    def test_extreme_values(self):
        """Two's-complement corners: min/max of the range."""
        x = jnp.asarray([[-128, 127, -1, 0]], dtype=jnp.int32)
        w = jnp.asarray([[-128], [127], [-128], [127]], dtype=jnp.int32)
        got = np.asarray(ops.dcim_mvm(x, w, B_x=8, B_w=8, k=4))
        np.testing.assert_array_equal(got, np.asarray(ref.dcim_mvm_ref(x, w)))

    @settings(max_examples=25, deadline=None)
    @given(
        M=st.integers(1, 16), K=st.integers(1, 96), N=st.integers(1, 16),
        k=st.sampled_from([1, 2, 4, 8]), seed=st.integers(0, 2**16),
    )
    def test_exactness_property(self, M, K, N, k, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-128, 128, size=(M, K)).astype(np.int32))
        w = jnp.asarray(rng.integers(-128, 128, size=(K, N)).astype(np.int32))
        got = np.asarray(ops.dcim_mvm(x, w, B_x=8, B_w=8, k=k))
        np.testing.assert_array_equal(got, np.asarray(ref.dcim_mvm_ref(x, w)))

    def test_block_shape_independence(self):
        """Tiling must not change results (padding/accumulation safety)."""
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.integers(-128, 128, size=(40, 200)).astype(np.int32))
        w = jnp.asarray(rng.integers(-128, 128, size=(200, 30)).astype(np.int32))
        a = np.asarray(dcim_mvm_pallas(x, w, block_m=128, block_n=128, block_k=128,
                                       interpret=True))
        b = np.asarray(dcim_mvm_pallas(x, w, block_m=16, block_n=8, block_k=32,
                                       interpret=True))
        np.testing.assert_array_equal(a, b)


class TestFpPrealignKernel:
    @pytest.mark.parametrize("shape", [(1, 1, 2), (6, 4, 16), (3, 7, 64), (2, 2, 256)])
    @pytest.mark.parametrize("B_M", [4, 8, 11, 24])
    def test_matches_ref(self, shape, B_M):
        rng = np.random.default_rng(shape[0] * B_M)
        x = jnp.asarray(
            (rng.normal(size=shape) * 10.0 ** rng.integers(-3, 4, size=shape)).astype(np.float32)
        )
        m1, e1 = fp_prealign_pallas(x, B_M=B_M, interpret=True)
        m2, e2 = ref.fp_prealign_ref(x, B_M=B_M)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))

    def test_zeros_and_mixed_signs(self):
        x = jnp.asarray(
            [[[0.0, -1.5, 3.25, -0.0, 1e-30, 7.0, -128.0, 0.5]]], jnp.float32
        )
        m1, e1 = fp_prealign_pallas(x, B_M=8, interpret=True)
        m2, e2 = ref.fp_prealign_ref(x, B_M=8)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))

    def test_max_element_alignment_invariant(self):
        """The group max element keeps its full B_M-bit mantissa
        (shift 0); every aligned mantissa is bounded by 2^B_M."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(4, 5, 32)).astype(np.float32))
        m, e = fp_prealign_pallas(x, B_M=8, interpret=True)
        m = np.asarray(m)
        assert np.all(np.abs(m) < 2**8)
        assert np.all(np.max(np.abs(m), axis=-1) >= 2**7)  # hidden bit of max

    @settings(max_examples=20, deadline=None)
    @given(B_M=st.sampled_from([4, 8, 11]), seed=st.integers(0, 2**16))
    def test_reconstruction_error_bound(self, B_M, seed):
        """|x - mant * 2^(emax-127-(B_M-1))| <= 2^(emax-127-(B_M-1))
        (one ULP of the aligned grid, from truncation)."""
        rng = np.random.default_rng(seed)
        x = np.asarray(rng.normal(size=(3, 2, 16)).astype(np.float32))
        m, e = fp_prealign_pallas(jnp.asarray(x), B_M=B_M, interpret=True)
        m, e = np.asarray(m, np.float64), np.asarray(e)
        scale = 2.0 ** (e[..., None] - 127.0 - (B_M - 1))
        recon = m * scale
        # <= 1 ULP lost to mantissa truncation + <= 1 ULP to the
        # alignment shift (both floor) => error < 2 ULP of the group grid.
        err = np.broadcast_to(scale, x.shape) * 2.0 + 1e-30
        np.testing.assert_array_less(np.abs(x - recon), err)


class TestFpDcimMatmul:
    @pytest.mark.parametrize("B_M,H,tol", [(4, 32, 1.5), (8, 32, 0.08),
                                           (11, 64, 0.01), (24, 64, 1e-4)])
    def test_accuracy_vs_f32(self, B_M, H, tol):
        rng = np.random.default_rng(B_M)
        x = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(128, 24)).astype(np.float32))
        got = np.asarray(ops.dcim_fp_matmul(x, w, H=H, B_M=B_M, B_w=B_M, k=4))
        want = np.asarray(ref.fp_matmul_f32_ref(x, w))
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
        assert np.percentile(rel, 90) < tol, f"p90 rel err {np.percentile(rel, 90)}"

    def test_error_monotone_in_mantissa_width(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(12, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 12)).astype(np.float32))
        want = np.asarray(ref.fp_matmul_f32_ref(x, w))
        errs = []
        for bm in (4, 8, 11):
            got = np.asarray(ops.dcim_fp_matmul(x, w, H=32, B_M=bm, B_w=bm, k=4))
            errs.append(np.median(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
        assert errs[0] > errs[1] > errs[2], errs

    def test_wide_path_guard(self):
        x = jnp.zeros((4, 512), jnp.float32)
        w = jnp.zeros((512, 4), jnp.float32)
        with pytest.raises(ValueError):
            ops.dcim_fp_matmul(x, w, H=512, B_M=24, B_w=24, k=4)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="on TPU the wrappers run the compiled kernels")
class TestCPUAutoFallback:
    """Off TPU the public wrappers must dispatch to the XLA structural
    refs — never the Pallas interpreter (~60x slower on CPU) — while
    ``interpret=True`` still forces the kernel for parity testing."""

    def test_dcim_mvm_no_pallas_in_trace(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-128, 128, size=(8, 32)).astype(np.int32))
        w = jnp.asarray(rng.integers(-128, 128, size=(32, 8)).astype(np.int32))
        jaxpr = jax.make_jaxpr(lambda a, b: ops.dcim_mvm(a, b))(x, w)
        assert "pallas_call" not in str(jaxpr)
        interp = jax.make_jaxpr(
            lambda a, b: ops.dcim_mvm(a, b, interpret=True)
        )(x, w)
        assert "pallas_call" in str(interp)
        np.testing.assert_array_equal(
            np.asarray(ops.dcim_mvm(x, w)),
            np.asarray(ops.dcim_mvm(x, w, interpret=True)),
        )

    def test_fp_prealign_no_pallas_in_trace(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(6, 64)).astype(np.float32))
        jaxpr = jax.make_jaxpr(lambda a: ops.fp_prealign(a, H=16))(x)
        assert "pallas_call" not in str(jaxpr)
        m_auto, e_auto = ops.fp_prealign(x, H=16)
        m_int, e_int = ops.fp_prealign(x, H=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(m_auto), np.asarray(m_int))
        np.testing.assert_array_equal(np.asarray(e_auto), np.asarray(e_int))

    def test_dcim_fp_matmul_routes_through_dispatch(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
        jaxpr = jax.make_jaxpr(
            lambda a, b: ops.dcim_fp_matmul(a, b, H=32, B_M=8, B_w=8, k=4)
        )(x, w)
        assert "pallas_call" not in str(jaxpr)
        np.testing.assert_array_equal(
            np.asarray(ops.dcim_fp_matmul(x, w, H=32, B_M=8, B_w=8, k=4)),
            np.asarray(ops.dcim_fp_matmul(x, w, H=32, B_M=8, B_w=8, k=4,
                                          interpret=True)),
        )


class TestSelectiveScanKernel:
    @pytest.mark.parametrize("shape", [(1, 8, 8, 4), (2, 64, 32, 8),
                                       (3, 128, 64, 16)])
    def test_matches_sequential_oracle(self, shape):
        from repro.kernels.selective_scan import selective_scan_pallas

        B, S, D, N = shape
        rng = np.random.default_rng(sum(shape))
        u = jnp.asarray(rng.normal(size=(B, S, D)).astype(np.float32))
        dt = jnp.asarray(np.abs(rng.normal(size=(B, S, D))).astype(np.float32) * 0.1)
        Bc = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
        Cc = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
        A = jnp.asarray(-np.abs(rng.normal(size=(D, N))).astype(np.float32))
        Ds = jnp.asarray(rng.normal(size=(D,)).astype(np.float32))
        y1, h1 = selective_scan_pallas(u, dt, Bc, Cc, A, Ds,
                                       block_d=min(16, D), block_s=min(16, S),
                                       interpret=True)
        y2, h2 = ref.selective_scan_ref(u, dt, Bc, Cc, A, Ds)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-5)

    def test_block_shape_independence(self):
        from repro.kernels.selective_scan import selective_scan_pallas

        rng = np.random.default_rng(1)
        B, S, D, N = 2, 64, 32, 8
        args = (
            jnp.asarray(rng.normal(size=(B, S, D)).astype(np.float32)),
            jnp.asarray(np.abs(rng.normal(size=(B, S, D))).astype(np.float32) * 0.1),
            jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32)),
            jnp.asarray(-np.abs(rng.normal(size=(D, N))).astype(np.float32)),
            jnp.asarray(rng.normal(size=(D,)).astype(np.float32)),
        )
        y1, h1 = selective_scan_pallas(*args, block_d=32, block_s=64,
                                       interpret=True)
        y2, h2 = selective_scan_pallas(*args, block_d=8, block_s=16,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)

    def test_initial_state_carried(self):
        """Scanning [first half] then [second half with h0] must equal one
        full scan — the chunked-serving contract."""
        from repro.kernels.selective_scan import selective_scan_pallas

        rng = np.random.default_rng(2)
        B, S, D, N = 1, 32, 16, 4
        u = jnp.asarray(rng.normal(size=(B, S, D)).astype(np.float32))
        dt = jnp.asarray(np.abs(rng.normal(size=(B, S, D))).astype(np.float32) * 0.1)
        Bc = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
        Cc = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
        A = jnp.asarray(-np.abs(rng.normal(size=(D, N))).astype(np.float32))
        Ds = jnp.asarray(rng.normal(size=(D,)).astype(np.float32))
        y_full, h_full = selective_scan_pallas(u, dt, Bc, Cc, A, Ds,
                                               block_d=16, block_s=16,
                                               interpret=True)
        h = S // 2
        y_a, h_a = selective_scan_pallas(u[:, :h], dt[:, :h], Bc[:, :h],
                                         Cc[:, :h], A, Ds, block_d=16, block_s=16,
                                         interpret=True)
        y_b, h_b = selective_scan_pallas(u[:, h:], dt[:, h:], Bc[:, h:],
                                         Cc[:, h:], A, Ds, h0=h_a,
                                         block_d=16, block_s=16,
                                         interpret=True)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y_a, y_b], axis=1)),
            np.asarray(y_full), atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_b), np.asarray(h_full), atol=1e-5)
