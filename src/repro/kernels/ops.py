"""Public jit'd wrappers around the Pallas kernels, and the backend
dispatch layer.

On TPU the kernels run compiled (``interpret=False``).  Off TPU every
public entry point auto-falls back to a bit-identical XLA reference —
the Pallas interpreter lowers the kernel body to XLA ops too, but pays
a large tracing/compile overhead per call (BENCH_kernels.json showed
interpreter-mode ``dcim_mvm`` at ~60x its XLA structural ref on CPU),
so the interpreter is reserved for parity tests, which force it with
``interpret=True`` / ``AttnBackend.PALLAS_INTERPRET``.

The attention dispatchers (:func:`paged_decode_gqa`,
:func:`paged_decode_mla`, :func:`prefix_prefill`) follow the same
pattern behind the :class:`AttnBackend` enum; ``LMConfig.attn_backend``
threads the choice through the serving stack with zero call-site churn.
"""
from __future__ import annotations

import enum
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as _P

from repro.dist import sharding as _shd

from . import dcim_mvm as _mvm
from . import fp_prealign as _pre
from . import paged_attention as _pa
from . import pareto_rank as _rank
from . import ref as _ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# --- attention backend dispatch ----------------------------------------------
class AttnBackend(str, enum.Enum):
    """Which implementation serves the paged-attention entry points.

    AUTO resolves to PALLAS on TPU and XLA elsewhere.  The XLA path is
    the original gather+attend reference in ``repro.models.attention``;
    PALLAS is the fused kernel in ``repro.kernels.paged_attention``
    (bitwise identical — asserted in tests/test_paged_attention.py);
    PALLAS_INTERPRET forces the Pallas interpreter off-TPU so parity
    tests and end-to-end serving runs exercise the kernel body on CPU.
    """

    AUTO = "auto"
    XLA = "xla"
    PALLAS = "pallas"
    PALLAS_INTERPRET = "pallas_interpret"


def resolve_attn_backend(backend=None) -> AttnBackend:
    b = AttnBackend(backend) if backend else AttnBackend.AUTO
    if b is AttnBackend.AUTO:
        return AttnBackend.XLA if _interpret_default() else AttnBackend.PALLAS
    return b


def _per_device_heads(kernel, args, head_axes, n_kv):
    """Run a Pallas attention kernel under the active mesh context.

    XLA cannot partition a Mosaic kernel, so inside a serving mesh each
    device runs the kernel in ``shard_map`` on the KV heads it holds
    (``head_axes[i]`` is the head axis of ``args[i]``, None for an
    argument every device holds whole; the output's head axis is 2).
    Attention never mixes heads, so this is the unsharded result.
    Without a context, or where ``n_kv`` (None for MLA, whose latent
    cache is replicated) does not split over the mesh, it runs whole."""
    ctx = _shd.current()
    if ctx is None:
        return kernel(*args)
    axes = None if n_kv is None else ctx.axes_for("kv_heads", n_kv)

    def spec(ndim, axis):
        if axis is None or not axes:
            return _P()
        entries = [None] * ndim
        entries[axis] = axes[0] if len(axes) == 1 else axes
        return _P(*entries)

    return jax.shard_map(
        kernel, mesh=ctx.mesh,
        in_specs=tuple(spec(a.ndim, ax) for a, ax in zip(args, head_axes)),
        out_specs=spec(4, 2), check_vma=False,
    )(*args)


def paged_decode_gqa(q, k_pages, v_pages, block_table, pos, backend=None):
    """Paged GQA decode attention: (B, 1, H, hd) q against the slot's
    pages.  XLA: gather a contiguous per-slot view, run
    ``decode_attention``.  PALLAS: the fused block-table kernel."""
    b = resolve_attn_backend(backend)
    if b is AttnBackend.XLA:
        from repro.models import attention as _attn

        return _attn.decode_attention(
            q,
            _attn._gather_pages(k_pages, block_table),
            _attn._gather_pages(v_pages, block_table),
            pos,
        )
    kernel = functools.partial(
        _pa.paged_decode_gqa_pallas,
        interpret=b is AttnBackend.PALLAS_INTERPRET,
    )
    return _per_device_heads(
        kernel, (q, k_pages, v_pages, block_table, pos),
        (2, 2, 2, None, None), k_pages.shape[2],
    )


def paged_decode_mla(q_abs, q_rope, ckv_pages, krope_pages, block_table,
                     pos, scale: float, backend=None):
    """Paged absorbed-MLA decode in the compressed c_kv space; returns
    the (B, 1, H, r) f32 context (``w_uv`` up-projection stays with the
    caller)."""
    b = resolve_attn_backend(backend)
    if b is AttnBackend.XLA:
        from repro.models import attention as _attn

        return _attn.mla_attend_core(
            q_abs, q_rope,
            _attn._gather_pages(ckv_pages, block_table),
            _attn._gather_pages(krope_pages, block_table),
            pos, scale,
        )
    kernel = functools.partial(
        _pa.paged_decode_mla_pallas, scale=scale,
        interpret=b is AttnBackend.PALLAS_INTERPRET,
    )
    args = (q_abs, q_rope, ckv_pages, krope_pages, block_table, pos)
    return _per_device_heads(kernel, args, (None,) * len(args), None)


def prefix_prefill(q, k_ctx, v_ctx, k_tail, v_tail, ctx_len, backend=None):
    """[reused-context ; causal-tail] prefill attention.  ``k_ctx`` /
    ``v_ctx`` are None when the prefix machinery is compiled out (L=0).
    XLA: concatenate and run ``prefix_attention``; PALLAS: the fused
    kernel (no HBM concat, no (B, Hk, G, T, L+T) score tensor)."""
    b = resolve_attn_backend(backend)
    if b is AttnBackend.XLA:
        from repro.models import attention as _attn

        if k_ctx is None:
            return _attn.prefix_attention(q, k_tail, v_tail, ctx_len, 0)
        return _attn.prefix_attention(
            q,
            jnp.concatenate([k_ctx, k_tail], axis=1),
            jnp.concatenate([v_ctx, v_tail], axis=1),
            ctx_len, k_ctx.shape[1],
        )
    interpret = b is AttnBackend.PALLAS_INTERPRET
    if k_ctx is None:
        def kernel(q, k_tail, v_tail, ctx_len):
            return _pa.prefix_prefill_pallas(
                q, None, None, k_tail, v_tail, ctx_len, interpret=interpret)
        args, axes = (q, k_tail, v_tail, ctx_len), (2, 2, 2, None)
    else:
        kernel = functools.partial(_pa.prefix_prefill_pallas,
                                   interpret=interpret)
        args = (q, k_ctx, v_ctx, k_tail, v_tail, ctx_len)
        axes = (2, 2, 2, 2, 2, None)
    return _per_device_heads(kernel, args, axes, k_tail.shape[2])


# --- pareto_rank -------------------------------------------------------------
def dominance_matrix(F, violation=None, interpret: bool | None = None):
    """(P, M) objectives -> (P, P) bool constrained-dominance matrix.

    On TPU this is the compiled Pallas ``pareto_rank`` kernel.  On CPU
    (``interpret=None`` auto-detection) it falls back to the broadcasted
    XLA dominance from ``repro.core.pareto`` — bit-identical (tested in
    tests/test_kernels.py) and much cheaper to compile than interpreter
    mode, which matters when NSGA-II vmaps the dominance over a scenario
    axis.  Pass ``interpret=True`` to force the Pallas interpreter (the
    kernel-parity tests do)."""
    if interpret is None and _interpret_default():
        from repro.core import pareto

        return pareto.dominance_matrix(
            jnp.asarray(F),
            None if violation is None else jnp.asarray(violation),
        )
    out = _rank.dominance_matrix_pallas(
        jnp.asarray(F),
        None if violation is None else jnp.asarray(violation),
        interpret=False if interpret is None else interpret,
    )
    return out.astype(jnp.bool_)


# --- dcim_mvm ----------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("B_x", "B_w", "k", "x_signed", "w_signed", "interpret"),
)
def dcim_mvm(
    x,
    w,
    B_x: int = 8,
    B_w: int = 8,
    k: int = 4,
    x_signed: bool = True,
    w_signed: bool = True,
    interpret: bool | None = None,
):
    """Exact integer matmul through the DCIM bit-serial dataflow.

    Off TPU (``interpret=None`` auto-detection) this dispatches to the
    XLA structural reference — the same bit-serial decomposition in
    plain jnp, bitwise identical to the kernel (tested in
    tests/test_kernels.py) and much faster than interpreter mode on
    CPU (jitted here: the decomposition's many slice/shift ops would
    otherwise pay per-op eager dispatch).  ``interpret=True`` forces
    the Pallas interpreter (parity tests do)."""
    if interpret is None and _interpret_default():
        return _ref.dcim_mvm_structural_ref(
            jnp.asarray(x), jnp.asarray(w), B_x=B_x, B_w=B_w, k=k,
            x_signed=x_signed, w_signed=w_signed,
        )
    return _mvm.dcim_mvm_pallas(
        jnp.asarray(x),
        jnp.asarray(w),
        B_x=B_x,
        B_w=B_w,
        k=k,
        x_signed=x_signed,
        w_signed=w_signed,
        interpret=False if interpret is None else interpret,
    )


# --- fp_prealign ---------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("H", "B_M", "interpret"))
def fp_prealign(x, H: int, B_M: int = 8, interpret: bool | None = None):
    """x: (M, K) f32, groups of H along K -> (mant (M, G, H) int32,
    group biased exponents (M, G) int32).

    Off TPU (``interpret=None``) dispatches to the frexp-based XLA
    reference (bitwise identical, tested in tests/test_kernels.py);
    ``interpret=True`` forces the Pallas interpreter."""
    M, K = x.shape
    assert K % H == 0, f"K={K} not divisible by group height H={H}"
    xg = jnp.asarray(x, jnp.float32).reshape(M, K // H, H)
    if interpret is None and _interpret_default():
        return _ref.fp_prealign_ref(xg, B_M=B_M)
    return _pre.fp_prealign_pallas(
        xg, B_M=B_M,
        interpret=False if interpret is None else interpret,
    )


# --- composed pre-aligned block-FP matmul (FP-DCIM pipeline) -------------------
@functools.partial(
    jax.jit, static_argnames=("H", "B_M", "B_w", "k", "interpret")
)
def dcim_fp_matmul(
    x,
    w,
    H: int = 64,
    B_M: int = 8,
    B_w: int = 8,
    k: int = 4,
    interpret: bool | None = None,
):
    """Full pre-aligned FP-DCIM pipeline (paper Fig. 3), end to end:

      1. online: pre-align input mantissas per H-group along K,
      2. offline: pre-align weight mantissas per H-group along K,
      3. integer mantissa MAC in the DCIM array (dcim_mvm per group),
      4. INT->FP conversion: scale each group's integer partial sum by
         2^(ex + ew) and accumulate in f32.

    x: (M, K) f32;  w: (K, N) f32;  returns (M, N) f32 approximating x @ w
    with block-FP (shared-group-exponent) numerics.
    """
    # ``interpret`` stays tri-state through the public dispatchers:
    # None auto-falls back to the XLA refs off TPU, True forces the
    # Pallas interpreter end to end (parity tests).
    interp = interpret
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and K % H == 0
    G = K // H

    mant_x, ex = fp_prealign(x, H, B_M, interpret=interp)          # (M,G,H),(M,G)
    mant_w, ew = fp_prealign(w.T, H, B_w, interpret=interp)        # (N,G,H),(N,G)

    narrow = (B_M + 1) + (B_w + 1) + math.ceil(math.log2(H)) <= 31

    if narrow:
        # Per-group integer MAC; exact in int32 (the hardware's B_r-wide
        # accumulator fits).  vmap over groups; each group is an exact
        # integer matmul through the bit-serial kernel.
        def group_mm(mx, mw):                                      # (M,H),(N,H)
            return dcim_mvm(
                mx, mw.T, B_x=B_M + 1, B_w=B_w + 1, k=k,
                x_signed=True, w_signed=True, interpret=interp,
            ).astype(jnp.float32)
    else:
        # Wide-mantissa path (FP32): split each mantissa into a signed
        # high half and an unsigned 12-bit low half; 4 partial integer
        # matmuls emulate the hardware's B_r-wide adder.  The 2^24/2^12
        # recombination happens in f32 (one extra rounding vs hardware,
        # bounded by 2^-24 relative).
        SPLIT = 12
        # Operand magnitudes: |hi| <= 2^(B-SPLIT), lo < 2^SPLIT.
        worst = 2 ** (2 * max(max(B_M, B_w) - SPLIT, SPLIT))
        if H * worst > 2**31:
            raise ValueError(
                f"H={H} too large for wide-mantissa emulation (B_M={B_M})"
            )

        def group_mm(mx, mw):
            xh, xl = mx >> SPLIT, mx & ((1 << SPLIT) - 1)
            wh, wl = mw >> SPLIT, mw & ((1 << SPLIT) - 1)

            def mm(a, b, bx, bw, xs, ws):
                return dcim_mvm(
                    a, b.T, B_x=bx, B_w=bw, k=k,
                    x_signed=xs, w_signed=ws, interpret=interp,
                ).astype(jnp.float32)

            hi_bits = max(B_M, B_w) + 1 - SPLIT + 1
            p_hh = mm(xh, wh, hi_bits, hi_bits, True, True)
            p_hl = mm(xh, wl, hi_bits, SPLIT, True, False)
            p_lh = mm(xl, wh, SPLIT, hi_bits, False, True)
            p_ll = mm(xl, wl, SPLIT, SPLIT, False, False)
            return (
                p_hh * float(2 ** (2 * SPLIT))
                + (p_hl + p_lh) * float(2**SPLIT)
                + p_ll
            )

    partials = jax.vmap(group_mm, in_axes=(1, 1))(mant_x, mant_w)  # (G,M,N)

    # INT->FP converter: 2^(ex+ew) group scale, remove the two mantissa
    # fixed-point offsets (B_M-1 / B_w-1) and the two IEEE biases (127).
    scale = jnp.exp2(
        ex[:, :, None].astype(jnp.float32)
        + ew.T[None, :, :].astype(jnp.float32)
        - (2 * 127 + (B_M - 1) + (B_w - 1))
    )                                                              # (M,G,N)
    out = jnp.sum(partials.transpose(1, 0, 2) * scale, axis=1)
    return out.astype(jnp.float32)


# ------------------------------ lint contract --------------------------------
from repro.analysis.registry import Built, PallasTrace, register_contract


@register_contract(
    "kernels.pallas",
    checks=("pallas", "precision"),
    description="every Pallas kernel traced at representative shapes: "
                "BlockSpec lane/sublane tiling, grid coverage of the "
                "padded arrays, interpreter-fallback accounting, and "
                "kernel-level precision hygiene (no f64, integer/low-"
                "precision dots declare their accumulator; register "
                "upcasts inside kernels are idiomatic, so the widening "
                "audit is off)",
)
def _build_kernels_contract() -> Built:
    from repro.kernels.pareto_rank import dominance_matrix_pallas
    from repro.kernels.selective_scan import selective_scan_pallas

    fallback = _interpret_default()
    traces = []

    F = jnp.zeros((130, 4), jnp.float32)
    traces.append(PallasTrace(
        "pareto_rank.dominance_matrix_pallas",
        jax.make_jaxpr(
            lambda f: dominance_matrix_pallas(f, interpret=True)
        )(F),
        interpret_fallback=fallback,
    ))

    x8 = jnp.zeros((32, 64), jnp.int32)
    w8 = jnp.zeros((64, 16), jnp.int32)
    traces.append(PallasTrace(
        "dcim_mvm.dcim_mvm_pallas",
        jax.make_jaxpr(
            lambda a, b: _mvm.dcim_mvm_pallas(
                a, b, B_x=8, B_w=8, k=4, interpret=True
            )
        )(x8, w8),
        interpret_fallback=fallback,
    ))

    xg = jnp.zeros((10, 3, 64), jnp.float32)
    traces.append(PallasTrace(
        "fp_prealign.fp_prealign_pallas",
        jax.make_jaxpr(
            lambda a: _pre.fp_prealign_pallas(a, B_M=8, interpret=True)
        )(xg),
        interpret_fallback=fallback,
    ))

    B, S, D, N = 2, 64, 128, 16
    traces.append(PallasTrace(
        "selective_scan.selective_scan_pallas",
        jax.make_jaxpr(
            lambda u, dt, b, c, a, d: selective_scan_pallas(
                u, dt, b, c, a, d, interpret=True
            )
        )(
            jnp.zeros((B, S, D), jnp.float32),
            jnp.zeros((B, S, D), jnp.float32),
            jnp.zeros((B, S, N), jnp.float32),
            jnp.zeros((B, S, N), jnp.float32),
            -jnp.ones((D, N), jnp.float32),
            jnp.zeros((D,), jnp.float32),
        ),
        interpret_fallback=fallback,
    ))

    # Fused paged-attention kernels, at TPU-representative shapes
    # (hd = 128 lanes).  Their block-table / position index maps take
    # scalar-prefetch refs, which the grid-coverage evaluator cannot
    # replay — that surfaces as a lint *warning*, by design.
    Bd, Hk, G, hd = 2, 2, 4, 128
    page, nb = 8, 3
    traces.append(PallasTrace(
        "paged_attention.paged_decode_gqa_pallas",
        jax.make_jaxpr(
            lambda q, kp, vp, bt, ps: _pa.paged_decode_gqa_pallas(
                q, kp, vp, bt, ps, interpret=True
            )
        )(
            jnp.zeros((Bd, 1, Hk * G, hd), jnp.float32),
            jnp.zeros((nb * Bd + 1, page, Hk, hd), jnp.bfloat16),
            jnp.zeros((nb * Bd + 1, page, Hk, hd), jnp.bfloat16),
            jnp.zeros((Bd, nb), jnp.int32),
            jnp.zeros((Bd,), jnp.int32),
        ),
        interpret_fallback=fallback,
    ))

    T, L = 8, 16
    traces.append(PallasTrace(
        "paged_attention.prefix_prefill_pallas",
        jax.make_jaxpr(
            lambda q, kc, vc, kt, vt, cl: _pa.prefix_prefill_pallas(
                q, kc, vc, kt, vt, cl, interpret=True
            )
        )(
            jnp.zeros((Bd, T, Hk * G, hd), jnp.float32),
            jnp.zeros((Bd, L, Hk, hd), jnp.bfloat16),
            jnp.zeros((Bd, L, Hk, hd), jnp.bfloat16),
            jnp.zeros((Bd, T, Hk, hd), jnp.bfloat16),
            jnp.zeros((Bd, T, Hk, hd), jnp.bfloat16),
            jnp.zeros((Bd,), jnp.int32),
        ),
        interpret_fallback=fallback,
    ))

    from repro.analysis.registry import PrecisionPolicy

    return Built(pallas=traces, precision=PrecisionPolicy(
        compute_dtype="float32", audit_widening=False,
    ))
