"""Pallas kernel: bit-serial DCIM matrix-vector/matrix multiply.

TPU-native re-expression of the paper's multiply-based DCIM dataflow
(Fig. 3/5).  The hardware streams ``k`` input bits per cycle against
1-bit weight planes; a column adder tree sums H products, the shift
accumulator folds the B_x/k input slices, and the result-fusion unit
folds the B_w weight bit-planes.  On TPU the natural mapping is:

  * weight bit-plane  V_b = (W >> b) & 1          (VPU bit ops, in VMEM)
  * input k-bit slice U_s = (U >> s*k) & (2^k-1)
  * "adder tree"      = one MXU matmul  U_s @ V_b  (int32 accumulate)
  * shift-accumulate  = sum_s 2^(k*s) * (.)
  * result fusion     = sum_b 2^b     * (.)

Signedness is two's complement, as in a DCIM macro: the top input slice
is taken with an arithmetic shift (so it carries the sign) and, for
signed weights, the top bit-plane has weight -2^(Bw-1):

  X = sum_s 2^(k*s) * X_s,   W = sum_b c_b * 2^b * W_b,   c_top = -1

so X @ W = sum_{b,s} c_b * 2^(b + k*s) * (X_s @ W_b) equals the exact
integer matmul — which is what a full-precision DCIM macro computes.
The grid is (M/BM, N/BN, K/BK) with int32 accumulation over the K
dimension in VMEM.

Each partial matmul runs on the MXU in bf16 with f32 accumulation (the
MXU takes no int32 operands).  That is exact: slice values satisfy
|X_s| <= 2^k - 1 <= 255 and W_b is 0 or 1, so every operand is an
integer bf16 holds exactly, and every partial sum is an integer below
BK * 2^k <= 2^24, which f32 holds exactly in any summation order.

Validity range: |Y| < 2^31 and k <= 8.  Guaranteed when
K * 2^(Bx+Bw) < 2^31, e.g. any K <= 32768 for INT8 x INT8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# MXU-aligned tiles.
BLOCK_M = 128
BLOCK_N = 128
BLOCK_K = 128


def _dcim_mvm_kernel(x_ref, w_ref, out_ref, *, B_x, B_w, k, x_signed, w_signed):
    x = x_ref[...]                              # (BM, BK) int32
    w = w_ref[...]                              # (BK, BN) int32
    n_slices = -(-B_x // k)                     # ceil(B_x / k)

    def x_slice(s):
        shifted = jnp.right_shift(x, s * k)
        if x_signed and s == n_slices - 1:
            return shifted                      # signed top slice
        return jnp.bitwise_and(shifted, (1 << k) - 1)

    def dot(a, b):
        # Small-integer operands, exact in bf16; f32 accumulation is
        # exact below 2^24 (module docstring).
        bf = lambda t: t.astype(jnp.float32).astype(jnp.bfloat16)
        return jax.lax.dot_general(
            bf(a), bf(b), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)

    acc = jnp.zeros(out_ref.shape, jnp.int32)
    # Result fusion over weight bit-planes x shift-accumulate over slices.
    for b in range(B_w):
        w_plane = jnp.bitwise_and(jnp.right_shift(w, b), 1)
        negative = w_signed and b == B_w - 1    # two's-complement sign plane
        for s in range(n_slices):
            term = dot(x_slice(s), w_plane) << (b + s * k)
            acc = acc - term if negative else acc + term

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(pl.program_id(2) != 0)
    def _accum():
        out_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=(
        "B_x", "B_w", "k", "x_signed", "w_signed",
        "block_m", "block_n", "block_k", "interpret",
    ),
)
def dcim_mvm_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    B_x: int = 8,
    B_w: int = 8,
    k: int = 4,
    x_signed: bool = True,
    w_signed: bool = True,
    block_m: int = BLOCK_M,
    block_n: int = BLOCK_N,
    block_k: int = BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Exact integer matmul via the DCIM bit-serial dataflow.

    x: (M, K) int32 in [-2^(Bx-1), 2^(Bx-1)) (or [0, 2^Bx) unsigned)
    w: (K, N) int32 in the analogous B_w range
    returns (M, N) int32 == x @ w
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert 1 <= k <= 8, f"slice width k={k} outside the exact range 1..8"
    Mp = pl.cdiv(M, block_m) * block_m
    Np = pl.cdiv(N, block_n) * block_n
    Kp = pl.cdiv(K, block_k) * block_k
    xp = jnp.zeros((Mp, Kp), jnp.int32).at[:M, :K].set(x.astype(jnp.int32))
    wp = jnp.zeros((Kp, Np), jnp.int32).at[:K, :N].set(w.astype(jnp.int32))

    kernel = functools.partial(
        _dcim_mvm_kernel,
        B_x=B_x, B_w=B_w, k=k, x_signed=x_signed, w_signed=w_signed,
    )
    out = pl.pallas_call(
        kernel,
        grid=(Mp // block_m, Np // block_n, Kp // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]
