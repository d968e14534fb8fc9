"""Pallas kernel: floating-point pre-alignment (paper Fig. 3, §III-A).

Implements the FP Pre-alignment module: for each group of H values along
the reduction axis, (1) a comparison tree finds the maximum exponent
X_Emax, (2) each value's mantissa (hidden bit included, two's-complement
signed, B_M bits) is barrel-shifted right by (X_Emax - X_E).  The aligned
mantissas can then feed the integer DCIM array directly; the group
exponent is consumed by the INT->FP converter after accumulation.

On TPU this is pure VPU work on f32 bit patterns in VMEM: exponent
extraction is a shift/mask of the bitcast int32, the max-tree is a
reduction over the trailing (H) axis, and the alignment shift is an
arithmetic right-shift.  Grid tiles (rows x groups); each block holds
(BM, BG, H) values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8
BLOCK_GROUPS = 8


def _prealign_kernel(x_ref, mant_ref, emax_ref, *, B_M):
    x = x_ref[...]                                   # (BM, BG, H) f32
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    sign = jnp.right_shift(bits, 31) & 1
    exp = jnp.right_shift(bits, 23) & 0xFF           # biased exponent
    frac = bits & 0x7FFFFF

    # B_M-bit magnitude mantissa including the hidden bit.  IEEE zero /
    # subnormals (exp == 0) carry no hidden bit -> mantissa 0 (hardware
    # flushes subnormals, as does the paper's datapath).
    full = jnp.where(exp > 0, frac | (1 << 23), 0)
    mant = jnp.right_shift(full, 23 - (B_M - 1))     # in [2^(B_M-1), 2^B_M)
    mant = jnp.where(sign == 1, -mant, mant)         # two's complement

    emax = jnp.max(exp, axis=-1, keepdims=True)      # comparison tree
    shift = jnp.minimum(emax - exp, 31)
    aligned = jnp.right_shift(mant, shift)           # arithmetic shift

    mant_ref[...] = aligned
    emax_ref[...] = emax                             # (BM, BG, 1)


@functools.partial(
    jax.jit,
    static_argnames=("B_M", "block_rows", "block_groups", "interpret"),
)
def fp_prealign_pallas(
    x: jnp.ndarray,
    B_M: int = 8,
    block_rows: int = BLOCK_ROWS,
    block_groups: int = BLOCK_GROUPS,
    interpret: bool = False,
):
    """x: (M, G, H) float32 -> (aligned int32 mantissas (M, G, H),
    biased group exponents (M, G) int32)."""
    M, G, H = x.shape
    Mp = pl.cdiv(M, block_rows) * block_rows
    Gp = pl.cdiv(G, block_groups) * block_groups
    xp = jnp.zeros((Mp, Gp, H), jnp.float32).at[:M, :G].set(
        x.astype(jnp.float32)
    )
    kernel = functools.partial(_prealign_kernel, B_M=B_M)
    mant, emax = pl.pallas_call(
        kernel,
        grid=(Mp // block_rows, Gp // block_groups),
        in_specs=[
            pl.BlockSpec((block_rows, block_groups, H), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, block_groups, H), lambda i, j: (i, j, 0)),
            # A trailing unit dim keeps the exponent block legal for the
            # TPU tiling (its last dim equals the array's).
            pl.BlockSpec((block_rows, block_groups, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Gp, H), jnp.int32),
            jax.ShapeDtypeStruct((Mp, Gp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xp)
    return mant[:M, :G], emax[:M, :G, 0]
