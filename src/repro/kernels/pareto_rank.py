"""Pallas kernel: pairwise (constrained-)Pareto dominance matrix.

The NSGA-II hot spot is the O(P^2 * M) dominance computation performed
every generation.  On TPU we tile the P x P comparison space into
(BI, BJ) VMEM blocks; each grid cell loads a (BI, M) strip and a
transposed (M, BJ) strip of the objective matrix (M is tiny — 4 for
SEGA-DCIM) and folds the M objectives into (BI, BJ) compares on the
VPU, one objective at a time.  Output is an int8 matrix D with
D[i, j] == 1 iff candidate i constrained-dominates j.

Constrained domination (Deb 2002) folds the violation scalar in:
  i dominates j  <=>  (feas_i & feas_j & pareto_dom(i, j)) | (v_i < v_j)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Default VMEM tile: 128 x 128 comparisons; a (128, M=4) f32 strip is
# 2 KiB, the int8 output tile is 16 KiB — comfortably within VMEM.
BLOCK_I = 128
BLOCK_J = 128


def _dominance_kernel(fi_ref, fjt_ref, vi_ref, vj_ref, out_ref):
    fi = fi_ref[...]          # (BI, M) objectives of candidates i
    fjt = fjt_ref[...]        # (M, BJ) objectives of candidates j, transposed
    vi = vi_ref[...]          # (BI, 1) constraint violation of i
    vj = vj_ref[...]          # (1, BJ) violation of j

    # One (BI, BJ) compare per objective: an i column against a j row,
    # so every operand stays 2-D with the j axis on the lanes.
    le = lt = None
    for m in range(fi.shape[1]):
        a, b = fi[:, m:m + 1], fjt[m:m + 1, :]
        le = (a <= b) if le is None else le & (a <= b)
        lt = (a < b) if lt is None else lt | (a < b)
    pdom = le & lt

    cdom = ((vi <= 0.0) & (vj <= 0.0) & pdom) | (vi < vj)
    out_ref[...] = jnp.where(cdom, 1, 0).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def dominance_matrix_pallas(
    F: jnp.ndarray,
    violation: jnp.ndarray | None = None,
    block_i: int = BLOCK_I,
    block_j: int = BLOCK_J,
    interpret: bool = False,
) -> jnp.ndarray:
    """(P, M) objectives [+ (P,) violation] -> (P, P) int8 dominance matrix.

    Inputs are padded to the block grid with +inf objectives / +inf
    violation; padded rows dominate nothing and the padded region is
    sliced away, so results are exact for any P.
    """
    P, M = F.shape
    F = jnp.where(jnp.isnan(F), jnp.inf, F.astype(jnp.float32))
    v = (
        jnp.zeros((P,), jnp.float32)
        if violation is None
        else violation.astype(jnp.float32)
    )

    Pi = pl.cdiv(P, block_i) * block_i
    Pj = pl.cdiv(P, block_j) * block_j
    Ppad = max(Pi, Pj)
    Fp = jnp.full((Ppad, M), jnp.inf, jnp.float32).at[:P].set(F)
    vp = jnp.full((Ppad,), jnp.float32(jnp.inf)).at[:P].set(v)

    grid = (Ppad // block_i, Ppad // block_j)
    out = pl.pallas_call(
        _dominance_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, M), lambda i, j: (i, 0)),
            pl.BlockSpec((M, block_j), lambda i, j: (0, j)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_j), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ppad, Ppad), jnp.int8),
        interpret=interpret,
    )(Fp, Fp.T, vp[:, None], vp[None, :])
    return out[:P, :P]
