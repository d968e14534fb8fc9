"""Pallas kernel: fused Mamba-1 selective scan.

The associative-scan formulation moves (S, d_inner, d_state) arrays
through log2(chunk) combine levels — ~16 HBM passes over the state
tensor (the dominant memory term of the SSM cells, see EXPERIMENTS.md
§Perf).  The TPU-native fix is a fused kernel: the recurrent state
h (bd, N) lives in VMEM scratch for the whole sequence; u/dt/B/C stream
through once and y streams out once — optimal HBM traffic.

Grid: (batch, d_inner/bd, S/st) with the sequence dimension innermost;
the VMEM scratch state persists across the sequential S grid steps (the
standard TPU accumulator pattern).  Inside a block a fori_loop walks the
st timesteps with (N, bd) VPU updates:

    h   = exp(dt * A) * h + (dt * u) * B_t
    y_t = h . C_t + D * u_t

The state is kept transposed, (N, bd), so that d_inner sits on the
lanes: each step reads one (1, bd) row of u and dt from its block, and
only the (1, N) rows of B and C need turning into (N, 1) columns, which
an identity-masked lane reduction does exactly.  The wrapper transposes
A, h0 and the returned state to and from that layout.

Validated against the pure-jnp sequential oracle (ref.selective_scan_ref)
and cross-checked against the associative-scan path in models/mamba.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

BLOCK_D = 256
BLOCK_S = 512


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, h0_ref,
                 y_ref, hout_ref, h_scr):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        h_scr[...] = h0_ref[0]                        # (N, bd)

    at = at_ref[...]                                  # (N, bd)
    d = d_ref[...]                                    # (1, bd)
    N = at.shape[0]
    st = u_ref.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    def column(row):                                  # (1, N) -> (N, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, h):
        u_t = u_ref[0, pl.ds(t, 1), :]                # (1, bd)
        dt_t = dt_ref[0, pl.ds(t, 1), :]
        b_t = column(b_ref[0, pl.ds(t, 1), :])        # (N, 1)
        c_t = column(c_ref[0, pl.ds(t, 1), :])
        h = jnp.exp(dt_t * at) * h + (dt_t * u_t) * b_t
        y_ref[0, pl.ds(t, 1), :] = (
            jnp.sum(h * c_t, axis=0, keepdims=True) + d * u_t
        )
        return h

    h = jax.lax.fori_loop(0, st, step, h_scr[...])
    h_scr[...] = h
    hout_ref[0] = h


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_s", "interpret")
)
def selective_scan_pallas(
    u: jnp.ndarray,       # (B, S, D)
    dt: jnp.ndarray,      # (B, S, D)
    B_c: jnp.ndarray,     # (B, S, N)
    C_c: jnp.ndarray,     # (B, S, N)
    A: jnp.ndarray,       # (D, N), negative
    D_skip: jnp.ndarray,  # (D,)
    h0: jnp.ndarray | None = None,   # (B, D, N)
    block_d: int = BLOCK_D,
    block_s: int = BLOCK_S,
    interpret: bool = False,
):
    """Returns (y (B, S, D) f32, h_last (B, D, N) f32)."""
    Bsz, S, D = u.shape
    N = B_c.shape[-1]
    bd = min(block_d, D)
    st = min(block_s, S)
    assert D % bd == 0 and S % st == 0, (D, bd, S, st)
    if h0 is None:
        h0 = jnp.zeros((Bsz, D, N), jnp.float32)

    y, h_last = pl.pallas_call(
        _scan_kernel,
        grid=(Bsz, D // bd, S // st),
        in_specs=[
            pl.BlockSpec((1, st, bd), lambda b, di, si: (b, si, di)),  # u
            pl.BlockSpec((1, st, bd), lambda b, di, si: (b, si, di)),  # dt
            pl.BlockSpec((1, st, N), lambda b, di, si: (b, si, 0)),    # B
            pl.BlockSpec((1, st, N), lambda b, di, si: (b, si, 0)),    # C
            pl.BlockSpec((N, bd), lambda b, di, si: (0, di)),          # A^T
            pl.BlockSpec((1, bd), lambda b, di, si: (0, di)),          # D
            pl.BlockSpec((1, N, bd), lambda b, di, si: (b, 0, di)),    # h0^T
        ],
        out_specs=[
            pl.BlockSpec((1, st, bd), lambda b, di, si: (b, si, di)),
            pl.BlockSpec((1, N, bd), lambda b, di, si: (b, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, D), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, N, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        interpret=interpret,
    )(
        u.astype(jnp.float32), dt.astype(jnp.float32),
        B_c.astype(jnp.float32), C_c.astype(jnp.float32),
        A.astype(jnp.float32).T, D_skip.astype(jnp.float32)[None, :],
        h0.astype(jnp.float32).swapaxes(1, 2),
    )
    return y, h_last.swapaxes(1, 2)
