"""Mamba-1 (selective SSM) mixer with chunked parallel scan.

Training/prefill: the sequence is cut into chunks; within a chunk the
linear recurrence  h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t  is solved
with ``jax.lax.associative_scan`` (log-depth, materializes only
(chunk, d_inner, d_state) states), and chunk boundary states are carried
by an outer ``lax.scan`` — memory O(S/chunk * d_inner * d_state) instead
of O(S * d_inner * d_state).

Decode: O(1) state update — the reason SSMs run the 500k-context cell.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard_act
from .common import dense, dense_init


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0          # 0 -> d_model / 16


def ssm_dims(cfg):
    s: SSMConfig = cfg.ssm
    d_inner = s.expand * cfg.d_model
    dt_rank = s.dt_rank or max(cfg.d_model // 16, 1)
    return d_inner, dt_rank


def mamba_init(key, cfg, dtype):
    s: SSMConfig = cfg.ssm
    d_inner, dt_rank = ssm_dims(cfg)
    ks = jax.random.split(key, 6)
    # S4D-real initialization of A; dt bias so softplus(dt) spans
    # [1e-3, 1e-1] as in the Mamba reference.
    A = jnp.tile(jnp.arange(1, s.d_state + 1, dtype=jnp.float32), (d_inner, 1))
    dt = jnp.exp(
        jax.random.uniform(ks[0], (d_inner,))
        * (math.log(0.1) - math.log(1e-3))
        + math.log(1e-3)
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))   # inverse softplus
    return {
        "in_proj": dense_init(ks[1], cfg.d_model, 2 * d_inner, dtype),
        "conv_w": (
            jax.random.normal(ks[2], (d_inner, s.d_conv)) / math.sqrt(s.d_conv)
        ).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "x_proj": dense_init(ks[3], d_inner, dt_rank + 2 * s.d_state, dtype),
        "dt_proj": dense_init(ks[4], dt_rank, d_inner, jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(A),
        "D": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(ks[5], d_inner, cfg.d_model, dtype),
    }


def _ssm_raw(p, u, cfg):
    """u: (B, S, Di) post-conv -> (dt, B_c, C_c, A) recurrence inputs."""
    s: SSMConfig = cfg.ssm
    _, dt_rank = ssm_dims(cfg)
    xp = dense(p["x_proj"], u)
    dt_in, Bc, Cc = jnp.split(xp, [dt_rank, dt_rank + s.d_state], axis=-1)
    dt = jax.nn.softplus(
        dense(p["dt_proj"], dt_in.astype(jnp.float32)) + p["dt_bias"]
    )                                                    # (B, S, Di)
    A = -jnp.exp(p["A_log"])                             # (Di, N)
    return dt, Bc.astype(jnp.float32), Cc.astype(jnp.float32), A


def _ssm_coeffs(p, u, cfg):
    """u: (B, S, Di) post-conv activations -> (dA, dBu, C) scan coefficients."""
    dt, Bc, Cc, A = _ssm_raw(p, u, cfg)
    dA = jnp.exp(dt[..., None] * A)                      # (B, S, Di, N)
    dBu = (dt * u.astype(jnp.float32))[..., None] * Bc[..., None, :]
    return dA, dBu, Cc


def _scan_chunk(h0, dA, dBu):
    """Solve h_t = dA_t h_{t-1} + dBu_t within one chunk via associative
    scan; h0: (B, Di, N); dA/dBu: (B, C, Di, N).  Returns all h_t."""
    def combine(a, b):
        a1, b1 = a
        a2, b2 = b
        return a1 * a2, b1 * a2 + b2

    aa, bb = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
    return aa * h0[:, None] + bb                          # (B, C, Di, N)


def _valid_mask(S: int, valid_len) -> jnp.ndarray:
    """(1, S) or (B, S) bool mask of real (non-right-pad) positions.
    ``valid_len`` may be a traced scalar (one valid length for the whole
    batch — single-request bucketed prefill) or a (B,) vector (batched
    burst prefill: each co-batched request has its own tail length)."""
    vl = jnp.asarray(valid_len)
    if vl.ndim == 0:
        vl = vl[None]
    return jnp.arange(S)[None, :] < vl[:, None]


def _pallas_scan(p, u, cfg, valid_len=None):
    """Fused Pallas selective scan (§Perf: one HBM pass instead of the
    associative scan's ~16).  Wrapped in shard_map when a mesh context is
    active: the recurrence is local in (batch, d_inner), sequential in S
    — no cross-device communication.  Forward-only (serving/prefill)."""
    from repro.dist import sharding as shd
    from repro.kernels.selective_scan import selective_scan_pallas

    dt, Bc, Cc, A = _ssm_raw(p, u, cfg)
    if valid_len is not None:
        # dt = 0 at padded steps -> dA = exp(0) = 1, dBu = dt*B*u = 0:
        # the kernel carries the state through pads unchanged.
        dt = jnp.where(_valid_mask(u.shape[1], valid_len)[..., None], dt, 0.0)
    D_skip = p["D"]

    def run(u_, dt_, b_, c_, a_, d_):
        # Compiled on TPU; off TPU the interpreter runs the same kernel.
        y, h = selective_scan_pallas(
            u_, dt_, b_, c_, a_, d_,
            interpret=jax.default_backend() != "tpu",
        )
        return y, h

    ctx = shd.current()
    if ctx is None:
        return run(u, dt, Bc, Cc, A, D_skip)

    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    bspec = ctx.spec(("batch", None, "d_inner"), u.shape)
    sspec = ctx.spec(("batch", None, None), Bc.shape)
    aspec = ctx.spec(("d_inner", None), A.shape)
    dspec = ctx.spec(("d_inner",), D_skip.shape)
    hspec = ctx.spec(("batch", "d_inner", None),
                     (u.shape[0], u.shape[2], A.shape[1]))
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(bspec, bspec, sspec, sspec, aspec, dspec),
        out_specs=(bspec, hspec),
        check_vma=False,
    )(u, dt, Bc, Cc, A, D_skip)


def mamba_mix(p, x, cfg, chunk: int, return_state: bool = False,
              training: bool = True, valid_len=None):
    """x: (B, S, D) -> (B, S, D), full-sequence (train/prefill).
    With ``return_state`` also returns the decode cache {"h", "conv"}
    capturing the post-prompt SSM state and conv tail.  When
    ``cfg.ssm_impl == "pallas"`` and not training, the recurrence runs in
    the fused Pallas kernel (no autodiff rule -> training keeps the
    differentiable associative scan).

    ``valid_len`` (traced scalar, or a (B,) vector of per-row lengths for
    batched burst prefill) marks positions >= valid_len as
    right-padding: their recurrence step is forced to the identity
    (dA = 1, dBu = 0, i.e. dt = 0) so the returned state is the state
    after the *valid* prefix, and the conv tail is taken ending at
    ``valid_len`` — bucketed prefill pads prompts without perturbing the
    decode cache.  Outputs at padded positions are unspecified."""
    s: SSMConfig = cfg.ssm
    d_inner, _ = ssm_dims(cfg)
    B, S, _ = x.shape
    xz = dense(p["in_proj"], x)
    u, z = jnp.split(xz, 2, axis=-1)                      # (B, S, Di) each
    u = shard_act(u, ("batch", None, "d_inner"))
    u_raw = u                                             # pre-conv (cache tail)

    # Depthwise causal conv, width d_conv.
    w = p["conv_w"].astype(u.dtype)                       # (Di, K)
    upad = jnp.pad(u, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
    conv = sum(
        upad[:, i : i + S] * w[:, i] for i in range(s.d_conv)
    ) + p["conv_b"].astype(u.dtype)
    u = jax.nn.silu(conv)

    if getattr(cfg, "ssm_impl", "assoc") == "pallas" and not training:
        y, h_last = _pallas_scan(p, u, cfg, valid_len=valid_len)
    else:
        dA, dBu, Cc = _ssm_coeffs(p, u, cfg)
        if valid_len is not None:
            keep = _valid_mask(S, valid_len)[..., None, None]
            dA = jnp.where(keep, dA, 1.0)
            dBu = jnp.where(keep, dBu, 0.0)

        chunk = min(chunk, S)
        while S % chunk:
            chunk -= 1
        n = S // chunk

        def body(h, xs):
            dAc, dBuc = xs                                # (B, C, Di, N)
            hs = _scan_chunk(h, dAc, dBuc)
            return hs[:, -1], hs

        dAc = dA.reshape(B, n, chunk, d_inner, s.d_state).swapaxes(0, 1)
        dBuc = dBu.reshape(B, n, chunk, d_inner, s.d_state).swapaxes(0, 1)
        h0 = jnp.zeros((B, d_inner, s.d_state), jnp.float32)
        h_last, hs = jax.lax.scan(body, h0, (dAc, dBuc))
        hs = hs.swapaxes(0, 1).reshape(B, S, d_inner, s.d_state)
        y = jnp.einsum("bsdn,bsn->bsd", hs, Cc) + p["D"] * u.astype(jnp.float32)
    # (the Pallas kernel applies the D skip internally)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    y = shard_act(y, ("batch", None, "d_inner"))
    out = dense(p["out_proj"], y)
    if not return_state:
        return out
    if valid_len is None:
        tail = u_raw[:, S - (s.d_conv - 1):, :] if S >= s.d_conv - 1 else jnp.pad(
            u_raw, ((0, 0), (s.d_conv - 1 - S, 0), (0, 0))
        )
    else:
        # Window of d_conv-1 pre-conv inputs ending at valid_len; the
        # left zero-pad makes valid_len < d_conv-1 match the short-prompt
        # branch above bit for bit.
        upad_l = jnp.pad(u_raw, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
        vl = jnp.asarray(valid_len)
        if vl.ndim == 0:
            tail = jax.lax.dynamic_slice(
                upad_l, (0, valid_len, 0),
                (u_raw.shape[0], s.d_conv - 1, u_raw.shape[2]),
            )
        else:
            # Per-row valid lengths (batched burst prefill): gather each
            # row's window — same values dynamic_slice would produce row
            # by row.
            idx = vl[:, None] + jnp.arange(s.d_conv - 1)[None, :]
            tail = jnp.take_along_axis(upad_l, idx[..., None], axis=1)
    return out, {"h": h_last, "conv": tail}


def mamba_cache_init(cfg, batch: int, dtype=jnp.float32):
    s: SSMConfig = cfg.ssm
    d_inner, _ = ssm_dims(cfg)
    return {
        "h": jnp.zeros((batch, d_inner, s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, d_inner), dtype),
    }


def mamba_step(p, x, cfg, cache):
    """Single-token decode: x (B, 1, D); O(1) state update."""
    s: SSMConfig = cfg.ssm
    B = x.shape[0]
    xz = dense(p["in_proj"], x[:, 0])
    u, z = jnp.split(xz, 2, axis=-1)                      # (B, Di)

    hist = jnp.concatenate([cache["conv"], u[:, None].astype(cache["conv"].dtype)], axis=1)
    w = p["conv_w"].astype(u.dtype)                       # (Di, K)
    conv = jnp.einsum("bkd,dk->bd", hist.astype(u.dtype), w) + p["conv_b"].astype(u.dtype)
    uc = jax.nn.silu(conv)

    dA, dBu, Cc = _ssm_coeffs(p, uc[:, None], cfg)        # (B,1,Di,N) etc.
    h = cache["h"] * dA[:, 0] + dBu[:, 0]
    y = jnp.einsum("bdn,bn->bd", h, Cc[:, 0]) + p["D"] * uc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = dense(p["out_proj"], y[:, None])
    return out, {"h": h, "conv": hist[:, 1:]}
