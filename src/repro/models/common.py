"""Shared model building blocks: norms, positions, FFNs, init helpers.

Pure-functional style: every module is an ``init(key, ...) -> params``
plus an ``apply(params, x, ...)`` pair operating on plain dict pytrees.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import current, repl_act, shard_act


def precision_island(name: str):
    """Declare a deliberate precision island around a block of ops.

    A thin wrapper over ``jax.named_scope`` with a tagged prefix: every
    equation traced inside carries ``island:<name>`` on its name stack,
    which ``repro.analysis.dtype_flow`` reads back to exempt the
    region's deliberate widening casts (f32 norms, rope tables, logits,
    optimizer moments, the DCIM quantize pipeline) from the precision
    lint.  Zero runtime cost — name stacks exist only in trace
    metadata."""
    return jax.named_scope(f"island:{name}")


def in_island(name: str):
    """Decorator form of :func:`precision_island` for whole functions."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with precision_island(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


# Pluggable matmul implementation for every ``dense`` in the model stack.
# ``repro.sim.functional.dcim_numerics`` installs a DCIM macro simulator
# here so serving (Engine / Scheduler) executes projections with the
# generated macro's numerics; ``None`` is the plain float path.  The hook
# is read at trace time, so jitted programs bake in whichever
# implementation was active when they were first called.
_MVM_IMPL = None


def set_mvm_impl(fn):
    """Install ``fn(x, w) -> y`` as the dense matmul; returns the
    previous implementation (for restore-on-exit context managers)."""
    global _MVM_IMPL
    prev = _MVM_IMPL
    _MVM_IMPL = fn
    return prev


def dense(p, x, reduce=None):
    """``x @ p["w"] (+ p["b"])`` accumulated in float32 and cast to
    ``x``'s dtype.  ``reduce``, where given, maps the cast product before
    the bias is added (the all-reduce of :func:`dense_row_parallel`)."""
    with precision_island("dense"):
        # Cast only on a real mismatch: a no-op convert_element_type in
        # the jaxpr would read as a spurious cast site to the lint.
        w = p["w"] if p["w"].dtype == x.dtype else p["w"].astype(x.dtype)
        if _MVM_IMPL is None:
            y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
            y = y.astype(x.dtype)
        else:
            y = _MVM_IMPL(x, w).astype(x.dtype)
        if reduce is not None:
            y = reduce(y)
        if "b" in p:
            b = p["b"] if p["b"].dtype == x.dtype else p["b"].astype(x.dtype)
            y = y + b
    return y


def dense_row_parallel(p, x, logical: str, scope: str):
    """``dense(p, x)`` for a row-parallel projection (``wo`` over the
    heads, ``w_down`` over the ff columns).  Under a serving mesh the
    contracted dim of ``x`` and the rows of ``p["w"]`` are both split by
    ``logical``: each device multiplies what it holds, accumulating in
    float32, casts its partial sum to the compute dtype, and one
    all-reduce in the named scope ``scope`` adds the partial sums.  (The
    TPU compiler turns an all-reduce of float32 partial sums followed by
    the cast into this same compute-dtype all-reduce, which
    ``tests/test_tpu_compile.py`` checks for v5e; casting first says so,
    and halves the bytes on backends that would keep float32.)
    Anywhere else it is ``dense``."""
    ctx = current()
    if ctx is None or not ctx.serving:
        return dense(p, x)
    with jax.named_scope(scope):
        x = shard_act(x, (None,) * (x.ndim - 1) + (logical,))
        return dense(p, x, reduce=repl_act)


def last_valid_hidden(h, valid_len):
    """Gather the hidden state of the last *real* token per row.

    ``h`` is (B, S, D); ``valid_len`` is None (take index S-1), a traced
    scalar (all rows share one valid length — single-request bucketed
    prefill), or a (B,) vector of per-row valid lengths (batched burst
    prefill, where co-batched requests have different tail lengths).
    Rows with ``valid_len == 0`` (burst padding) clamp to index 0; their
    output is junk the caller must ignore.  Returns (B, 1, D)."""
    if valid_len is None:
        return h[:, -1:]
    idx = jnp.maximum(jnp.asarray(valid_len, jnp.int32) - 1, 0)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (h.shape[0],))
    return jnp.take_along_axis(h, idx[:, None, None], axis=1)


def page_write_indices(block_tables, ctx_len, tail_valid, T, page_size):
    """(page, row) scatter indices for writing T tail positions into a
    paged KV pool.

    Position ``t`` of row ``b`` lands at global sequence position
    ``ctx_len[b] + t``, i.e. page ``block_tables[b, g // page_size]``,
    row ``g % page_size``.  Positions at or past ``tail_valid`` (bucket
    right-padding) are redirected to the reserved garbage page 0 so pad
    junk can never overwrite a live page.  Returns two (B, T) int32
    arrays (page_idx, row_idx)."""
    gpos = ctx_len[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    in_tail = jnp.arange(T)[None, :] < tail_valid[:, None]
    pg = jnp.take_along_axis(block_tables, gpos // page_size, axis=1)
    pg = jnp.where(in_tail, pg, 0)
    rw = jnp.where(in_tail, gpos % page_size, 0)
    return pg.astype(jnp.int32), rw.astype(jnp.int32)


# --- norms -------------------------------------------------------------------
def norm_init(d: int, kind: str, dtype):
    if kind == "rms":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def norm_apply(p, x, kind: str, eps: float = 1e-5):
    with precision_island("norm"):
        xf = x.astype(jnp.float32)
        if kind == "rms":
            y = xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
            )
        else:
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32)
        if "bias" in p:
            y = y + p["bias"].astype(jnp.float32)
        return y.astype(x.dtype)


# --- rotary positions ----------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S)."""
    with precision_island("rope"):
        hd = x.shape[-1]
        inv = rope_freqs(hd, theta)                               # (hd/2,)
        ang = positions[..., None].astype(jnp.float32) * inv      # (..., S, hd/2)
        cos = jnp.cos(ang)[..., None, :]                          # (..., S, 1, hd/2)
        sin = jnp.sin(ang)[..., None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
        )
        return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray,
    positions: jnp.ndarray,   # (3, ..., S) — temporal / height / width ids
    sections,                 # e.g. (16, 24, 24); sums to head_dim // 2
    theta: float,
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE: the rotary half-dims are partitioned into
    3 sections, each rotated by its own position stream."""
    with precision_island("rope"):
        hd = x.shape[-1]
        assert sum(sections) == hd // 2, (sections, hd)
        inv = rope_freqs(hd, theta)                               # (hd/2,)
        # Section id per rotary channel.
        sec_id = jnp.repeat(
            jnp.arange(3), jnp.asarray(sections), total_repeat_length=hd // 2
        )
        # positions: (3, ..., S) -> per-channel positions (..., S, hd/2)
        pos = jnp.moveaxis(positions, 0, -1)                      # (..., S, 3)
        pos_c = jnp.take_along_axis(
            pos.astype(jnp.float32),
            jnp.broadcast_to(
                sec_id, pos.shape[:-1] + (hd // 2,)
            ).astype(jnp.int32),
            axis=-1,
        )                                                         # (..., S, hd/2)
        ang = pos_c * inv
        cos = jnp.cos(ang)[..., None, :]
        sin = jnp.sin(ang)[..., None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
        )
        return out.astype(x.dtype)


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """MusicGen-style fixed sinusoidal embeddings; positions (..., S)."""
    half = d_model // 2
    freq = jnp.exp(
        -math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half
    )
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --- feed-forward ---------------------------------------------------------------
def ffn_init(key, d_model: int, d_ff: int, act: str, dtype, bias: bool = False):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype, bias)}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[1], d_model, d_ff, dtype, bias)
    p["w_down"] = dense_init(ks[2], d_ff, d_model, dtype, bias)
    return p


def ffn_apply(p, x, act: str):
    up = dense(p["w_up"], x)
    if act == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x)) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:  # pragma: no cover
        raise ValueError(act)
    h = shard_act(h, ("batch", None, "ff"))
    return dense_row_parallel(p["w_down"], h, "ff", "repro.tp.ffn_down")


def softmax_xent_chunked(
    logits_fn,
    h: jnp.ndarray,              # (B, S, D) final hidden states
    targets: jnp.ndarray,        # (B, S) int32
    mask: Optional[jnp.ndarray],
    chunk: int = 0,
):
    """Cross-entropy over a (possibly huge) vocab without materializing the
    full (B, S, V) logits: scan over sequence chunks.  ``logits_fn`` maps
    (B, C, D) -> (B, C, V)."""
    B, S, D = h.shape
    if chunk <= 0 or S % chunk != 0 or S == chunk:
        logits = logits_fn(h)
        return _xent(logits, targets, mask)

    n = S // chunk
    hs = h.reshape(B, n, chunk, D).swapaxes(0, 1)          # (n, B, C, D)
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)
    ms = None if mask is None else mask.reshape(B, n, chunk).swapaxes(0, 1)

    def body(carry, xs):
        tot, cnt = carry
        if ms is None:
            hc, tc = xs
            mc = None
        else:
            hc, tc, mc = xs
        loss, weight = _xent(logits_fn(hc), tc, mc, reduce=False)
        return (tot + loss, cnt + weight), None

    xs = (hs, ts) if ms is None else (hs, ts, ms)
    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), xs)
    return tot / jnp.maximum(cnt, 1.0)


def _xent(logits, targets, mask, reduce: bool = True):
    with precision_island("xent"):
        # Pin the (..., V) logits (and, through the transpose rule of
        # with_sharding_constraint, their cotangent) to the vocab-sharded
        # layout the unembedding produces.  Without the annotation the SPMD
        # partitioner has to invent a sharding for the logits cotangent
        # inside the transposed loss-chunk scan and falls back to an
        # "involuntary full rematerialization" copy of the full (B, C, V)
        # tensor on the 2x16x16 production mesh.
        logits = shard_act(
            logits.astype(jnp.float32),
            ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",),
        )
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        nll = logz - gold
        if mask is None:
            mask = jnp.ones_like(nll)
        mask = mask.astype(jnp.float32)
        tot = jnp.sum(nll * mask)
        cnt = jnp.sum(mask)
        if reduce:
            return tot / jnp.maximum(cnt, 1.0)
        return tot, cnt
