"""Fused paged-attention Pallas kernels (vLLM-style).

Decode: one kernel reads K/V pages THROUGH the block table — the
``(B, nb)`` page list, the ``(B,)`` position vector and the layer index
are scalar-prefetched (``PrefetchScalarGridSpec``) so the BlockSpec
index maps can steer each grid step's DMA at the page the slot actually
owns, in the layer's slab of the stacked ``(groups, n_pages, ...)``
pool: the layer scan carries the whole pool and never copies a slab
out for the kernel.  Pages stream into a VMEM scratch gather buffer; at
the slot's last page the kernel runs the masked attend over the full
gathered sequence.  The XLA
path this replaces (``attention._gather_pages`` + ``decode_attention``)
materializes a contiguous ``(B, nb * page, ...)`` HBM copy of every
slot's pages per layer per step; here the gather lives only in VMEM.

Prefill: one kernel attends ``[reused-context ; causal tail]`` without
ever materializing the concatenated K/V or the ``(B, Hk, G, T, L+T)``
score tensor in HBM — context and tail blocks are copied side by side
into a VMEM scratch (the "concat" is per-cell, on-chip) and scores live
per (batch row, q tile) in VMEM.

Bitwise parity: every kernel keeps the reference path's exact compute
structure — one masked single-normalization softmax over the full key
axis and single dot-generals for scores and PV (NOT a rescaling online-
softmax accumulation, which changes summation trees and breaks the
serving stack's token-exactness contracts).  Page-granular gathering is
safe because each score element is an independent dot over the head
dim; masking, softmax and the PV contraction run over the full gathered
axis exactly as ``attention.decode_attention`` / ``prefix_attention``
do.  Parity is asserted bitwise in tests/test_paged_attention.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ----------------------------- decode: GQA -----------------------------------
def _decode_gqa_kernel(bt_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref,
                       o_ref, k_s, v_s, *, page: int, nb: int):
    """Grid (B, nb), pages innermost.  Each step DMAs one page of K/V
    (selected by the block-table index maps) and scatters it, head by
    head, into the head-major (Hk, S, hd) gather scratch; the last page
    runs mask + softmax + PV over the full sequence, batched over the
    KV heads."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    Hk = k_s.shape[0]
    off = pl.multiple_of(i * page, page)
    for h in range(Hk):
        k_s[h, pl.ds(off, page), :] = k_ref[:, h, :]
        v_s[h, pl.ds(off, page), :] = v_ref[:, h, :]

    @pl.when(i == nb - 1)
    def _attend():
        S = nb * page
        s = jnp.einsum(
            "hgd,hsd->hgs", q_ref[...], k_s[...],
            preferred_element_type=jnp.float32,
        )
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, S), 2)
        s = jnp.where(iota <= pos_ref[b], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_ref[...] = jnp.einsum(
            "hgs,hsd->hgd", p.astype(v_s.dtype), v_s[...],
            preferred_element_type=jnp.float32,
        )


def paged_decode_gqa_pallas(q, k_pages, v_pages, block_table, pos, layer,
                            interpret: bool = False):
    """Fused paged GQA decode.

    q: (B, 1, H, hd); k_pages/v_pages: (groups, n_pages, page, Hk,
    hd[v]), the stacked pool; block_table: (B, nb) int32 page ids; pos:
    (B,) int32 per-row lengths; layer: int32 scalar, the slab of
    ``groups`` to read.  Returns (B, 1, H, hdv) f32 — bitwise identical
    to ``decode_attention(q, gather(k[layer]), gather(v[layer]), pos)``.
    Rows whose table points at the reserved garbage page 0 (inactive
    slots, pos clamped to 0) are handled by the mask exactly as in the
    reference.

    The gather scratch is head-major, (Hk, S, hd): with the head axis
    second-minor, as in the pages, a small Hk would be padded to a full
    sublane tile (2 -> 16 rows in bf16 for Hk = 2), eight times the
    memory.
    """
    B, _, H, hd = q.shape
    _, _, page, Hk, _ = k_pages.shape
    hdv = v_pages.shape[-1]
    G = H // Hk
    nb = block_table.shape[1]
    S = nb * page
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Hk, G, hd)

    def page_idx(b, i, bt, ps, ly):
        return (ly[0], bt[b, i], 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_gqa_kernel, page=page, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((None, Hk, G, hd), lambda b, i, bt, ps, ly: (b, 0, 0, 0)),
                pl.BlockSpec((None, None, page, Hk, hd), page_idx),
                pl.BlockSpec((None, None, page, Hk, hdv), page_idx),
            ],
            out_specs=pl.BlockSpec(
                (None, Hk, G, hdv), lambda b, i, bt, ps, ly: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hk, S, hd), k_pages.dtype),
                pltpu.VMEM((Hk, S, hdv), v_pages.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, hdv), jnp.float32),
        interpret=interpret,
    )(block_table, pos, jnp.reshape(layer, (1,)).astype(jnp.int32), qg,
      k_pages, v_pages)
    return out.reshape(B, 1, H, hdv)


# ----------------------------- decode: MLA -----------------------------------
def _decode_mla_kernel(bt_ref, pos_ref, layer_ref, qa_ref, qr_ref, c_ref,
                       r_ref, o_ref, c_s, r_s, *, page: int, nb: int,
                       scale: float):
    b = pl.program_id(0)
    i = pl.program_id(1)
    c_s[pl.ds(i * page, page)] = c_ref[0]
    r_s[pl.ds(i * page, page)] = r_ref[0]

    @pl.when(i == nb - 1)
    def _attend():
        S = nb * page
        ckv = c_s[...].astype(jnp.float32)
        krope = r_s[...].astype(jnp.float32)
        s = (
            jnp.einsum("hr,sr->hs", qa_ref[0], ckv,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("hd,sd->hs", qr_ref[0], krope,
                         preferred_element_type=jnp.float32)
        ) * scale
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        s = jnp.where(iota <= pos_ref[b], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_ref[0] = jnp.einsum(
            "hs,sr->hr", p, ckv, preferred_element_type=jnp.float32
        )


def paged_decode_mla_pallas(q_abs, q_rope, ckv_pages, krope_pages,
                            block_table, pos, layer, scale: float,
                            interpret: bool = False):
    """Fused paged absorbed-MLA decode, in the compressed c_kv space.

    q_abs: (B, 1, H, r) f32 absorbed queries; q_rope: (B, 1, H, dr);
    ckv_pages: (groups, n_pages, page, r); krope_pages: (groups,
    n_pages, page, dr); ``layer`` picks the slab, as in the GQA kernel.
    Returns the (B, 1, H, r) f32 context (the ``w_uv`` up-projection
    stays outside) — bitwise identical to ``mla_attend_core`` over the
    gathered per-slot views.  ``scale`` multiplies the SUMMED nope+rope
    scores, matching the reference's post-sum scaling."""
    B, _, H, r = q_abs.shape
    dr = q_rope.shape[-1]
    _, _, page, _ = ckv_pages.shape
    nb = block_table.shape[1]
    S = nb * page
    qa = q_abs.astype(jnp.float32).reshape(B, H, r)
    qr = q_rope.astype(jnp.float32).reshape(B, H, dr)

    def page_idx(b, i, bt, ps, ly):
        return (ly[0], bt[b, i], 0, 0)

    def row_idx(b, i, bt, ps, ly):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_mla_kernel, page=page, nb=nb, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((1, H, r), row_idx),
                pl.BlockSpec((1, H, dr), row_idx),
                pl.BlockSpec((None, 1, page, r), page_idx),
                pl.BlockSpec((None, 1, page, dr), page_idx),
            ],
            out_specs=pl.BlockSpec((1, H, r), row_idx),
            scratch_shapes=[
                pltpu.VMEM((S, r), ckv_pages.dtype),
                pltpu.VMEM((S, dr), krope_pages.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        interpret=interpret,
    )(block_table, pos, jnp.reshape(layer, (1,)).astype(jnp.int32), qa, qr,
      ckv_pages, krope_pages)
    return out.reshape(B, 1, H, r)


# ------------------------- prefill: [ctx ; causal tail] -----------------------
def _prefix_kernel(ctx_ref, q_ref, qpos_ref, *refs, L: int, T: int):
    """Grid (B, Tp // Tt), q tiles innermost.  At each row's first tile
    the context and tail K/V blocks are copied side by side into the
    head-major gather scratch (the on-chip "concat"); every tile then
    runs one masked softmax + PV over the full L+T axis, batched over
    the KV heads.  A tile's rows are its (query, group) pairs, query
    major; ``qpos_ref`` holds each row's query index."""
    if L:
        kc_ref, vc_ref, kt_ref, vt_ref, o_ref, k_s, v_s = refs
    else:
        kt_ref, vt_ref, o_ref, k_s, v_s = refs
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _load():
        if L:
            k_s[:, pl.ds(0, L), :] = kc_ref[...]
            v_s[:, pl.ds(0, L), :] = vc_ref[...]
        k_s[:, pl.ds(L, T), :] = kt_ref[...]
        v_s[:, pl.ds(L, T), :] = vt_ref[...]

    s = jnp.einsum(
        "hmd,hsd->hms", q_ref[...], k_s[...],
        preferred_element_type=jnp.float32,
    )                                               # (Hk, Tt*G, L+T)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, L + T), 2)
    row = qpos_ref[...][None]                       # (1, Tt*G, 1)
    mask = ((col < L) & (col < ctx_ref[b])) | ((col >= L) & (col - L <= row))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_ref[...] = jnp.einsum(
        "hms,hsd->hmd", p.astype(v_s.dtype), v_s[...],
        preferred_element_type=jnp.float32,
    )


# Bytes of f32 scores one prefill grid cell may hold, (Hk, Tt*G, L+T):
# with the softmax's temporaries beside them this is most of what the
# context blocks and the gather scratch leave of the 16 MiB of VMEM a
# v5e kernel may use by default.
SCORE_TILE_BYTES = 2 * 1024 * 1024


def _tail_tile(T: int, row_bytes: int) -> int:
    """Queries per prefill tile: all of T when its scores fit
    ``SCORE_TILE_BYTES``, else the largest power of two (at least 8)
    that does."""
    if T * row_bytes <= SCORE_TILE_BYTES:
        return T
    Tt = 8
    while 2 * Tt * row_bytes <= SCORE_TILE_BYTES and 2 * Tt < T:
        Tt *= 2
    return Tt


def prefix_prefill_pallas(q, k_ctx, v_ctx, k_tail, v_tail, ctx_len,
                          tail_block: int | None = None,
                          interpret: bool = False):
    """Fused [reused-context ; causal-tail] prefill attention.

    q: (B, T, H, hd) tail queries at absolute positions ctx_len + t;
    k_ctx/v_ctx: (B, L, Hk, hd[v]) gathered context pages (None when
    the scheduler compiles the prefix machinery out — L == 0);
    k_tail/v_tail: (B, T, Hk, hd[v]); ctx_len: (B,) int32 valid context
    lengths.  Returns (B, T, H, hdv) f32 — the same masked softmax as
    ``prefix_attention(q, concat([k_ctx, k_tail]), ..., ctx_len, L)``
    without materializing the concat or the (B, Hk, G, T, L+T) score
    tensor in HBM.  T is tiled by ``tail_block`` queries (default: as
    many as ``_tail_tile`` lets fit; softmax rows are per-query, so
    tiling cannot change any output bit); q is zero-padded up to the
    tile multiple and the pad rows sliced off.

    Operands enter head-major — q as (B, Hk, Tp*G, hd), K/V as
    (B, Hk, S, hd) — for the reason given in the decode kernel: the
    context is an XLA gather of the slot's pages either way, and the
    transpose rides along with it."""
    B, T, H, hd = q.shape
    Hk = k_tail.shape[2]
    hdv = v_tail.shape[-1]
    G = H // Hk
    L = 0 if k_ctx is None else k_ctx.shape[1]
    scale = 1.0 / math.sqrt(hd)
    Tt = min(tail_block or _tail_tile(T, Hk * G * (L + T) * 4), T)
    Tp = -(-T // Tt) * Tt
    qg = (q * scale).reshape(B, T, Hk, G, hd)
    if Tp != T:
        qg = jnp.pad(qg, ((0, 0), (0, Tp - T), (0, 0), (0, 0), (0, 0)))
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(B, Hk, Tp * G, hd)
    qpos = jnp.repeat(jnp.arange(Tp, dtype=jnp.int32), G)[:, None]

    def head_major(x):                      # (B, S, Hk, d) -> (B, Hk, S, d)
        return x.transpose(0, 2, 1, 3)

    def _idx_q(b, t, ctx):
        return (b, 0, t, 0)

    def _idx_kv(b, t, ctx):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((None, Hk, Tt * G, hd), _idx_q),
        pl.BlockSpec((Tt * G, 1), lambda b, t, ctx: (t, 0)),
    ]
    operands = [qg, qpos]
    if L:
        in_specs += [
            pl.BlockSpec((None, Hk, L, hd), _idx_kv),
            pl.BlockSpec((None, Hk, L, hdv), _idx_kv),
        ]
        operands += [head_major(k_ctx), head_major(v_ctx)]
    in_specs += [
        pl.BlockSpec((None, Hk, T, hd), _idx_kv),
        pl.BlockSpec((None, Hk, T, hdv), _idx_kv),
    ]
    operands += [head_major(k_tail), head_major(v_tail)]

    out = pl.pallas_call(
        functools.partial(_prefix_kernel, L=L, T=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Tp // Tt),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, Hk, Tt * G, hdv), _idx_q),
            scratch_shapes=[
                pltpu.VMEM((Hk, L + T, hd), k_tail.dtype),
                pltpu.VMEM((Hk, L + T, hdv), v_tail.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, Tp * G, hdv), jnp.float32),
        interpret=interpret,
    )(ctx_len, *operands)
    out = out.reshape(B, Hk, Tp, G, hdv).transpose(0, 2, 1, 3, 4)
    return out[:, :T].reshape(B, T, H, hdv)
