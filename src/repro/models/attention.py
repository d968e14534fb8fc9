"""Attention mixers: chunked-flash GQA (online softmax, O(chunk^2) memory)
and DeepSeek-style MLA (low-rank Q/KV, absorbed decode).

Layouts: activations are (B, S, H, hd); caches are (B, S_max, Hk, hd)
(GQA) or (B, S_max, r_kv)/(B, S_max, d_rope) (MLA compressed cache —
the whole point of MLA).

The paged serving paths (``*_apply_decode_paged`` / ``*_apply_prefix``)
route their attention core through ``repro.kernels.ops`` behind the
``AttnBackend`` enum (``cfg.attn_backend``): the fused Pallas
paged-attention kernels on TPU, this module's gather+attend reference
elsewhere — bitwise identical by construction (single-normalization
softmax on both sides; asserted in tests/test_paged_attention.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard_act
from repro.kernels import ops as kops
from . import common
from .common import apply_mrope, apply_rope, dense, dense_init

NEG_INF = -1e30


# =============================== chunked flash ===============================
@common.in_island("attn")
def flash_attention(
    q: jnp.ndarray,            # (B, Sq, H, hd)
    k: jnp.ndarray,            # (B, Sk, Hk, hd)
    v: jnp.ndarray,            # (B, Sk, Hk, hdv)
    q_offset=0,                # global position of q[0] (causal masking)
    kv_valid: Optional[jnp.ndarray] = None,   # number of valid kv positions
    causal: bool = True,
    q_chunk: int = 512,
    kv_chunk: int = 512,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    _, Sk, Hk, hdv = v.shape
    G = H // Hk
    scale = 1.0 / math.sqrt(q.shape[-1])

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # Pad both sequence dims up to chunk multiples; padded KV is masked
    # out via kv_valid, padded Q rows are sliced off the output.
    Sq_p = -(-Sq // q_chunk) * q_chunk
    Sk_p = -(-Sk // kv_chunk) * kv_chunk
    if Sk_p != Sk:
        kv_valid = jnp.minimum(
            Sk if kv_valid is None else kv_valid, Sk
        )
        k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    nq, nk = Sq_p // q_chunk, Sk_p // kv_chunk

    qg = (q * scale).reshape(B, nq, q_chunk, Hk, G, hd).swapaxes(0, 1)
    kg = k.reshape(B, nk, kv_chunk, Hk, hd).swapaxes(0, 1)
    vg = v.reshape(B, nk, kv_chunk, Hk, hdv).swapaxes(0, 1)

    kpos_base = jnp.arange(kv_chunk)
    qpos_base = jnp.arange(q_chunk)

    def attend_q_chunk(qi, qc, kg_use, vg_use):
        """One q chunk against kv chunks [0, kg_use.shape[0])."""
        qpos = q_offset + qi * q_chunk + qpos_base      # (qc,)

        def kv_body(carry, kx):
            m, l, o = carry
            kj, kc, vc = kx
            s = jnp.einsum(
                "bqhgd,bchd->bhgqc", qc, kc,
                preferred_element_type=jnp.float32,
            )                                           # (B, Hk, G, qc, kc)
            kpos = kj * kv_chunk + kpos_base
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if kv_valid is not None:
                mask &= kpos[None, :] < kv_valid
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # NB: fully-masked rows have s == m_new == NEG_INF; the explicit
            # re-mask keeps exp(0) == 1 from leaking into l/o.
            p = jnp.where(
                mask[None, None, None], jnp.exp(s - m_new[..., None]), 0.0
            )
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhgqc,bchd->bqhgd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            o_new = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hk, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, q_chunk, Hk, G, hdv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_body, (m0, l0, o0),
            (jnp.arange(kg_use.shape[0]), kg_use, vg_use),
        )
        denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        # Cast to the output dtype *inside* the q-chunk body: the scan then
        # stacks bf16 chunks instead of f32 + a full-stack convert after
        # (2x stacked-buffer traffic, §Perf iteration I4).
        return (o / denom).astype(v.dtype)

    if causal and 1 < nq <= 16 and isinstance(q_offset, int) and q_offset == 0:
        # Causal chunk skipping (§Perf I7): q chunk qi only attends kv
        # chunks 0..qi.  Unrolling the q loop lets each inner scan stop at
        # the diagonal — ~2x less attention compute/traffic than masking
        # all nk chunks.  Only worth the HLO-size cost for small nq.
        outs = []
        for qi in range(nq):
            # last q position in this chunk is (qi+1)*q_chunk - 1; it may
            # attend kv positions <= itself -> chunks [0, ceil(.../kc)).
            k_hi = min(-(-((qi + 1) * q_chunk) // kv_chunk), nk)
            k_hi = max(k_hi, 1)
            outs.append(attend_q_chunk(qi, qg[qi], kg[:k_hi], vg[:k_hi]))
        out = jnp.stack(outs, 0).swapaxes(0, 1).reshape(B, Sq_p, H, hdv)
        return out[:, :Sq]

    def q_body(_, qx):
        qi, qc = qx
        return None, attend_q_chunk(qi, qc, kg, vg)

    _, out = jax.lax.scan(q_body, None, (jnp.arange(nq), qg))
    out = out.swapaxes(0, 1).reshape(B, Sq_p, H, hdv)
    return out[:, :Sq]


@common.in_island("attn")
def decode_attention(
    q: jnp.ndarray,            # (B, 1, H, hd)
    k_cache: jnp.ndarray,      # (B, S_max, Hk, hd)
    v_cache: jnp.ndarray,      # (B, S_max, Hk, hdv)
    pos,                       # current length (q is at index pos):
                               # scalar, or (B,) per-row positions
) -> jnp.ndarray:
    B, _, H, hd = q.shape
    _, S, Hk, hdv = v_cache.shape
    G = H // Hk
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Hk, G, hd)
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32
    )
    if jnp.ndim(pos) == 1:
        mask = jnp.arange(S)[None, :] <= pos[:, None]       # (B, S)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    else:
        mask = jnp.arange(S) <= pos
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, 1, H, hdv)


@common.in_island("attn")
def prefix_attention(
    q: jnp.ndarray,            # (B, T, H, hd) tail queries
    k_all: jnp.ndarray,        # (B, L + T, Hk, hd)  [ctx pages ; tail]
    v_all: jnp.ndarray,        # (B, L + T, Hk, hdv)
    ctx_len: jnp.ndarray,      # (B,) valid context positions (0 disables ctx)
    L: int,                    # static context capacity (ctx rows in k_all)
) -> jnp.ndarray:
    """Attention for tail-only prefill over a reused prefix.

    Keys are the concatenation of a gathered page context (rows
    ``[0, L)``, valid where ``j < ctx_len[b]``) and the tail's own K/V
    (rows ``[L, L+T)``, causal within the tail).  Every query attends at
    least its own tail position, so the softmax is never fully masked
    even for ``ctx_len == 0`` rows (burst members without a prefix hit)
    or right-padded tail rows.  One plain masked softmax — prefill runs
    once per request, so O(T * (L+T)) score memory is acceptable where
    the chunked-flash path would need an lse-merge."""
    B, T, H, hd = q.shape
    Hk = k_all.shape[2]
    G = H // Hk
    hdv = v_all.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    # Head-major, with each KV head's (query, group) pairs as the rows
    # of one matrix: the layout the fused Pallas kernel computes in.
    qg = (q * scale).reshape(B, T, Hk, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, Hk, T * G, hd)
    s = jnp.einsum(
        "bhmd,bhsd->bhms", qg, k_all.transpose(0, 2, 1, 3),
        preferred_element_type=jnp.float32,
    )                                                   # (B, Hk, T*G, L+T)
    mask_ctx = jnp.arange(L)[None, :] < ctx_len[:, None]          # (B, L)
    mask_tail = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]  # (T, T)
    mask = jnp.concatenate(
        [
            jnp.broadcast_to(mask_ctx[:, None, :], (B, T, L)),
            jnp.broadcast_to(mask_tail[None], (B, T, T)),
        ],
        axis=-1,
    )                                                   # (B, T, L+T)
    mask = jnp.repeat(mask, G, axis=1)[:, None]         # (B, 1, T*G, L+T)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhms,bhsd->bhmd", p.astype(v_all.dtype), v_all.transpose(0, 2, 1, 3),
        preferred_element_type=jnp.float32,
    )                                                   # (B, Hk, T*G, hdv)
    o = o.reshape(B, Hk, T, G, hdv).transpose(0, 2, 1, 3, 4)
    return o.reshape(B, T, H, hdv)


def _gather_pages(pages: jnp.ndarray, block_table: jnp.ndarray) -> jnp.ndarray:
    """(n_pages, page, ...) pages + (B, nb) block table -> contiguous
    per-slot views (B, nb * page, ...)."""
    g = pages[block_table]                      # (B, nb, page, ...)
    return g.reshape(g.shape[0], -1, *g.shape[3:])


def _out_proj(p, o):
    """The output projection ``wo`` of the (B, S, heads * hd) attention
    output, row-parallel over the heads under a serving mesh."""
    return common.dense_row_parallel(p["wo"], o, "heads", "repro.tp.attn_out")


# ================================= GQA =======================================
def gqa_init(key, cfg, dtype):
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype, cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv * hd, dtype, cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv * hd, dtype, cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype, False),
    }


def _positions(cfg, B, S, offset, position_ids):
    if position_ids is not None:
        return position_ids
    pos = jnp.arange(S)[None, :] + offset
    if cfg.pos == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, S))
    return jnp.broadcast_to(pos, (B, S))


def gqa_qkv(p, x, cfg, offset=0, position_ids=None):
    B, S, _ = x.shape
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = dense(p["wk"], x).reshape(B, S, cfg.n_kv, hd)
    v = dense(p["wv"], x).reshape(B, S, cfg.n_kv, hd)
    pos = _positions(cfg, B, S, offset, position_ids)
    if cfg.pos == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    elif cfg.pos == "mrope":
        q = apply_mrope(q, pos, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos, cfg.mrope_sections, cfg.rope_theta)
    q = shard_act(q, ("batch", None, "heads", None))
    k = shard_act(k, ("batch", None, "kv_heads", None))
    v = shard_act(v, ("batch", None, "kv_heads", None))
    return q, k, v


def gqa_apply_train(p, x, cfg, position_ids=None):
    q, k, v = gqa_qkv(p, x, cfg, 0, position_ids)
    o = flash_attention(
        q, k, v, causal=True, q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv
    )
    B, S = x.shape[:2]
    o = shard_act(o, ("batch", None, "heads", None))
    return _out_proj(p, o.reshape(B, S, -1).astype(x.dtype)), (k, v)


def gqa_apply_decode(p, x, cfg, cache, pos, position_ids=None):
    """cache: dict(k=(B, S_max, Hk, hd), v=...); x: (B, 1, D).
    ``pos`` is a scalar (all rows at the same length) or a (B,) vector of
    per-row lengths (slot-based serving: each slot decodes at its own
    position)."""
    B = x.shape[0]
    per_row = jnp.ndim(pos) == 1
    off = pos[:, None] if per_row else pos
    q, k, v = gqa_qkv(p, x, cfg, off, position_ids)
    if per_row:
        rows = jnp.arange(B)
        k_cache = cache["k"].at[rows, pos].set(k[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[rows, pos].set(v[:, 0].astype(cache["v"].dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
    o = decode_attention(q, k_cache, v_cache, pos)
    y = _out_proj(p, o.reshape(B, 1, -1).astype(x.dtype))
    return y, {"k": k_cache, "v": v_cache}


def gqa_apply_decode_paged(p, x, cfg, cache, block_table, pos):
    """Slot-decode through a paged KV pool: cache k/v are
    (n_pages, page, Hk, hd) shared pages, ``block_table`` is the (B, nb)
    per-slot page list, ``pos`` the (B,) per-row lengths.  The new K/V
    scatters into page ``bt[b, pos // page]`` row ``pos % page`` (always
    a page the slot owns alone — shared prefix pages are fully covered
    by the prompt and decode writes start at the prompt end), then the
    attention runs through ``kops.paged_decode_gqa``: on the XLA backend
    the slot's pages gather into a contiguous (B, nb * page, ...) view
    for the same masked ``decode_attention`` the monolithic path runs;
    on the Pallas backend the fused kernel reads the pages through the
    block table in VMEM (bitwise identical)."""
    B = x.shape[0]
    q, k, v = gqa_qkv(p, x, cfg, pos[:, None])
    page = cache["k"].shape[1]
    pg = jnp.take_along_axis(block_table, (pos // page)[:, None], axis=1)[:, 0]
    rw = pos % page
    k_pages = cache["k"].at[pg, rw].set(k[:, 0].astype(cache["k"].dtype))
    v_pages = cache["v"].at[pg, rw].set(v[:, 0].astype(cache["v"].dtype))
    o = kops.paged_decode_gqa(
        q, k_pages, v_pages, block_table, pos, backend=cfg.attn_backend
    )
    y = _out_proj(p, o.reshape(B, 1, -1).astype(x.dtype))
    return y, {"k": k_pages, "v": v_pages}


def gqa_apply_prefix(p, x, cfg, cache, block_table, ctx_len, wr_pg, wr_rw,
                     use_context: bool = True):
    """Paged (burst) prefill of tail tokens over an optional reused
    prefix: queries sit at absolute positions ``ctx_len[b] + t`` (RoPE),
    attend the gathered context pages (valid where ``j < ctx_len``) plus
    the tail causally, and the tail K/V scatters into the slot's pages
    at ``(wr_pg, wr_rw)`` (right-pad writes land in the garbage page).
    The context is read from the *pre-write* pool — a request never
    shares a page with a burst member whose fill is still pending (the
    scheduler splits such bursts), so the gather sees only pages filled
    by earlier programs.

    ``use_context=False`` (static) compiles the prefix machinery out:
    when the scheduler's prefix reuse is gated off, ``ctx_len`` is
    always 0 and gathering max_len always-masked context keys per layer
    would be pure waste."""
    B, T, _ = x.shape
    q, k, v = gqa_qkv(p, x, cfg, ctx_len[:, None])
    if use_context:
        with common.precision_island("attn"):
            k_ctx = _gather_pages(cache["k"], block_table).astype(k.dtype)
            v_ctx = _gather_pages(cache["v"], block_table).astype(v.dtype)
    else:
        k_ctx = v_ctx = None
    o = kops.prefix_prefill(
        q, k_ctx, v_ctx, k, v, ctx_len, backend=cfg.attn_backend
    )
    k_pages = cache["k"].at[wr_pg, wr_rw].set(k.astype(cache["k"].dtype))
    v_pages = cache["v"].at[wr_pg, wr_rw].set(v.astype(cache["v"].dtype))
    y = _out_proj(p, o.reshape(B, T, -1).astype(x.dtype))
    return y, {"k": k_pages, "v": v_pages}


# ================================= MLA =======================================
@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


def mla_init(key, cfg, dtype):
    m: MLAConfig = cfg.mla
    H = cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "q_a": dense_init(ks[0], cfg.d_model, m.q_lora_rank, dtype),
        "q_norm": common.norm_init(m.q_lora_rank, "rms", dtype),
        "q_b": dense_init(
            ks[1], m.q_lora_rank, H * (m.qk_nope_dim + m.qk_rope_dim), dtype
        ),
        "kv_a": dense_init(
            ks[2], cfg.d_model, m.kv_lora_rank + m.qk_rope_dim, dtype
        ),
        "kv_norm": common.norm_init(m.kv_lora_rank, "rms", dtype),
        "kv_b": dense_init(
            ks[3], m.kv_lora_rank, H * (m.qk_nope_dim + m.v_head_dim), dtype
        ),
        "wo": dense_init(ks[4], H * m.v_head_dim, cfg.d_model, dtype),
    }


def _mla_q(p, x, cfg, offset):
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cq = common.norm_apply(p["q_norm"], dense(p["q_a"], x), "rms")
    q = dense(p["q_b"], cq).reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    pos = jnp.arange(S)[None, :] + offset
    q_rope = apply_rope(q_rope, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, offset):
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    a = dense(p["kv_a"], x)
    c_kv, k_rope = jnp.split(a, [m.kv_lora_rank], axis=-1)
    c_kv = common.norm_apply(p["kv_norm"], c_kv, "rms")
    pos = jnp.arange(S)[None, :] + offset
    k_rope = apply_rope(
        k_rope[:, :, None, :], jnp.broadcast_to(pos, (B, S)), cfg.rope_theta
    )[:, :, 0]
    return c_kv, k_rope


def mla_apply_train(p, x, cfg, position_ids=None):
    """Prefill/train MLA: reconstruct per-head K/V from the compressed
    cache, chunked-flash attention over (nope+rope) keys."""
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, cfg, 0)
    c_kv, k_rope = _mla_ckv(p, x, cfg, 0)

    kvb = dense(p["kv_b"], c_kv).reshape(B, S, H, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_dim))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = shard_act(q, ("batch", None, "heads", None))
    k = shard_act(k, ("batch", None, "heads", None))
    v = shard_act(v, ("batch", None, "heads", None))
    o = flash_attention(
        q, k, v, causal=True, q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv
    )
    y = _out_proj(p, o.reshape(B, S, -1).astype(x.dtype))
    return y, (c_kv, k_rope)


def _mla_absorb_weights(p, cfg):
    m: MLAConfig = cfg.mla
    H = cfg.n_heads
    w_kv_b = p["kv_b"]["w"].reshape(m.kv_lora_rank, H, m.qk_nope_dim + m.v_head_dim)
    return w_kv_b[:, :, : m.qk_nope_dim], w_kv_b[:, :, m.qk_nope_dim:]


@common.in_island("attn")
def _mla_absorb_q(p, cfg, q_nope):
    """Absorb ``w_uk`` into the nope queries: returns the (B, q, H, r)
    f32 absorbed queries, the post-sum score scale, and ``w_uv`` for the
    caller's up-projection."""
    m: MLAConfig = cfg.mla
    w_uk, w_uv = _mla_absorb_weights(p, cfg)
    q_abs = jnp.einsum(
        "bqhd,rhd->bqhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)
    )
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    return q_abs, scale, w_uv


@common.in_island("attn")
def mla_attend_core(q_abs, q_rope, ckv, krope, pos, scale):
    """The absorbed-MLA masked attend over contiguous cache views:
    scores and context computed in the compressed c_kv space.  ``pos``
    is a scalar or a (B,) vector; rows past ``pos`` are masked.  Returns
    the (B, q, H, r) f32 context — ``w_uv`` stays with the caller.  This
    is the XLA reference the fused ``paged_decode_mla_pallas`` kernel is
    bitwise-checked against (and the shared core of the monolithic and
    paged decode paths, so the two can never diverge numerically)."""
    s = (
        jnp.einsum("bqhr,bsr->bhqs", q_abs, ckv.astype(jnp.float32))
        + jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32), krope.astype(jnp.float32))
    ) * scale                                          # (B,H,1,S)
    S_max = ckv.shape[1]
    if jnp.ndim(pos) == 1:
        mask = jnp.arange(S_max)[None, :] <= pos[:, None]   # (B, S)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    else:
        mask = jnp.arange(S_max) <= pos
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bsr->bqhr", pattn, ckv.astype(jnp.float32))


@common.in_island("attn")
def _mla_absorbed_attend(p, cfg, q_nope, q_rope, ckv, krope, pos):
    """One absorbed-MLA decode attention against a contiguous
    (B, S, r_kv)/(B, S, d_rope) cache view: absorb, attend, up-project."""
    q_abs, scale, w_uv = _mla_absorb_q(p, cfg, q_nope)
    ctx = mla_attend_core(q_abs, q_rope, ckv, krope, pos, scale)
    return jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv.astype(jnp.float32))


def mla_apply_decode(p, x, cfg, cache, pos):
    """Absorbed MLA decode: scores/context computed in the compressed
    c_kv space — the cache stays (B, S, r_kv) + (B, S, d_rope).  ``pos``
    is a scalar or a (B,) vector of per-row lengths (slotted serving)."""
    B = x.shape[0]
    per_row = jnp.ndim(pos) == 1
    off = pos[:, None] if per_row else pos
    q_nope, q_rope = _mla_q(p, x, cfg, off)           # (B,1,H,dn),(B,1,H,dr)
    c_new, kr_new = _mla_ckv(p, x, cfg, off)          # (B,1,rkv),(B,1,dr)
    if per_row:
        rows = jnp.arange(B)
        ckv = cache["c_kv"].at[rows, pos].set(c_new[:, 0].astype(cache["c_kv"].dtype))
        krope = cache["k_rope"].at[rows, pos].set(kr_new[:, 0].astype(cache["k_rope"].dtype))
    else:
        ckv = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, pos, 0)
        )
        krope = jax.lax.dynamic_update_slice(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, pos, 0)
        )
    o = _mla_absorbed_attend(p, cfg, q_nope, q_rope, ckv, krope, pos)
    y = _out_proj(p, o.reshape(B, 1, -1).astype(x.dtype))
    return y, {"c_kv": ckv, "k_rope": krope}


def mla_apply_decode_paged(p, x, cfg, cache, block_table, pos):
    """Absorbed MLA decode through a paged compressed cache: pages are
    (n_pages, page, r_kv)/(n_pages, page, d_rope); the new row scatters
    into the slot's page at ``pos``, then ``kops.paged_decode_mla`` runs
    ``mla_attend_core`` over the block table — via an XLA gather or the
    fused Pallas kernel, per ``cfg.attn_backend``."""
    B = x.shape[0]
    q_nope, q_rope = _mla_q(p, x, cfg, pos[:, None])
    c_new, kr_new = _mla_ckv(p, x, cfg, pos[:, None])
    page = cache["c_kv"].shape[1]
    pg = jnp.take_along_axis(block_table, (pos // page)[:, None], axis=1)[:, 0]
    rw = pos % page
    ckv_pages = cache["c_kv"].at[pg, rw].set(c_new[:, 0].astype(cache["c_kv"].dtype))
    kr_pages = cache["k_rope"].at[pg, rw].set(kr_new[:, 0].astype(cache["k_rope"].dtype))
    q_abs, scale, w_uv = _mla_absorb_q(p, cfg, q_nope)
    ctx = kops.paged_decode_mla(
        q_abs, q_rope, ckv_pages, kr_pages, block_table, pos, scale,
        backend=cfg.attn_backend,
    )
    with common.precision_island("attn"):
        o = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv.astype(jnp.float32))
    y = _out_proj(p, o.reshape(B, 1, -1).astype(x.dtype))
    return y, {"c_kv": ckv_pages, "k_rope": kr_pages}


def mla_apply_prefix(p, x, cfg, cache, block_table, ctx_len, wr_pg, wr_rw,
                     use_context: bool = True):
    """Paged (burst) MLA prefill over an optional reused prefix: per-head
    K/V are reconstructed from the compressed cache for BOTH the gathered
    context pages and the tail (exactly as ``mla_apply_train``
    reconstructs them for a full prompt), then one ``prefix_attention``
    runs the ctx+causal-tail mask.  The tail's compressed rows scatter
    into the slot's pages at ``(wr_pg, wr_rw)``.  ``use_context=False``
    (static) compiles the context gather out, as in
    ``gqa_apply_prefix``."""
    m: MLAConfig = cfg.mla
    B, T, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, cfg, ctx_len[:, None])
    c_kv, k_rope = _mla_ckv(p, x, cfg, ctx_len[:, None])

    if use_context:
        with common.precision_island("attn"):
            ckv_ctx = _gather_pages(
                cache["c_kv"], block_table
            ).astype(c_kv.dtype)
            kr_ctx = _gather_pages(
                cache["k_rope"], block_table
            ).astype(k_rope.dtype)
        L = ckv_ctx.shape[1]
        c_all = jnp.concatenate([ckv_ctx, c_kv], axis=1)     # (B, L+T, rkv)
        kr_all = jnp.concatenate([kr_ctx, k_rope], axis=1)   # (B, L+T, dr)
    else:
        c_all, kr_all, L = c_kv, k_rope, 0

    kvb = dense(p["kv_b"], c_all).reshape(B, L + T, H, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :], (B, L + T, H, m.qk_rope_dim))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # Hand ctx/tail slices to the dispatcher: the XLA backend re-concats
    # them (bitwise a no-op), the Pallas backend attends them fused.
    o = kops.prefix_prefill(
        q,
        k[:, :L] if L else None,
        v[:, :L] if L else None,
        k[:, L:], v[:, L:], ctx_len,
        backend=cfg.attn_backend,
    )

    ckv_pages = cache["c_kv"].at[wr_pg, wr_rw].set(c_kv.astype(cache["c_kv"].dtype))
    kr_pages = cache["k_rope"].at[wr_pg, wr_rw].set(k_rope.astype(cache["k_rope"].dtype))
    y = _out_proj(p, o.reshape(B, T, -1).astype(x.dtype))
    return y, {"c_kv": ckv_pages, "k_rope": kr_pages}
