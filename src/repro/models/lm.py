"""LM assembly: Embed -> scan(blocks) -> Norm -> Head, with train / prefill
/ decode entry points for every assigned architecture.

Serving caches come in two layouts: monolithic per-slot regions
(``init_cache`` / ``prefill`` / ``decode_step`` / ``insert_cache_slot``)
and the paged pool (``init_paged_pool`` / ``prefill_paged`` /
``decode_step_paged``) where attention K/V lives in shared refcounted
pages addressed through per-slot block tables — see docs/serving.md.
Either pool is allocated ONCE per ``serve.ServeSession`` and reused
across traces (every compiled program donates and rebinds it);
``pool_nbytes`` reports the persistent footprint.

Layers are scanned in groups of ``cfg.scan_period()`` (1 for uniform
stacks; 8 for Jamba's 1-attn:7-mamba interleave) so the HLO stays small
at 61-80 layers.  Activation remat wraps each scanned group.  Sequence
parallelism is annotated on the residual stream between blocks
(``shard_act(x, ("batch", "seq_sp", None))``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import repl_act, shard_act, take_rows
from . import attention as attn
from . import common, mamba as ssm, moe as moe_mod
from .common import (
    dense,
    dense_init,
    last_valid_hidden,
    norm_apply,
    norm_init,
    page_write_indices,
)
from .config import LMConfig


# ------------------------------- init ---------------------------------------
def _init_mixer(key, cfg: LMConfig, kind: str):
    if kind == "gqa":
        return attn.gqa_init(key, cfg, cfg.pdtype)
    if kind == "mla":
        return attn.mla_init(key, cfg, cfg.pdtype)
    return ssm.mamba_init(key, cfg, cfg.pdtype)


def _init_ffn(key, cfg: LMConfig, kind: str):
    if kind == "none":
        return {}
    if kind == "moe":
        return moe_mod.moe_init(key, cfg, cfg.pdtype)
    return common.ffn_init(key, cfg.d_model, cfg.d_ff, cfg.act, cfg.pdtype)


def _init_block(key, cfg: LMConfig, mk: str, fk: str):
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
         "mixer": _init_mixer(k1, cfg, mk)}
    if fk != "none":
        p["ln2"] = norm_init(cfg.d_model, cfg.norm, cfg.pdtype)
        p["ffn"] = _init_ffn(k2, cfg, fk)
    return p


def init(key, cfg: LMConfig) -> Dict[str, Any]:
    cfg.validate()
    period = cfg.scan_period()
    groups = cfg.n_layers // period
    keys = jax.random.split(key, 4)

    blocks = []
    for pos in range(period):
        mk, fk = cfg.mixer_kind(pos), cfg.ffn_of(pos)
        per_group = [
            _init_block(
                jax.random.fold_in(keys[0], g * period + pos), cfg, mk, fk
            )
            for g in range(groups)
        ]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))

    params: Dict[str, Any] = {"blocks": blocks, "ln_f": norm_init(cfg.d_model, cfg.norm, cfg.pdtype)}
    if not cfg.external_embed:
        params["embed"] = {
            "w": (jax.random.normal(keys[1], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(cfg.pdtype)
        }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[2], cfg.d_model, cfg.vocab_size, cfg.pdtype)
    if cfg.mtp:
        params["mtp"] = {
            "proj": dense_init(keys[3], 2 * cfg.d_model, cfg.d_model, cfg.pdtype),
            "block": _init_block(
                jax.random.fold_in(keys[3], 1), cfg, cfg.mixer_kind(0), cfg.ffn_of(0)
            ),
            "ln": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
        }
    return params


# ------------------------------- blocks ---------------------------------------
def _block_train(bp, x, cfg: LMConfig, mk: str, fk: str, position_ids, training: bool = True, valid_len=None):
    h = norm_apply(bp["ln1"], x, cfg.norm)
    aux = jnp.float32(0.0)
    if mk == "gqa":
        y, kv = attn.gqa_apply_train(bp["mixer"], h, cfg, position_ids)
        cacheable = {"k": kv[0], "v": kv[1]}
    elif mk == "mla":
        y, kv = attn.mla_apply_train(bp["mixer"], h, cfg, position_ids)
        cacheable = {"c_kv": kv[0], "k_rope": kv[1]}
    else:
        y, cacheable = ssm.mamba_mix(
            bp["mixer"], h, cfg, cfg.mamba_chunk, return_state=True,
            training=training, valid_len=valid_len,
        )
    x = x + y
    if fk != "none":
        h2 = norm_apply(bp["ln2"], x, cfg.norm)
        if fk == "moe":
            y2, aux = moe_mod.moe_apply(bp["ffn"], h2, cfg, training=training)
        else:
            y2 = common.ffn_apply(bp["ffn"], h2, cfg.act)
        x = x + y2
    x = shard_act(x, ("batch", "seq_sp", None))
    return x, cacheable, aux


def _apply_ffn(bp, x, cfg: LMConfig, fk: str):
    """Inference-mode FFN half of a block (shared by the decode and
    paged-prefill block bodies)."""
    if fk == "none":
        return x
    h2 = norm_apply(bp["ln2"], x, cfg.norm)
    if fk == "moe":
        y2, _ = moe_mod.moe_apply(bp["ffn"], h2, cfg, training=False)
    else:
        y2 = common.ffn_apply(bp["ffn"], h2, cfg.act)
    return x + y2


def _block_decode(bp, x, cfg: LMConfig, mk: str, fk: str, cache, pos, position_ids):
    h = norm_apply(bp["ln1"], x, cfg.norm)
    if mk == "gqa":
        y, cache = attn.gqa_apply_decode(bp["mixer"], h, cfg, cache, pos, position_ids)
    elif mk == "mla":
        y, cache = attn.mla_apply_decode(bp["mixer"], h, cfg, cache, pos)
    else:
        y, cache = ssm.mamba_step(bp["mixer"], h, cfg, cache)
    return _apply_ffn(bp, x + y, cfg, fk), cache


def _block_decode_paged(bp, x, cfg: LMConfig, mk: str, fk: str, cache, l,
                        block_tables, pos):
    """One decode block over a paged pool: ``cache`` is the block's pool
    entry stacked over the scan's groups, of which layer ``l`` is this
    block's, updated in place.  Attention mixers read/write shared pages
    through the block table; SSM mixers keep per-slot O(1) state (rows
    [0, B) of the pool's n_slots+1 rows — the last row is the garbage
    slot that absorbs burst-padding prefill writes)."""
    B = x.shape[0]
    h = norm_apply(bp["ln1"], x, cfg.norm)
    if mk == "gqa":
        y, cache = attn.gqa_apply_decode_paged(
            bp["mixer"], h, cfg, cache, l, block_tables, pos
        )
    elif mk == "mla":
        y, cache = attn.mla_apply_decode_paged(
            bp["mixer"], h, cfg, cache, l, block_tables, pos
        )
    else:
        y, new = ssm.mamba_step(
            bp["mixer"], h, cfg,
            {"h": cache["h"][l, :B], "conv": cache["conv"][l, :B]},
        )
        cache = {
            "h": cache["h"].at[l, :B].set(new["h"]),
            "conv": cache["conv"].at[l, :B].set(
                new["conv"].astype(cache["conv"].dtype)),
        }
    return _apply_ffn(bp, x + y, cfg, fk), cache


def _block_prefill_paged(bp, x, cfg: LMConfig, mk: str, fk: str, cache, l,
                         block_tables, ctx_len, tail_valid, wr_pg, wr_rw,
                         slots, use_context: bool):
    """One paged-prefill block over layer ``l`` of the block's stacked
    pool entry, updated in place: attention mixers attend [reused prefix
    pages ; causal tail] and scatter the tail K/V into the slot's pages;
    SSM mixers run the chunked mix over the tail (per-row valid_len) and
    scatter the post-prompt state at ``slots`` (prefix reuse never
    applies to SSM layers — the scheduler guarantees ctx_len == 0 for
    architectures with recurrent state).  ``use_context=False`` (static)
    compiles the context gather out entirely — the shape a scheduler
    with prefix reuse gated off uses."""
    h = norm_apply(bp["ln1"], x, cfg.norm)
    if mk == "gqa":
        y, cache = attn.gqa_apply_prefix(
            bp["mixer"], h, cfg, cache, l, block_tables, ctx_len, wr_pg,
            wr_rw, use_context,
        )
    elif mk == "mla":
        y, cache = attn.mla_apply_prefix(
            bp["mixer"], h, cfg, cache, l, block_tables, ctx_len, wr_pg,
            wr_rw, use_context,
        )
    else:
        y, state = ssm.mamba_mix(
            bp["mixer"], h, cfg, cfg.mamba_chunk, return_state=True,
            training=False, valid_len=tail_valid,
        )
        cache = {
            "h": cache["h"].at[l, slots].set(state["h"]),
            "conv": cache["conv"].at[l, slots].set(
                state["conv"].astype(cache["conv"].dtype)),
        }
    return _apply_ffn(bp, x + y, cfg, fk), cache


# ------------------------------ embedding -------------------------------------
def embed_inputs(params, batch: Dict[str, Any], cfg: LMConfig, offset=0):
    if cfg.external_embed:
        x = batch["embeds"].astype(cfg.cdtype)
    else:
        x = take_rows(params["embed"]["w"].astype(cfg.cdtype), batch["tokens"])
    if cfg.pos == "sinusoidal":
        B, S = x.shape[:2]
        pos = jnp.arange(S)[None, :] + offset
        x = x + common.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    return shard_act(x, ("batch", "seq_sp", None))


def _head_logits(params, h, cfg: LMConfig):
    with common.precision_island("logits"):
        if cfg.tie_embeddings:
            w = params["embed"]["w"]
            w = w if w.dtype == h.dtype else w.astype(h.dtype)
            logits = jnp.matmul(
                h, w.T, preferred_element_type=jnp.float32
            ).astype(h.dtype)
        else:
            logits = dense(params["head"], h)
        # A serving mesh gathers the vocab-sharded logits so argmax and
        # categorical sampling run replicated (the same reduction order
        # and RNG bits on every device); no-op outside a serving context.
        return repl_act(logits)


# ------------------------------- forward --------------------------------------
def forward_hidden(params, x, cfg: LMConfig, position_ids=None, collect_cache=False, training=True, valid_len=None):
    """Scan the block stack; returns (h, stacked cacheables, aux_sum).
    ``valid_len`` marks trailing positions as right-padding for cache
    collection (see ``mamba_mix``); attention needs no mask — causality
    already keeps right-pads out of every valid position's output."""
    period = cfg.scan_period()
    kinds = [(cfg.mixer_kind(i), cfg.ffn_of(i)) for i in range(period)]

    def group_body(x, group_params):
        caches = []
        aux = jnp.float32(0.0)
        for pos in range(period):
            mk, fk = kinds[pos]
            x, c, a = _block_train(group_params[pos], x, cfg, mk, fk, position_ids, training, valid_len)
            caches.append(c)
            aux = aux + a
        return x, (tuple(caches), aux)

    if cfg.remat:
        group_body = jax.checkpoint(group_body)

    def scan_body(x, gp):
        return group_body(x, gp)

    x, (caches, auxs) = jax.lax.scan(scan_body, x, tuple(params["blocks"]))
    return x, (caches if collect_cache else None), jnp.sum(auxs)


def loss_fn(params, batch: Dict[str, Any], cfg: LMConfig):
    """Training loss: chunked CE + MoE aux (+ MTP branch for DeepSeek)."""
    x = embed_inputs(params, batch, cfg)
    pos_ids = batch.get("position_ids")
    h, _, aux = forward_hidden(params, x, cfg, pos_ids)
    h = norm_apply(params["ln_f"], h, cfg.norm)

    targets = batch["targets"]
    mask = batch.get("loss_mask")

    def logits32(hh):
        with common.precision_island("logits"):
            return _head_logits(params, hh, cfg).astype(jnp.float32)

    loss = common.softmax_xent_chunked(
        logits32, h, targets, mask, cfg.loss_chunk,
    )
    metrics = {"ce": loss, "aux": aux}
    loss = loss + cfg.aux_loss_weight * aux

    if cfg.mtp and not cfg.external_embed:
        # DeepSeek-style depth-1 MTP: combine h_t with embed(token_{t+1})
        # to predict token_{t+2} through one extra block.
        emb = params["embed"]["w"].astype(h.dtype)[batch["tokens"][:, 1:]]
        comb = jnp.concatenate([h[:, :-1], emb], axis=-1)
        z = dense(params["mtp"]["proj"], comb)
        mk, fk = cfg.mixer_kind(0), cfg.ffn_of(0)
        z, _, _ = _block_train(params["mtp"]["block"], z, cfg, mk, fk, None)
        z = norm_apply(params["mtp"]["ln"], z, cfg.norm)
        t2 = targets[:, 1:]
        m2 = None if mask is None else mask[:, 1:]
        mtp_loss = common.softmax_xent_chunked(
            logits32, z, t2, m2, cfg.loss_chunk,
        )
        metrics["mtp"] = mtp_loss
        loss = loss + cfg.mtp_weight * mtp_loss

    metrics["loss"] = loss
    return loss, metrics


# ----------------------------- caches / serving --------------------------------
def init_cache(cfg: LMConfig, batch: int, max_len: int):
    """Abstract-friendly cache pytree matching the scanned block layout."""
    period = cfg.scan_period()
    groups = cfg.n_layers // period
    cdt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.cache_dtype]

    def one(mk):
        if mk == "gqa":
            return {
                "k": jnp.zeros((groups, batch, max_len, cfg.n_kv, cfg.hd), cdt),
                "v": jnp.zeros((groups, batch, max_len, cfg.n_kv, cfg.hd), cdt),
            }
        if mk == "mla":
            m = cfg.mla
            return {
                "c_kv": jnp.zeros((groups, batch, max_len, m.kv_lora_rank), cdt),
                "k_rope": jnp.zeros((groups, batch, max_len, m.qk_rope_dim), cdt),
            }
        c = ssm.mamba_cache_init(cfg, batch, cdt)
        return jax.tree.map(
            lambda a: jnp.zeros((groups,) + a.shape, a.dtype), c
        )

    return tuple(one(cfg.mixer_kind(pos)) for pos in range(period))


def decode_step(params, inputs, pos, caches, cfg: LMConfig):
    """One decode step: inputs {"tokens": (B,1)} | {"embeds": (B,1,D)};
    pos = current length (new token written at index pos) — a scalar
    (classic equal-length batch) or a (B,) vector of per-row lengths
    (slot-based continuous batching: every slot decodes at its own
    position inside ONE program)."""
    x = embed_inputs(
        params, inputs, cfg,
        offset=pos[:, None] if jnp.ndim(pos) == 1 else pos,
    )
    period = cfg.scan_period()
    kinds = [(cfg.mixer_kind(i), cfg.ffn_of(i)) for i in range(period)]
    pos_ids = inputs.get("position_ids")

    def scan_body(x, xs):
        gp, gcaches = xs
        new_caches = []
        for p_i in range(period):
            mk, fk = kinds[p_i]
            x, c = _block_decode(gp[p_i], x, cfg, mk, fk, gcaches[p_i], pos, pos_ids)
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(scan_body, x, (tuple(params["blocks"]), caches))
    h = norm_apply(params["ln_f"], x, cfg.norm)
    logits = _head_logits(params, h, cfg)
    return logits, new_caches


def prefill(params, batch, cfg: LMConfig, max_len: Optional[int] = None,
            valid_len=None):
    """Run the full prompt; returns (caches padded to max_len, last-token
    logits).  SSM mixers carry O(1) state; attention mixers stack K/V.

    ``valid_len`` (traced scalar) supports *bucketed* prefill: the prompt
    is right-padded to a bucket length, positions >= valid_len are
    padding, and the returned logits are taken at index valid_len - 1
    (the last real token).  Right-pads never reach a real position's
    output (causal attention) or the returned SSM state / conv tail
    (identity recurrence steps, see ``mamba_mix``); the K/V cache rows in
    [valid_len, S) hold pad junk, which is safe because decode at
    position p overwrites row p before the causal mask first exposes it."""
    x = embed_inputs(params, batch, cfg)
    S = x.shape[1]
    B = x.shape[0]
    max_len = max_len or S
    pos_ids = batch.get("position_ids")
    h, caches, _ = forward_hidden(params, x, cfg, pos_ids, collect_cache=True,
                                  training=False, valid_len=valid_len)
    h = norm_apply(params["ln_f"], h, cfg.norm)
    logits = _head_logits(params, last_valid_hidden(h, valid_len), cfg)

    period = cfg.scan_period()
    cdt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.cache_dtype]
    full = init_cache(cfg, B, max_len)
    out = []
    for p_i in range(period):
        mk = cfg.mixer_kind(p_i)
        got = caches[p_i]           # stacked over groups, seq dim = S
        if mk == "gqa":
            out.append({
                "k": jax.lax.dynamic_update_slice(
                    full[p_i]["k"], got["k"].astype(cdt), (0, 0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    full[p_i]["v"], got["v"].astype(cdt), (0, 0, 0, 0, 0)),
            })
        elif mk == "mla":
            out.append({
                "c_kv": jax.lax.dynamic_update_slice(
                    full[p_i]["c_kv"], got["c_kv"].astype(cdt), (0, 0, 0, 0)),
                "k_rope": jax.lax.dynamic_update_slice(
                    full[p_i]["k_rope"], got["k_rope"].astype(cdt), (0, 0, 0, 0)),
            })
        else:
            # Mamba prefill: the chunked mix returns the exact
            # post-prompt state {"h", "conv"} per layer.
            out.append(
                {"h": got["h"], "conv": got["conv"].astype(cdt)}
            )
    return tuple(out), logits


# --------------------------- paged KV-cache pool -------------------------------
def init_paged_pool(cfg: LMConfig, n_slots: int, n_pages: int, page_size: int,
                    mesh=None):
    """Paged cache pool: attention caches are SHARED pages instead of
    per-slot monolithic regions.

    Attention leaves are (groups, n_pages, page_size, ...) — a slot's
    logical (max_len, ...) cache is the concatenation of the pages its
    block-table row names, which lets fully-covered prompt-prefix pages
    be refcounted across requests (shared-prefix reuse).  Page 0 is the
    reserved GARBAGE page: never allocated, it absorbs the clamped
    writes of inactive decode slots and the right-pad writes of burst
    prefill, so junk can never land in a live page.

    SSM state is O(1) in sequence length, so it stays per-slot:
    (groups, n_slots + 1, ...), where row ``n_slots`` is the garbage
    SLOT that absorbs the state writes of burst-padding rows.

    With ``mesh`` (a tensor-parallel serving mesh, axis ``"model"``) the
    K/V page leaves are laid out head-sharded via
    ``dist.sharding.serve_pool_sharding_tree``, while MLA latent pages
    and SSM states replicate (their contractions need whole operands)."""
    period = cfg.scan_period()
    groups = cfg.n_layers // period
    cdt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.cache_dtype]

    def one(mk):
        if mk == "gqa":
            return {
                "k": jnp.zeros((groups, n_pages, page_size, cfg.n_kv, cfg.hd), cdt),
                "v": jnp.zeros((groups, n_pages, page_size, cfg.n_kv, cfg.hd), cdt),
            }
        if mk == "mla":
            m = cfg.mla
            return {
                "c_kv": jnp.zeros((groups, n_pages, page_size, m.kv_lora_rank), cdt),
                "k_rope": jnp.zeros((groups, n_pages, page_size, m.qk_rope_dim), cdt),
            }
        c = ssm.mamba_cache_init(cfg, n_slots + 1, cdt)
        return jax.tree.map(
            lambda a: jnp.zeros((groups,) + a.shape, a.dtype), c
        )

    def make():
        return tuple(one(cfg.mixer_kind(pos)) for pos in range(period))

    if mesh is None:
        return make()
    from repro.dist.sharding import serve_pool_sharding_tree

    # Made where it lives: a pool larger than one chip is never whole on
    # any device, as it would be if made and then placed.
    layout = serve_pool_sharding_tree(jax.eval_shape(make), mesh)
    return jax.jit(make, out_shardings=layout)()


def _group_index(cfg: LMConfig):
    """The layer scan's group indices, scanned beside the blocks: each
    step indexes the pool it carries (stacked over the groups) at its
    own group and updates it in place.  Scanning the pool as ``xs`` and
    returning it as ``ys`` would copy every layer's slab out, into a
    fresh stacked pool, and that pool back into the donated one."""
    return jnp.arange(cfg.n_layers // cfg.scan_period(), dtype=jnp.int32)


def decode_step_paged(params, inputs, pos, pool, block_tables, cfg: LMConfig):
    """One decode step over all slots, reading/writing attention caches
    THROUGH the block tables (``(B, max_len // page_size)`` int32 page
    ids per slot) inside the one jitted program.  ``pos`` is the (B,)
    per-slot length vector; masking makes the result bitwise identical
    to ``decode_step`` over equivalent monolithic per-slot caches.

    The attention core dispatches on ``cfg.attn_backend``
    (``kernels.ops.AttnBackend``): the fused paged-attention Pallas
    kernels on TPU, the XLA gather+attend reference elsewhere — the
    backends are bitwise identical, so this program's exactness
    contracts are backend-independent."""
    x = embed_inputs(params, inputs, cfg, offset=pos[:, None])
    period = cfg.scan_period()
    kinds = [(cfg.mixer_kind(i), cfg.ffn_of(i)) for i in range(period)]

    def scan_body(carry, xs):
        x, pool = carry
        gp, l = xs
        pool = list(pool)
        for p_i in range(period):
            mk, fk = kinds[p_i]
            x, pool[p_i] = _block_decode_paged(
                gp[p_i], x, cfg, mk, fk, pool[p_i], l, block_tables, pos
            )
        return (x, tuple(pool)), None

    (x, pool), _ = jax.lax.scan(
        scan_body, (x, pool), (tuple(params["blocks"]), _group_index(cfg))
    )
    h = norm_apply(params["ln_f"], x, cfg.norm)
    logits = _head_logits(params, h, cfg)
    return logits, pool


def prefill_paged(params, batch, cfg: LMConfig, pool, block_tables, slots,
                  ctx_len, tail_valid, page_size: int,
                  use_context: bool = True):
    """Batched burst prefill into the paged pool.

    ``batch["tokens"]`` is (B, T): each row holds one admitted request's
    prompt TAIL (the part after its reused prefix), right-padded to the
    tail bucket T.  Per row: ``ctx_len`` counts reused prefix tokens
    (0 without a hit), ``tail_valid`` the real tail tokens, ``slots``
    the decode slot (the garbage slot ``n_slots`` for burst padding
    rows), and ``block_tables[b]`` the slot's page list — prefix pages
    resident and already filled, tail pages freshly allocated.

    Tail positions are absolute (``ctx_len + t``) for RoPE/sinusoidal
    embeddings; attention runs [prefix pages ; causal tail]; tail K/V
    scatters into the slot's pages (pads to the garbage page); SSM state
    scatters at ``slots``.  ``use_context=False`` (static, for
    schedulers whose prefix reuse is gated off — ctx_len is then always
    0) skips the per-layer context gather entirely.  Returns
    (pool, (B, 1, V) logits at each row's last real token).

    This is also the scheduler's **chunked-prefill** entry: a
    continuation chunk passes the already-filled token count as
    ``ctx_len`` and the next chunk as the tail.  Nothing here needs the
    chunk boundary to be page-aligned — positions are absolute via
    ``offset=ctx_len``, the context gather reads whole pages but masks
    attention at ``j < ctx_len[b]``, and ``page_write_indices`` scatters
    a tail starting mid-page.  Each chunk therefore computes bitwise
    what a single full prefill would at those positions (the exactness
    gate in serve.Scheduler holds the cases where it could differ —
    SSM state, lossy cache dtype — out of this path)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = embed_inputs(params, batch, cfg, offset=ctx_len[:, None])
    wr_pg, wr_rw = page_write_indices(
        block_tables, ctx_len, tail_valid, T, page_size
    )
    period = cfg.scan_period()
    kinds = [(cfg.mixer_kind(i), cfg.ffn_of(i)) for i in range(period)]

    def scan_body(carry, xs):
        x, pool = carry
        gp, l = xs
        pool = list(pool)
        for p_i in range(period):
            mk, fk = kinds[p_i]
            x, pool[p_i] = _block_prefill_paged(
                gp[p_i], x, cfg, mk, fk, pool[p_i], l, block_tables,
                ctx_len, tail_valid, wr_pg, wr_rw, slots, use_context,
            )
        return (x, tuple(pool)), None

    (x, pool), _ = jax.lax.scan(
        scan_body, (x, pool), (tuple(params["blocks"]), _group_index(cfg))
    )
    h = norm_apply(params["ln_f"], x, cfg.norm)
    logits = _head_logits(params, last_valid_hidden(h, tail_valid), cfg)
    return pool, logits


def pool_nbytes(pool) -> int:
    """Device footprint of a cache pool (paged or monolithic) in bytes.

    The pool is the biggest long-lived buffer of the serving stack;
    since ``serve.ServeSession`` allocates it exactly once per session
    (it used to be rebuilt per trace) the session reports this number
    through ``ServeStats.pool_bytes`` so capacity planning can see what
    persists across traces."""
    return int(sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool)
    ))


def insert_cache_slot(pool, row_caches, slot):
    """Overwrite slot ``slot`` of a pooled cache (batch dim 1, after the
    stacked-groups dim 0) with a freshly prefilled batch-of-1 cache.

    The WHOLE per-slot region is replaced — K/V rows beyond the new
    prompt come from ``init_cache`` zeros, so nothing of the slot's
    previous occupant survives recycling (no cross-request KV leakage).
    """
    return jax.tree.map(
        lambda pool_leaf, new_leaf: pool_leaf.at[:, slot].set(
            new_leaf[:, 0].astype(pool_leaf.dtype)
        ),
        pool, row_caches,
    )


# ------------------------------ lint contract --------------------------------
from repro.analysis.registry import Built, register_contract  # noqa: E402


@register_contract(
    "lm.prefill_paged",
    checks=("donation", "transfers", "precision"),
    description="batched paged prefill at a smoke config: the donated "
                "pool must alias in the compiled module, a pool-"
                "rebinding call must run clean under a transfer guard, "
                "and the traced program must satisfy the f32 precision "
                "policy (no f64, declared dot accumulation, widening "
                "only inside islands)",
)
def _build_prefill_paged_contract() -> Built:
    from repro import configs
    from repro.analysis.jaxpr_tools import compile_unit
    from repro.analysis.registry import PrecisionPolicy

    cfg = configs.get_smoke_config("qwen2.5-3b")
    params = init(jax.random.PRNGKey(0), cfg)
    n_slots, page_size, pages_per_slot = 2, 8, 4
    pool = init_paged_pool(
        cfg, n_slots, n_slots * pages_per_slot + 1, page_size
    )
    B, T = 2, 8

    def entry(params, pool, tokens, block_tables, slots, ctx_len, tail_valid):
        return prefill_paged(
            params, {"tokens": tokens}, cfg, pool, block_tables, slots,
            ctx_len, tail_valid, page_size, False,
        )

    jitted = jax.jit(entry, donate_argnums=(1,))
    call_args = (
        jnp.zeros((B, T), jnp.int32),
        jnp.zeros((B, pages_per_slot), jnp.int32),
        jnp.asarray([0, 1], jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.full((B,), T, jnp.int32),
    )
    unit = compile_unit(
        "prefill_paged", jitted, (params, pool) + call_args,
        donate_argnums=(1,),
    )

    # Rebinding call loop, exactly like the serve session drives it: the
    # donated pool is consumed and replaced by the returned one.
    state = {"pool": pool}

    def hot():
        new_pool, logits = jitted(params, state["pool"], *call_args)
        state["pool"] = new_pool
        return jax.block_until_ready(logits)

    prefill_jaxpr = jax.make_jaxpr(entry)(params, pool, *call_args)
    return Built(
        compiled=[unit], hot=hot, hot_label="prefill_paged call",
        hot_jaxprs=[("prefill_paged", prefill_jaxpr)],
        precision=PrecisionPolicy(compute_dtype=cfg.compute_dtype),
    )
