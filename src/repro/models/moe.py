"""Mixture-of-Experts FFN: top-k router + GShard-style einsum dispatch.

The dispatch/combine tensors are one-hot over (expert, capacity-slot) per
token group; with experts sharded on the "model" mesh axis GSPMD lowers
the dispatch einsums to all-to-alls — the standard expert-parallel
pattern.  Optional always-on shared experts (DeepSeek-V3) ride the dense
FFN path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import repl_act, shard_act
from .common import dense, dense_init, ffn_apply, ffn_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden dim
    n_shared: int = 0            # always-on shared experts (DeepSeek)
    capacity_factor: float = 1.25
    group_size: int = 256        # tokens per dispatch group


def moe_init(key, cfg, dtype):
    m: MoEConfig = cfg.moe
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(cfg.d_model)
    p = {
        "router": dense_init(ks[0], cfg.d_model, m.n_experts, jnp.float32),
        "w_gate": (
            jax.random.normal(ks[1], (m.n_experts, cfg.d_model, m.d_ff)) * scale
        ).astype(dtype),
        "w_up": (
            jax.random.normal(ks[2], (m.n_experts, cfg.d_model, m.d_ff)) * scale
        ).astype(dtype),
        "w_down": (
            jax.random.normal(ks[3], (m.n_experts, m.d_ff, cfg.d_model))
            * (1.0 / math.sqrt(m.d_ff))
        ).astype(dtype),
    }
    if m.n_shared:
        p["shared"] = ffn_init(ks[4], cfg.d_model, m.d_ff * m.n_shared, "swiglu", dtype)
    return p


def _group(x, group_size):
    B, S, D = x.shape
    T = B * S
    tg = min(group_size, T)
    while T % tg:
        tg -= 1
    return x.reshape(T // tg, tg, D), tg


def _expert_weights(p):
    # §Perf iterations I1/I2 (see EXPERIMENTS.md):
    #  - expert weights re-constrained *inside* the layer-scan body so the
    #    FSDP all-gather happens per layer (1 layer's experts) instead of
    #    GSPMD hoisting one whole-stack gather before the loop;
    #  - dispatched activations keep their token-group dim on the data
    #    axes ("batch"); replicating it forced a full token all-gather
    #    per layer in the baseline.
    return (
        shard_act(p["w_gate"], ("experts", None, "fsdp")),
        shard_act(p["w_up"], ("experts", None, "fsdp")),
        shard_act(p["w_down"], ("experts", "fsdp", None)),
    )


def moe_apply(p, x, cfg, training: bool = True):
    """x: (B, S, D) -> (B, S, D).  Capacity-based token dropping (GShard);
    returns the combined expert outputs (+ shared experts, + aux loss kept
    in metrics by the caller via ``moe_apply.last_aux`` pattern avoided —
    aux loss is returned explicitly).

    Inference (``training=False``) is *drop-free*: every token reaches
    all of its top-k experts.  Capacity drops are a load-balancing
    training artifact; at serving time they would make a token's output
    depend on which other tokens share its dispatch group — i.e. on
    batch composition and prompt padding — which breaks the
    continuous-batching contract that scheduling never changes numerics.
    The inference path therefore routes per token with no capacity axis
    at all (see below)."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    # Decode (S == 1): every token is its own dispatch group.  Grouping
    # across the batch dim would couple co-scheduled requests — one
    # slot's token could evict another's expert-capacity slot — so a
    # slotted decode step must route each row independently (and match
    # a batch-of-1 decode bit for bit).
    xg, tg = _group(x, 1 if S == 1 else m.group_size)  # (G, Tg, D)
    G = xg.shape[0]
    E = m.n_experts

    logits = dense(p["router"], xg.astype(jnp.float32))          # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, m.top_k)               # (G, Tg, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # Load-balancing auxiliary loss (Switch/GShard).
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1)
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)

    w_gate, w_up, w_down = _expert_weights(p)

    if not training:
        # Drop-free inference without the capacity axis: a drop-free
        # GShard layout would need C = Tg capacity slots, making the
        # dispatch/combine one-hots (Tg, E, Tg) — quadratic in group
        # size and pure bookkeeping when nothing can ever drop.  Instead
        # every expert runs every token (same static GEMM shapes as the
        # full-capacity layout, E/top_k more work than the routed ideal)
        # and the top-k gates combine the outputs.  Per-token math only:
        # independent of batch composition, grouping and prompt padding.
        gates = jnp.sum(
            jax.nn.one_hot(idx, E, dtype=jnp.float32) * gate_vals[..., None],
            axis=2,
        )                                                        # (G, Tg, E)
        h = jax.nn.silu(jnp.einsum("gtd,edf->egtf", xg, w_gate.astype(xg.dtype)))
        h = h * jnp.einsum("gtd,edf->egtf", xg, w_up.astype(xg.dtype))
        h = shard_act(h, ("experts", "batch", None, "ff"))
        ye = jnp.einsum("egtf,efd->egtd", h, w_down.astype(xg.dtype))
        # A serving mesh gathers the expert dim before the combine, so
        # the weighted sum over experts associates as it does on one
        # device (top_k >= 3 sums are order-sensitive).
        ye = repl_act(ye)
        y = jnp.einsum("gte,egtd->gtd", gates.astype(ye.dtype), ye)
        y = y.reshape(B, S, D)
        if m.n_shared:
            y = y + ffn_apply(p["shared"], x, "swiglu")
        return y, aux

    C = max(int(math.ceil(tg * m.top_k / E * m.capacity_factor)), 1)

    # Position-in-expert bookkeeping, slot-ordered (GShard).
    dispatch = jnp.zeros((G, tg, E, C), jnp.bfloat16)
    combine = jnp.zeros((G, tg, E, C), jnp.float32)
    counts = jnp.zeros((G, E), jnp.int32)
    for kk in range(m.top_k):
        e_k = idx[..., kk]                                       # (G, Tg)
        onehot = jax.nn.one_hot(e_k, E, dtype=jnp.int32)         # (G, Tg, E)
        pos = counts[:, None, :] + jnp.cumsum(onehot, axis=1) - onehot
        pos_tok = jnp.sum(pos * onehot, axis=-1)                 # (G, Tg)
        keep = pos_tok < C
        slot = jax.nn.one_hot(jnp.where(keep, pos_tok, C), C + 1, dtype=jnp.float32)[..., :C]
        d_k = onehot.astype(jnp.float32)[..., None] * slot[:, :, None, :]
        dispatch = dispatch + d_k.astype(jnp.bfloat16)
        combine = combine + d_k * (gate_vals[..., kk] * keep)[..., None, None]
        counts = counts + jnp.sum(onehot, axis=1)

    xe = jnp.einsum("gtec,gtd->egcd", dispatch.astype(xg.dtype), xg)
    xe = shard_act(xe, ("experts", "batch", None, None))
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, w_gate.astype(xe.dtype)))
    h = h * jnp.einsum("egcd,edf->egcf", xe, w_up.astype(xe.dtype))
    ye = jnp.einsum("egcf,efd->egcd", h, w_down.astype(xe.dtype))
    ye = shard_act(ye, ("experts", "batch", None, None))
    ye = repl_act(ye)
    y = jnp.einsum("gtec,egcd->gtd", combine.astype(ye.dtype), ye)

    y = y.reshape(B, S, D)
    if m.n_shared:
        y = y + ffn_apply(p["shared"], x, "swiglu")
    return y, aux
