"""Plain reference forward pass of a dense GQA decoder (Qwen2 and Phi-3
families), written from the published architecture and independent of
the program under test: it imports nothing of ``src/`` and takes nothing
the program made.  Its weights come from ``bench/weights.py`` by seed.

Per layer: RMSNorm, Q/K/V projections (with bias where the configuration
has ``attention_bias``), rotary embedding on the whole head dimension in
the rotate-half convention, causal softmax attention with grouped KV
heads, output projection, residual; RMSNorm, SwiGLU feed-forward,
residual.  Then a final RMSNorm and the (tied) LM head.

It computes in float32 with every matmul at ``Precision.HIGHEST``, one
layer at a time over a batch of whole sequences, so it fits beside
nothing else on one chip.  ``low=True`` is the control: the same pass
with every matmul operand rounded to float8 e4m3 under a per-tensor
absmax scale, the precision below the served bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, low: bool):
    if low:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(c: dict, low: bool, w: dict, x):
    """One decoder layer over one sequence x: (S, d)."""
    S = x.shape[0]
    H, Hk, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rms(x, w["ln1.scale"], eps)

    def proj(name, heads):
        y = _mm(h, w[f"mixer.{name}.w"], low)
        if f"mixer.{name}.b" in w:
            y = y + w[f"mixer.{name}.b"]
        return y.reshape(S, heads, hd)

    q = _rope(proj("wq", H), theta)
    k = _rope(proj("wk", Hk), theta)
    v = proj("wv", Hk)
    G = H // Hk
    qg = q.reshape(S, Hk, G, hd) / np.sqrt(hd)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HI)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI).reshape(S, H * hd)
    x = x + _mm(o, w["mixer.wo.w"], low)
    h2 = _rms(x, w["ln2.scale"], eps)
    ff = jax.nn.silu(_mm(h2, w["ffn.w_gate.w"], low)) * _mm(h2, w["ffn.w_up.w"], low)
    return x + _mm(ff, w["ffn.w_down.w"], low)


@partial(jax.jit, static_argnames=("cfg_items", "low", "dtype"))
def _layer(x, words, layer, *, cfg_items, low, dtype):
    c = dict(cfg_items)
    w = {n: t.astype(jnp.float32)
         for n, t in weights.layer_tensors(words, c, layer, dtype).items()}
    return jax.lax.map(partial(_block, c, low, w), x)


@partial(jax.jit, static_argnames=("cfg_items", "low"))
def _head(x, table, head_w, ln_f, reads, *, cfg_items, low):
    """Per sequence: max logit, argmax and the logits of ``reads``
    (B, S, R) token ids at each position."""
    c = dict(cfg_items)

    def one(args):
        xs, rd = args
        h = _rms(xs, ln_f, c["rms_norm_eps"])
        logits = _mm(h, head_w if head_w is not None else table.T, low)
        return (logits.max(-1), logits.argmax(-1).astype(jnp.int32),
                jnp.take_along_axis(logits, rd, axis=-1))

    return jax.lax.map(one, (x, reads))


def logit_reads(c: dict, seed: int, seqs, reads, *, low: bool = False,
                dtype=jnp.bfloat16, pad_to: int = 512):
    """Run the reference over ``seqs`` (list of int token arrays) and
    return numpy (max, argmax, read) per sequence, trimmed to its length:
    ``max`` and ``argmax`` (S,), ``read`` (S, R) the logits of the token
    ids ``reads[i]`` ((S, R) int) at each position."""
    B = len(seqs)
    S = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    R = reads[0].shape[1]
    tok = np.zeros((B, S), np.int32)
    rd = np.zeros((B, S, R), np.int32)
    for i, (s, r) in enumerate(zip(seqs, reads)):
        tok[i, :len(s)] = s
        rd[i, :len(s)] = r
    words = jnp.asarray(weights.seed_words(seed))
    d = c["hidden_size"]
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, bool, str))))
    table = weights.tensor(words, "embed.w", 0, (c["vocab_size"], d), d,
                           dtype).astype(jnp.float32)
    x = table[jnp.asarray(tok)]
    for layer in range(c["num_hidden_layers"]):
        x = _layer(x, words, jnp.int32(layer), cfg_items=items, low=low,
                   dtype=dtype)
    ln_f = weights.tensor(words, "ln_f.scale", 0, (d,), d, dtype).astype(jnp.float32)
    head_w = None
    if not c.get("tie_word_embeddings", False):
        head_w = weights.tensor(words, "head.w", 0, (d, c["vocab_size"]), d,
                                dtype).astype(jnp.float32)
    mx, am, got = (np.asarray(a) for a in _head(
        x, table, head_w, ln_f, jnp.asarray(rd), cfg_items=items, low=low))
    return [(mx[i, :len(s)], am[i, :len(s)], got[i, :len(s)])
            for i, s in enumerate(seqs)]


def served_gaps(c: dict, seed: int, prompts, served, *, control: bool = False,
                dtype=jnp.bfloat16):
    """Per request, the gap by which each served token's reference logit
    lies below the reference's best at its position.

    ``control=False``: the served tokens are the program's.  ``control``:
    the reference itself at float8 picks the token at each position of
    the same sequences, and that token's gap is read in the float32
    reference.  Returns a list of (n_served,) float arrays."""
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
            for p, s in zip(prompts, served)]
    if control:
        low = logit_reads(c, seed, seqs, [np.zeros((len(q), 1), np.int32)
                                          for q in seqs], low=True, dtype=dtype)
        picks = [am for _, am, _ in low]
    reads = []
    for i, (p, s) in enumerate(zip(prompts, served)):
        r = np.zeros((len(seqs[i]), 1), np.int32)
        at = np.arange(len(p) - 1, len(seqs[i]))
        r[at, 0] = picks[i][at] if control else s
        reads.append(r)
    out = logit_reads(c, seed, seqs, reads, dtype=dtype)
    gaps = []
    for (mx, _, got), p in zip(out, prompts):
        at = np.arange(len(p) - 1, len(mx))
        gaps.append(mx[at] - got[at, 0])
    return gaps
