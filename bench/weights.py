"""Seeded random weights for a dense GQA decoder, owned by the benchmark.

Every tensor is a function of (seed, tensor name, layer) alone, so the
program under test and the plain reference read the same values without
either handing anything to the other: the harness lays the tensors out
in the program's parameter tree (``program_params``), and the reference
regenerates each layer's tensors itself (``layer_tensors``).

Values are drawn in float32 and rounded to the served dtype; the
reference widens that rounding back to float32, so both sides start from
identical numbers.

Names are the program tree's paths below one layer (``mixer.wq.w``) or
at the top (``embed.w``).  Scales keep activations near unit RMS:
matrices ``N(0, 1/fan_in)``, biases ``N(0, 0.1^2)``, norm scales
``1 + N(0, 0.1^2)`` and the (tied) embedding ``N(0, 1/d)``, which gives
logits of about unit standard deviation.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size up to 64 bits as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _key(words, name: str, layer):
    k = jax.random.fold_in(jax.random.PRNGKey(0x5EED), words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def tensor(words, name: str, layer, shape, d_model: int, dtype):
    """One tensor, drawn in float32 and rounded to ``dtype``."""
    z = jax.random.normal(_key(words, name, layer), shape, jnp.float32)
    if name.endswith("scale"):
        x = 1.0 + 0.1 * z
    elif name.endswith(".b"):
        x = 0.1 * z
    elif name == "embed.w":
        x = z / np.sqrt(d_model)
    else:
        x = z / np.sqrt(shape[-2])
    return x.astype(dtype)


def layer_shapes(c: dict) -> dict:
    """Per-layer tensor shapes of a dense GQA block, from the benchmark's
    configuration keys (Hugging Face names)."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, Hk, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    s = {
        "ln1.scale": (d,), "ln2.scale": (d,),
        "mixer.wq.w": (d, H * hd), "mixer.wk.w": (d, Hk * hd),
        "mixer.wv.w": (d, Hk * hd), "mixer.wo.w": (H * hd, d),
        "ffn.w_up.w": (d, ff), "ffn.w_gate.w": (d, ff), "ffn.w_down.w": (ff, d),
    }
    if c.get("attention_bias"):
        s.update({"mixer.wq.b": (H * hd,), "mixer.wk.b": (Hk * hd,),
                  "mixer.wv.b": (Hk * hd,)})
    return s


def global_shapes(c: dict) -> dict:
    s = {"embed.w": (c["vocab_size"], c["hidden_size"]),
         "ln_f.scale": (c["hidden_size"],)}
    if not c.get("tie_word_embeddings", False):
        s["head.w"] = (c["hidden_size"], c["vocab_size"])
    return s


def layer_tensors(words, c: dict, layer, dtype=jnp.bfloat16) -> dict:
    d = c["hidden_size"]
    return {n: tensor(words, n, layer, s, d, dtype)
            for n, s in layer_shapes(c).items()}


def _path_name(path) -> str:
    parts = []
    for p in path:
        k = getattr(p, "key", getattr(p, "idx", None))
        parts.append(str(k))
    return ".".join(parts)


def program_params(c: dict, tree_shapes, seed: int):
    """The program's parameter tree (``tree_shapes``, as
    ``jax.eval_shape`` of its init gives it) filled with this module's
    tensors, in one jitted call on the default device.  Stacked block
    leaves (``blocks.<i>.<name>``, leading axis = layer) are vmapped over
    the layer index.  A leaf this module has no tensor for, or a shape
    that disagrees with the configuration, is an error."""
    want_layer, want_global = layer_shapes(c), global_shapes(c)
    L = c["num_hidden_layers"]
    named = jax.tree_util.tree_flatten_with_path(tree_shapes)[0]
    for path, leaf in named:
        name = _path_name(path)
        if name.startswith("blocks."):
            sub = name.split(".", 2)[2]
            if want_layer.get(sub) != tuple(leaf.shape[1:]) or leaf.shape[0] != L:
                raise ValueError(f"program leaf {name} {leaf.shape} not in the "
                                 f"benchmark's layout")
        elif want_global.get(name) != tuple(leaf.shape):
            raise ValueError(f"program leaf {name} {leaf.shape} not in the "
                             f"benchmark's layout")
    n_layer = sum(1 for p, _ in named if _path_name(p).startswith("blocks."))
    if n_layer != len(want_layer) or len(named) - n_layer != len(want_global):
        raise ValueError("program parameter tree and benchmark layout differ")

    treedef = jax.tree_util.tree_structure(tree_shapes)
    d = c["hidden_size"]

    def build(words):
        leaves = []
        for path, leaf in named:
            name = _path_name(path)
            if name.startswith("blocks."):
                sub = name.split(".", 2)[2]
                leaves.append(jax.vmap(
                    lambda i, sub=sub, leaf=leaf: tensor(
                        words, sub, i, leaf.shape[1:], d, leaf.dtype)
                )(jnp.arange(L)))
            else:
                leaves.append(tensor(words, name, 0, leaf.shape, d, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jnp.asarray(seed_words(seed)))
