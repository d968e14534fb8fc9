"""Model FLOPs of a dense GQA decoder per token, from the configuration:
2 per weight of every matmul the token passes through (the LM head
included, the embedding gather not), plus attention's 4 * heads * hd per
position attended (QK^T and PV).  The arithmetic follows
``launch/roofline.py``'s ``param_counts`` / ``model_flops``."""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    d, ff, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, Hk, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    per_layer = d * (H + 2 * Hk) * hd + H * hd * d + 3 * d * ff
    return L * per_layer + d * c["vocab_size"]


def attention_flops(c: dict, positions_attended: int) -> int:
    """Attention FLOPs of ``positions_attended`` (query, key) pairs, summed
    over all layers and heads."""
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"] * int(
        positions_attended)


def flops(c: dict, tokens: int, positions_attended: int) -> int:
    """FLOPs of ``tokens`` tokens whose queries attend
    ``positions_attended`` positions in all."""
    return 2 * matmul_params(c) * int(tokens) + attention_flops(c, positions_attended)
