"""Useful operations of a dense decoder, counted from its shapes.

A token at position ``p`` (it attends to ``p + 1`` positions) costs two
operations per weight of every matmul it passes through, plus the
attention scores and the weighted sum over its context:

    per_token = 2 * L * (d * (q + 2 * kv) + q * d + 3 * d * f) + 2 * d * vocab
    attention = 4 * L * q * (p + 1)                       (q = heads * head_dim)

Worked example, smollm-360m (d 960, L 32, 15/5 heads of 64, f 2560, vocab
49152): q = 960, kv = 320, so each layer holds 960 * 1600 + 960 * 960 +
3 * 960 * 2560 = 9,830,400 matmul weights; 32 layers and the head give
2 * (32 * 9,830,400 + 960 * 49,152) = 723,517,440 operations per token.
A token at position 511 adds 4 * 32 * 960 * 512 = 62,914,560, so a
prompt of 512 tokens costs 512 * 723,517,440 + 4 * 32 * 960 * (512 * 513 / 2)
= 386,578,513,920 operations. Masked columns of a chunk scan are not work
the request needed and are never counted.
"""
from __future__ import annotations


def _dims(model: dict):
    d = model["hidden_size"]
    q = d  # heads * head_dim
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    return d, q, kv, model["num_hidden_layers"], model["intermediate_size"], model["vocab_size"]


def matmul_flops_per_token(model: dict) -> int:
    d, q, kv, L, f, v = _dims(model)
    return 2 * (L * (d * (q + 2 * kv) + q * d + 3 * d * f) + d * v)


def attention_flops(model: dict, context_sum: int) -> int:
    """Attention operations of tokens whose context lengths add to ``context_sum``."""
    d, q, kv, L, f, v = _dims(model)
    return 4 * L * q * int(context_sum)


def flops(model: dict, tokens: int, context_sum: int) -> int:
    """Operations of ``tokens`` tokens whose context lengths add to ``context_sum``."""
    return int(tokens) * matmul_flops_per_token(model) + attention_flops(model, context_sum)


def span_context_sum(start: int, end: int) -> int:
    """Sum of the context lengths of the tokens at positions [start, end)."""
    return (end * (end + 1) - start * (start + 1)) // 2
