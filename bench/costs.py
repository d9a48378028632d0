"""Operations and bytes the algorithm needs, from a configuration's shapes.

Counted at the configuration's stated precision (``precision``, bf16:
2 bytes), whatever dtype or padding the program uses today, so that a
change of dtype or padding is measured against the same yardstick.  A
multiply-add counts as two operations.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def elem_bytes(c: dict) -> int:
    return BYTES[c["precision"]]


def attn_params(c: dict) -> int:
    d, h, hkv, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"]
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def dense_ffn_params(c: dict) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def expert_params(c: dict) -> int:
    return 3 * c["d_model"] * c["moe"]["expert_d_ff"]


def kv_bytes_per_position(c: dict) -> int:
    """Key and value of one position in one layer."""
    return 2 * c["num_kv_heads"] * c["head_dim"] * elem_bytes(c)


def attn_flops(c: dict, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` positions."""
    return 4 * ctx * c["num_heads"] * c["head_dim"]


def decode_unit(c: dict, rows: int, ctx_sum: int):
    """(flops, bytes) of one decode call of one layer's unit program for
    ``rows`` live rows attending ``ctx_sum`` positions in all: the
    layer's attention weights (and dense MLP; a MoE layer's experts run
    apart), the live KV rows read, and each row's new K/V written."""
    p = attn_params(c) + (0 if c.get("moe") else dense_ffn_params(c))
    norms = 2 * c["d_model"]
    flops = 2 * rows * p + attn_flops(c, ctx_sum)
    nbytes = ((p + norms) * elem_bytes(c) + ctx_sum * kv_bytes_per_position(c)
              + rows * kv_bytes_per_position(c))
    return flops, nbytes


def token_flops(c: dict, ctx: int) -> int:
    """Model operations of one token at context ``ctx`` (its own position
    included): every layer's matmuls (the top-k experts and the router
    of a MoE layer), attention, and the output head."""
    d, L = c["d_model"], c["num_layers"]
    per_layer = attn_params(c)
    if c.get("moe"):
        per_layer += c["moe"]["top_k"] * expert_params(c) + d * c["moe"]["num_experts"]
    else:
        per_layer += dense_ffn_params(c)
    return 2 * (L * per_layer + d * c["vocab_size"]) + L * attn_flops(c, ctx)
