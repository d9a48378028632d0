"""Operations and bytes the algorithm needs, from a configuration's shapes.

Counted at the configuration's stated precision (``precision``, bf16:
2 bytes), whatever dtype or padding the program uses today, so that a
change of dtype or padding is measured against the same yardstick.  A
multiply-add counts as two operations.  Every count is an integer.

The layers are those the file describes (``layers``): ``pattern`` x
``num_periods``, then ``remainder``, or ``num_layers`` of one
``[mixer, ffn]``.  Each is counted by its kind:

- ``attn``: grouped-query attention, K and V of ``num_kv_heads`` a
  position; ``attn_local`` the same over at most ``window`` positions.
- ``mla``: DeepSeek's latent attention (``mla``): a q projection through
  a ``q_lora_rank`` latent, or straight from ``d_model`` where it is 0;
  ``kv_a`` to the ``kv_lora_rank`` latent and the shared rope key; ``kv_b``
  up to each head's no-rope key and value; the output.  The cache holds
  ``kv_lora_rank + qk_rope_head_dim`` values a position.  Two forms:
  ``decode_unit`` counts the absorbed form, the least work any
  implementation needs: ``kv_b``'s key half folded into the query and its
  value half into the output, once per row, and the latent cache read
  once and scored at ``kv_lora_rank + qk_rope_head_dim`` dims a head,
  its values at ``kv_lora_rank``.  ``token_flops`` counts the published
  formulation: ``kv_b`` applied once per position, attention at
  ``qk_nope + qk_rope`` dims a head for the scores and ``v_head_dim`` for
  the values.
- ``dense``: a SwiGLU MLP of ``d_ff``.
- ``moe``: the router, the ``top_k`` routed experts of ``expert_d_ff``
  and ``num_shared`` shared experts of ``shared_d_ff``.
- ``ssm``, ``cross`` and ``enc`` are not counted yet: they raise.
"""
from __future__ import annotations

from collections import Counter

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
ATTENTION = ("attn", "attn_local", "mla")


def elem_bytes(c: dict) -> int:
    return BYTES[c["precision"]]


def layers(c: dict) -> list:
    """``(mixer, ffn)`` of every layer, in order."""
    rem = [tuple(p) for p in c.get("remainder", ())]
    if "pattern" in c:
        pat = [tuple(p) for p in c["pattern"]]
        periods = c.get("num_periods") or (c["num_layers"] - len(rem)) // len(pat)
    else:
        pat, periods = [(c["mixer"], c["ffn"])], c["num_layers"] - len(rem)
    out = pat * periods + rem
    if len(out) != c["num_layers"]:
        raise ValueError(f"{c['name']}: the pattern gives {len(out)} layers, "
                         f"num_layers is {c['num_layers']}")
    return out


def layer_counts(c: dict) -> Counter:
    """How many layers of each ``(mixer, ffn)`` kind the stack holds."""
    return Counter(layers(c))


def _mixer_kind(mixer: str) -> str:
    if mixer not in ATTENTION:
        raise ValueError(f"no costs for {mixer!r} layers yet")
    return mixer


def attn_params(c: dict) -> int:
    d, h, hkv, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"]
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def mla_params(c: dict) -> int:
    """q (through its latent, or straight), kv_a, kv_b and the output."""
    d, h, m = c["d_model"], c["num_heads"], c["mla"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r = m["q_lora_rank"]
    q = d * r + r * h * qk if r else d * h * qk
    kv = (d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
          + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"]))
    return q + kv + h * m["v_head_dim"] * d


def mixer_params(c: dict, mixer: str) -> int:
    return mla_params(c) if _mixer_kind(mixer) == "mla" else attn_params(c)


def mixer_norms(c: dict, mixer: str) -> int:
    """Norm weights of a layer: before the mixer and the FFN, and MLA's
    norms of its latents."""
    n = 2 * c["d_model"]
    if _mixer_kind(mixer) == "mla":
        n += c["mla"]["kv_lora_rank"] + (c["mla"]["q_lora_rank"] or 0)
    return n


def dense_ffn_params(c: dict) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def expert_params(c: dict) -> int:
    return 3 * c["d_model"] * c["moe"]["expert_d_ff"]


def moe_active_params(c: dict) -> int:
    """What one token multiplies in a MoE layer: the router, its top-k
    routed experts and the shared experts."""
    m = c["moe"]
    return (c["d_model"] * m["num_experts"] + m["top_k"] * expert_params(c)
            + m.get("num_shared", 0) * 3 * c["d_model"] * m.get("shared_d_ff", 0))


def ffn_active_params(c: dict, ffn: str) -> int:
    if ffn == "dense":
        return dense_ffn_params(c)
    if ffn == "moe":
        return moe_active_params(c)
    raise ValueError(f"no costs for {ffn!r} FFNs")


def kv_values_per_position(c: dict, mixer: str) -> int:
    if _mixer_kind(mixer) == "mla":
        return c["mla"]["kv_lora_rank"] + c["mla"]["qk_rope_head_dim"]
    return 2 * c["num_kv_heads"] * c["head_dim"]


def kv_bytes_per_position(c: dict, mixer: str) -> int:
    """Key and value (MLA: the latent and the rope key) of one position in
    one layer of kind ``mixer``."""
    return kv_values_per_position(c, mixer) * elem_bytes(c)


def attended(c: dict, mixer: str, ctx: int) -> int:
    """Positions a query at context ``ctx`` attends in a ``mixer`` layer."""
    return min(ctx, c["window"]) if mixer == "attn_local" else ctx


def attn_flops(c: dict, ctx: int) -> int:
    """Scores and weighted values of one GQA query over ``ctx``
    positions."""
    return 4 * ctx * c["num_heads"] * c["head_dim"]


def mla_flops(c: dict, ctx: int, absorbed: bool) -> int:
    """Scores and weighted values of one MLA query over ``ctx``
    positions, in the absorbed or the published form (module
    docstring)."""
    m = c["mla"]
    if absorbed:
        per_head = 2 * m["kv_lora_rank"] + m["qk_rope_head_dim"]
    else:
        per_head = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return 2 * ctx * c["num_heads"] * per_head


def mixer_flops(c: dict, mixer: str, n: int, absorbed: bool) -> int:
    """Attention's operations over ``n`` attended positions in all."""
    if _mixer_kind(mixer) == "mla":
        return mla_flops(c, n, absorbed)
    return attn_flops(c, n)


def decode_unit(c: dict, ctxs, layer) -> tuple:
    """(flops, bytes) of one decode call of the unit program of one layer
    of kind ``layer`` = ``(mixer, ffn)``, for live rows attending
    ``ctxs`` positions each: the layer's mixer weights (and dense MLP; a
    MoE layer's experts, shared ones included, run apart), its norms, the
    live KV rows read, and each row's new KV written.  MLA in the absorbed
    form."""
    mixer, ffn = layer
    p = mixer_params(c, mixer) + (dense_ffn_params(c) if ffn == "dense" else 0)
    kvb = kv_bytes_per_position(c, mixer)
    read = sum(attended(c, mixer, n) for n in ctxs)
    flops = 2 * len(ctxs) * p + mixer_flops(c, mixer, read, absorbed=True)
    nbytes = ((p + mixer_norms(c, mixer)) * elem_bytes(c) + read * kvb
              + len(ctxs) * kvb)
    return flops, nbytes


def token_flops(c: dict, ctx: int) -> int:
    """Model operations of one token at context ``ctx`` (its own position
    included): every layer's matmuls (a MoE layer's router, top-k and
    shared experts), attention (MLA in the published form), and the
    output head."""
    n = 0
    for (mixer, ffn), k in layer_counts(c).items():
        n += k * (2 * (mixer_params(c, mixer) + ffn_active_params(c, ffn))
                  + mixer_flops(c, mixer, attended(c, mixer, ctx),
                                absorbed=False))
    return n + 2 * c["d_model"] * c["vocab_size"]
