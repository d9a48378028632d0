"""Layer parameter tables + apply functions.

Every parameter is declared once in a *table*: ``name -> ParamDef(shape,
axes, scale)`` where ``axes`` are logical axis names ("vocab", "ff",
"experts", "heads", "embed", ...).  The same table drives init (shapes),
sharding (logical->mesh rules in launch/sharding.py), and checkpointing
(leaf paths are stable).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, ATTN_LOCAL, CROSS, DENSE, ENC, MLA, MOE,
                                SSM, LayerSpec, ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (NEG_INF, Dist, axis_index, psum, pmax,
                                 rms_norm, silu)
from repro.models.rope import apply_rope, rope_angles


class ParamDef(NamedTuple):
    shape: tuple
    axes: tuple          # logical axis names, len == len(shape)
    scale: float = -1.0  # -1 -> fan-in default; 0 -> zeros


def _fan_in(shape):
    return 1.0 / math.sqrt(max(1, shape[0]))


# ===========================================================================
# Parameter tables
# ===========================================================================


QUANT_GROUP = 128


def _maybe_quant(cfg: ModelConfig, table: dict) -> dict:
    """Replace eligible 2-D ParamDefs with INT4 packed + scale pairs
    (paper's W4; dequant is VREG-fused, see kernels/int4_matmul.py)."""
    if not cfg.quant_weights:
        return table
    out = {}
    for name, pd in table.items():
        K = pd.shape[0] if pd.shape else 0
        if (len(pd.shape) == 2 and K % QUANT_GROUP == 0
                and pd.shape[1] % 2 == 0 and K * pd.shape[1] >= 1 << 16):
            out[name + "#q"] = ParamDef((K, pd.shape[1] // 2),
                                        (pd.axes[0], pd.axes[1]), -2.0)
            out[name + "#s"] = ParamDef((K // QUANT_GROUP, pd.shape[1]),
                                        (None, pd.axes[1]), -3.0)
        else:
            out[name] = pd
    return out


def _mm(xn, p, name):
    """x @ W with transparent INT4-packed weights: the dequant runs under a
    ``vreg_fused_int4`` scope — the roofline analyzer maps it to the Pallas
    kernel's traffic model (packed bytes cross HBM; fp weights live in
    VREGs only).  Validated against the kernel in tests/test_kernels.py."""
    if name + "#q" in p:
        with jax.named_scope("vreg_fused_int4"):
            from repro.quant.int4 import dequantize_int4
            w = dequantize_int4(p[name + "#q"], p[name + "#s"], xn.dtype,
                                QUANT_GROUP)
        return xn @ w
    return xn @ p[name]


def attn_table(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pre = "c" if cross else ""
    t = {
        pre + "wq": ParamDef((d, h * dh), ("embed", "heads_ff")),
        pre + "wk": ParamDef((d, hkv * dh), ("embed", "kv_ff")),
        pre + "wv": ParamDef((d, hkv * dh), ("embed", "kv_ff")),
        pre + "wo": ParamDef((h * dh, d), ("heads_ff", "embed")),
    }
    t = _maybe_quant(cfg, t)
    if cfg.qk_norm and not cross:
        t["q_norm"] = ParamDef((dh,), (None,), 0.0)
        t["k_norm"] = ParamDef((dh,), (None,), 0.0)
    return t


def mla_table(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dq = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": ParamDef((d, m.q_lora_rank), ("embed", "lora")),
        "q_a_norm": ParamDef((m.q_lora_rank,), (None,), 0.0),
        "wq_b": ParamDef((m.q_lora_rank, h * dq), ("lora", "heads_ff")),
        "wkv_a": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim),
                          ("embed", "lora")),
        "kv_a_norm": ParamDef((m.kv_lora_rank,), (None,), 0.0),
        "w_uk": ParamDef((m.kv_lora_rank, h, m.qk_nope_head_dim),
                         ("lora", "heads", None)),
        "w_uv": ParamDef((m.kv_lora_rank, h, m.v_head_dim),
                         ("lora", "heads", None)),
        "wo": ParamDef((h * m.v_head_dim, d), ("heads_ff", "embed")),
    }


def ssm_table(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    H = d_in // s.head_dim
    gn = s.n_groups * s.d_state
    conv_ch = d_in + 2 * gn
    return {
        "z_proj": ParamDef((d, d_in), ("embed", "ff")),
        "x_proj": ParamDef((d, d_in), ("embed", "ff")),
        "bc_proj": ParamDef((d, 2 * gn), ("embed", None)),
        "dt_proj": ParamDef((d, H), ("embed", "heads")),
        "conv_w": ParamDef((s.d_conv, conv_ch), (None, "ff")),
        "conv_b": ParamDef((conv_ch,), ("ff",), 0.0),
        "A_log": ParamDef((H,), ("heads",), 1.0),
        "D": ParamDef((H,), ("heads",), 1.0),
        "dt_bias": ParamDef((H,), ("heads",), 1.0),
        "ssm_norm": ParamDef((d_in,), ("ff",), 0.0),
        "out_proj": ParamDef((d_in, d), ("ff", "embed")),
    }


def ffn_table(cfg: ModelConfig, spec: LayerSpec) -> dict:
    d = cfg.d_model
    if spec.ffn == DENSE:
        if cfg.d_ff == 0:
            return {}
        return _maybe_quant(cfg, {
            "w_gate": ParamDef((d, cfg.d_ff), ("embed", "ff")),
            "w_up": ParamDef((d, cfg.d_ff), ("embed", "ff")),
            "w_down": ParamDef((cfg.d_ff, d), ("ff", "embed")),
        })
    m = cfg.moe
    # experts sharded over `model` (EP); the per-expert ff dim is *storage*
    # sharded over `data` (ZeRO-3 flavor) — gathered just-in-time in the
    # train path, consumed as partial-sum slices in the decode path.
    t = {
        "wg": ParamDef((d, m.num_experts), ("embed", None)),
        "w_gate": ParamDef((m.num_experts, d, m.expert_d_ff),
                           ("experts", "embed", "expert_ff")),
        "w_up": ParamDef((m.num_experts, d, m.expert_d_ff),
                         ("experts", "embed", "expert_ff")),
        "w_down": ParamDef((m.num_experts, m.expert_d_ff, d),
                           ("experts", "expert_ff", "embed")),
    }
    if m.num_shared:
        sf = m.shared_d_ff * m.num_shared
        t.update({
            "ws_gate": ParamDef((d, sf), ("embed", "ff")),
            "ws_up": ParamDef((d, sf), ("embed", "ff")),
            "ws_down": ParamDef((sf, d), ("ff", "embed")),
        })
    return t


def mixer_table(cfg: ModelConfig, spec: LayerSpec) -> dict:
    if spec.mixer in (ATTN, ATTN_LOCAL, ENC):
        return attn_table(cfg)
    if spec.mixer == CROSS:
        return {**attn_table(cfg), **attn_table(cfg, cross=True),
                "norm_cross": ParamDef((cfg.d_model,), (None,), 0.0)}
    if spec.mixer == MLA:
        return mla_table(cfg)
    if spec.mixer == SSM:
        return ssm_table(cfg)
    raise ValueError(spec.mixer)


def layer_table(cfg: ModelConfig, spec: LayerSpec) -> dict:
    t = {"norm_mixer": ParamDef((cfg.d_model,), (None,), 0.0)}
    t.update(mixer_table(cfg, spec))
    ft = ffn_table(cfg, spec)
    if ft:
        t["norm_ffn"] = ParamDef((cfg.d_model,), (None,), 0.0)
        t.update(ft)
    return t


def padded_vocab(cfg: ModelConfig, multiple: int = 256) -> int:
    """Vocab padded to a mesh-divisible multiple (masked in the LM head);
    covers model-axis sizes up to 256 for any real vocab."""
    return -(-cfg.vocab_size // multiple) * multiple


def embed_table(cfg: ModelConfig) -> dict:
    vp = padded_vocab(cfg)
    t = {"emb": ParamDef((vp, cfg.d_model), ("vocab", "embed"),
                         1.0 / math.sqrt(cfg.d_model))}
    if not cfg.tie_embeddings:
        t["w_out"] = ParamDef((cfg.d_model, vp), ("embed", "vocab"))
    return t


# ===========================================================================
# Layer context: everything apply functions need besides params/x.
# ===========================================================================


@dataclass
class Ctx:
    cfg: ModelConfig
    dist: Dist
    mode: str                        # train | prefill | decode
    angles: Optional[jnp.ndarray] = None    # (s, half) rope angles
    pos: Optional[jnp.ndarray] = None       # scalar decode position
    memory: Optional[jnp.ndarray] = None    # (b, s_enc, d) enc-dec memory
    cache_len: int = 0               # decode/prefill cache allocation length
    is_encoder: bool = False
    batch_size: int = 0              # global batch (0 = assume shardable)
    # lossy-KV roundtrip for the speculative verify pass: applied to the
    # pass's fresh rows that LATER queries attend (they round-trip the
    # host tier between sequential steps); None = cache tier is lossless
    kv_roundtrip: Optional[Any] = None

    @property
    def dp(self):
        """Batch-dim sharding axes; None when the batch can't shard (b=1)."""
        ax = self.dist.data_axes
        if not ax:
            return None
        if self.batch_size and self.dist.is_dist:
            n = 1
            for a in ax:
                n *= self.dist.mesh.shape[a]
            if self.batch_size % n != 0 or self.batch_size < n:
                return None
        return ax if len(ax) > 1 else ax[0]

    def act_spec(self):
        """PartitionSpec dims for (b, s, ...) activations."""
        if self.mode == "decode":
            return (self.dp, None)
        return (self.dp, self.dist.model_axis)

    def seq_axis(self):
        return self.dist.model_axis if self.mode != "decode" else None


def _shard_map(ctx: Ctx, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=ctx.dist.mesh, in_specs=in_specs,
                         out_specs=out_specs)


# ===========================================================================
# Attention layers
# ===========================================================================


def _qkv(p, xn, cfg, pre=""):
    b, s, _ = xn.shape
    q = _mm(xn, p, pre + "wq").reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = _mm(xn, p, pre + "wk").reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = _mm(xn, p, pre + "wv").reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def apply_attention(p, x, ctx: Ctx, cache, spec: LayerSpec):
    cfg = ctx.cfg
    b, s, d = x.shape
    window = cfg.window if spec.mixer == ATTN_LOCAL else 0
    causal = spec.mixer != ENC
    xn = rms_norm(x, p["norm_mixer"], cfg.norm_eps)
    q, k, v = _qkv(p, xn, cfg)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if ctx.angles is not None and spec.mixer != ENC:
        q = apply_rope(q, ctx.angles)
        k = apply_rope(k, ctx.angles)

    new_cache = cache
    if ctx.mode == "decode":
        out, new_cache = _decode_attn(q, k, v, ctx, cache, window)
    else:
        out = _seq_attn(q, k, v, ctx, causal, window)
        if ctx.mode == "prefill":
            new_cache = _build_cache(k, v, ctx, window)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    x = x + _mm(out, p, "wo")
    return x, new_cache


def _seq_attn(q, k, v, ctx: Ctx, causal, window, softcap=0.0):
    """Full-sequence attention: ring over the model axis when distributed."""
    cfg = ctx.cfg
    axis = ctx.seq_axis()
    q_chunk = 512
    if not ctx.dist.is_dist or axis is None:
        return attn.ring_attention(q, k, v, axis=None, causal=causal,
                                   window=window, softcap=softcap,
                                   q_chunk=q_chunk)
    sp = P(ctx.dp, axis, None, None)
    fn = _shard_map(ctx, partial(attn.ring_attention, axis=axis,
                                 causal=causal, window=window,
                                 softcap=softcap, q_chunk=q_chunk),
                    in_specs=(sp, sp, sp), out_specs=sp)
    return fn(q, k, v)


def _build_cache(k, v, ctx: Ctx, window):
    """Prefill: lay k/v into the allocated cache buffer."""
    b, s, hkv, dh = k.shape
    if window:
        W = window
        if s < W:
            pad = W - s
            kw = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vw = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return {"k": kw, "v": vw}
        # rolling buffer invariant: slot j holds the latest position p < s
        # with p % W == j  ->  p_j = s - W + ((j - s % W) % W)
        p_idx = s - W + ((jnp.arange(W) - (s % W)) % W)
        return {"k": jnp.take(k, p_idx, axis=1),
                "v": jnp.take(v, p_idx, axis=1)}
    L = ctx.cache_len or s
    if L == s:
        return {"k": k, "v": v}
    dt = k.dtype
    zk = jnp.zeros((b, L, hkv, dh), dt)
    zv = jnp.zeros((b, L, hkv, dh), dt)
    return {"k": lax.dynamic_update_slice(zk, k, (0, 0, 0, 0)),
            "v": lax.dynamic_update_slice(zv, v, (0, 0, 0, 0))}


def _decode_attn(q, k_new, v_new, ctx: Ctx, cache, window):
    cfg = ctx.cfg
    if q.shape[1] > 1 and not window:
        # speculative verify pass: one ragged decode step appends s = k+1
        # rows (current token + draft proposals) and scores every position
        # through its own causal prefix.  Single-device path only — the
        # speculative engines run Dist.local() and spec_decode_capability
        # gates out window/MLA/SSM mixers.
        out, kc, vc = attn.spec_decode_attention(q, cache["k"], cache["v"],
                                                 k_new, v_new, ctx.pos,
                                                 kv_roundtrip=ctx.kv_roundtrip)
        return out, {"k": kc, "v": vc}
    if window:
        # the window cache is replicated over `model`; without a constraint
        # GSPMD replicates the *updated cache* by all-gathering cache-sized
        # tensors every layer (167 MB x10 on gemma3 decode — the dominant
        # collective).  Constraining the 1-token q/k/v first makes the
        # gather 3 orders of magnitude smaller.  (§Perf B, iteration B1)
        q = ctx.dist.constrain(q, ctx.dp, None, None, None)
        k_new = ctx.dist.constrain(k_new, ctx.dp, None, None, None)
        v_new = ctx.dist.constrain(v_new, ctx.dp, None, None, None)
        ck = ctx.dist.constrain(cache["k"], ctx.dp, None, None, None)
        cv = ctx.dist.constrain(cache["v"], ctx.dp, None, None, None)
        out, kc, vc = attn.local_decode_attention(
            q, ck, cv, k_new, v_new, ctx.pos, window)
        kc = ctx.dist.constrain(kc, ctx.dp, None, None, None)
        vc = ctx.dist.constrain(vc, ctx.dp, None, None, None)
        return out, {"k": kc, "v": vc}
    axes = ctx.dist.kv_shard_axes
    if not ctx.dist.is_dist or not axes:
        out, kc, vc = attn.decode_attention(q, cache["k"], cache["v"],
                                            k_new, v_new, ctx.pos, axes=())
        return out, {"k": kc, "v": vc}
    dp = ctx.dp
    b_spec = None if (len(axes) > 1) else dp   # long_500k: batch replicated
    qsp = P(b_spec, None, None, None)
    csp = P(b_spec, axes if len(axes) > 1 else axes[0], None, None)
    fn = _shard_map(ctx, partial(attn.decode_attention, axes=axes),
                    in_specs=(qsp, csp, csp, qsp, qsp, P()),
                    out_specs=(qsp, csp, csp))
    out, kc, vc = fn(q, cache["k"], cache["v"], k_new, v_new, ctx.pos)
    return out, {"k": kc, "v": vc}


def apply_layer_chunk(p, x, ctx: Ctx, prefix_k, prefix_v, q_offset: int):
    """One dense global-attention layer applied to a prefill CHUNK.

    ``x`` holds the chunk's rows (global positions ``q_offset ..``);
    ``prefix_k``/``prefix_v`` are the engine-held FRESH K/V of the
    earlier chunks (post-rope, compute precision — the same values the
    monolithic ``apply_attention`` prefill would have in-pass, NOT the
    cache-tier copies).  Every op here is the row-wise twin of the
    ``apply_attention`` prefill path, so the chunk's output rows equal
    the monolithic pass's rows bit for bit (``chunked_prefill_capability``
    gates callers to ATTN mixers + dense FFN).  Returns ``(x, k, v)``
    with the chunk's fresh rope'd K/V for the caller to extend the
    prefix and append to the KV store."""
    cfg = ctx.cfg
    b, s, d = x.shape
    xn = rms_norm(x, p["norm_mixer"], cfg.norm_eps)
    q, k, v = _qkv(p, xn, cfg)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if ctx.angles is not None:
        q = apply_rope(q, ctx.angles)
        k = apply_rope(k, ctx.angles)
    kk = k if prefix_k is None else jnp.concatenate([prefix_k, k], axis=1)
    vv = v if prefix_v is None else jnp.concatenate([prefix_v, v], axis=1)
    out = attn.chunk_prefill_attention(q, kk, vv, q_offset=q_offset,
                                       q_chunk=512)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    x = x + _mm(out, p, "wo")
    x, _ = apply_dense_ffn(p, x, ctx)
    return x, k, v


# ===========================================================================
# Cross-attention (whisper decoder)
# ===========================================================================


def apply_cross_layer(p, x, ctx: Ctx, cache, spec: LayerSpec):
    """Decoder layer: causal self-attn + cross-attn over encoder memory."""
    cfg = ctx.cfg
    b, s, d = x.shape
    # self attention (reuses apply_attention mechanics)
    x, new_cache = apply_attention(p, x, ctx, cache, LayerSpec(ATTN, spec.ffn))
    # cross attention
    xn = rms_norm(x, p["norm_cross"], cfg.norm_eps)
    q = (xn @ p["cwq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    if ctx.mode == "decode":
        ck, cv = cache["ck"], cache["cv"]
        new_cache = {**new_cache, "ck": ck, "cv": cv}
    else:
        mem = ctx.memory
        sm = mem.shape[1]
        ck = (mem @ p["cwk"]).reshape(b, sm, cfg.num_kv_heads, cfg.head_dim)
        cv = (mem @ p["cwv"]).reshape(b, sm, cfg.num_kv_heads, cfg.head_dim)
        if ctx.mode == "prefill":
            new_cache = {**new_cache, "ck": ck, "cv": cv}
    out = attn.ref_attention(q, ck, cv, causal=False)
    x = x + out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["cwo"]
    return x, new_cache


# ===========================================================================
# MLA (DeepSeek)
# ===========================================================================


def apply_mla(p, x, ctx: Ctx, cache, spec: LayerSpec):
    cfg = ctx.cfg
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    xn = rms_norm(x, p["norm_mixer"], cfg.norm_eps)

    qa = rms_norm(xn @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
    qb = (qa @ p["wq_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = qb[..., :dn], qb[..., dn:]
    kv_a = xn @ p["wkv_a"]                                # (b, s, r + dr)
    c = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]                   # (b, s, dr)
    if ctx.angles is not None:
        q_rope = apply_rope(q_rope, ctx.angles)
        k_rope = apply_rope(k_rope[:, :, None, :], ctx.angles)[:, :, 0]
    scale = 1.0 / math.sqrt(dn + dr)

    new_cache = cache
    if ctx.mode == "decode":
        # absorbed path over the latent cache
        q_eff = jnp.einsum("bshn,rhn->bshr", q_nope, p["w_uk"])
        axes = ctx.dist.kv_shard_axes if ctx.dist.is_dist else ()
        if axes:
            dp = ctx.dp
            b_spec = None if len(axes) > 1 else dp
            qsp = P(b_spec, None, None, None)
            csp = P(b_spec, axes if len(axes) > 1 else axes[0], None)
            nsp = P(b_spec, None, None)
            fn = _shard_map(ctx, partial(attn.mla_decode_attention,
                                         scale=scale, axes=axes),
                            in_specs=(qsp, qsp, csp, csp, nsp, nsp, P()),
                            out_specs=(qsp, csp, csp))
            ctxl, cc, krc = fn(q_eff, q_rope, cache["c"], cache["kr"],
                               c, k_rope, ctx.pos)
        else:
            ctxl, cc, krc = attn.mla_decode_attention(
                q_eff, q_rope, cache["c"], cache["kr"], c, k_rope, ctx.pos,
                scale=scale, axes=())
        new_cache = {"c": cc, "kr": krc}
        out = jnp.einsum("bshr,rhv->bshv", ctxl.astype(x.dtype), p["w_uv"])
    else:
        # MLA-aware ring: rotate the 576-dim latent, expand per step in the
        # ring body (71x less ICI than rotating expanded K/V — §Perf C1).
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        axis = ctx.seq_axis()
        if ctx.dist.is_dist and axis is not None:
            sp_q = P(ctx.dp, axis, None, None)
            sp_c = P(ctx.dp, axis, None)
            fn = _shard_map(ctx, partial(attn.mla_ring_attention, axis=axis),
                            in_specs=(sp_q, sp_c, sp_c,
                                      P(None, None, None), P(None, None, None)),
                            out_specs=sp_q)
            out = fn(q, c, k_rope, p["w_uk"], p["w_uv"])
        else:
            out = attn.mla_ring_attention(q, c, k_rope, p["w_uk"], p["w_uv"],
                                          axis=None)
        if ctx.mode == "prefill":
            L = ctx.cache_len or s
            cc = jnp.zeros((b, L, m.kv_lora_rank), x.dtype)
            krc = jnp.zeros((b, L, dr), x.dtype)
            new_cache = {
                "c": lax.dynamic_update_slice(cc, c.astype(x.dtype), (0, 0, 0)),
                "kr": lax.dynamic_update_slice(krc, k_rope.astype(x.dtype),
                                               (0, 0, 0))}
    x = x + out.reshape(b, s, h * dv) @ p["wo"]
    return x, new_cache


# ===========================================================================
# SSM layer (Mamba2)
# ===========================================================================


def _pick_chunk(length: int, target: int) -> int:
    """Largest divisor of ``length`` that is <= target (static)."""
    for c in range(min(target, length), 0, -1):
        if length % c == 0:
            return c
    return 1


def _causal_conv(x, w, b, halo=None):
    """Depthwise causal conv via shifted adds.  x: (b, l, ch); w: (width, ch);
    halo: (b, width-1, ch) previous context or None (zeros)."""
    width = w.shape[0]
    if halo is None:
        halo = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([halo, x], axis=1)
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + xp[:, i:i + x.shape[1]] * w[i]
    return out + b


def apply_ssm(p, x, ctx: Ctx, cache, spec: LayerSpec):
    cfg = ctx.cfg
    s_cfg = cfg.ssm
    b, l, d = x.shape
    d_in = s_cfg.expand * d
    H = d_in // s_cfg.head_dim
    hd = s_cfg.head_dim
    G, N = s_cfg.n_groups, s_cfg.d_state
    gn = G * N
    xn = rms_norm(x, p["norm_mixer"], cfg.norm_eps)

    z = xn @ p["z_proj"]                                  # (b, l, d_in)
    xin = xn @ p["x_proj"]
    bc = xn @ p["bc_proj"]                                # (b, l, 2gn)
    dt_raw = xn @ p["dt_proj"]                            # (b, l, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    conv_in = jnp.concatenate([xin, bc], axis=-1)         # (b, l, conv_ch)
    new_cache = cache
    if ctx.mode == "decode":
        halo = cache["conv"]                              # (b, width-1, ch)
        conv = _causal_conv(conv_in, p["conv_w"], p["conv_b"], halo)
        new_halo = jnp.concatenate([halo, conv_in], axis=1)[:, 1:]
        conv = silu(conv)
        xc = conv[..., :d_in].reshape(b, H, hd)
        Bc = conv[..., d_in:d_in + gn].reshape(b, G, N)
        Cc = conv[..., d_in + gn:].reshape(b, G, N)
        dt = jax.nn.softplus(dt_raw[:, 0] + p["dt_bias"])  # (b, H)
        y, h_new = ssm_mod.ssd_decode_step(xc, dt, A, Bc, Cc, cache["state"])
        y = y + xc.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
        y = y.reshape(b, 1, d_in)
        new_cache = {"conv": new_halo, "state": h_new.astype(jnp.float32)}
    else:
        axis = ctx.seq_axis()
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])

        def inner(conv_in, dt):
            # inside shard_map: fetch conv halo from previous shard
            if axis is not None:
                tail = conv_in[:, -(s_cfg.d_conv - 1):]
                from repro.models.common import axis_size as _axis_size
                prev = lax.ppermute(
                    tail, axis,
                    [(i, i + 1) for i in range(_axis_size(axis) - 1)])
            else:
                prev = None
            conv = silu(_causal_conv(conv_in, p["conv_w"], p["conv_b"], prev))
            bl, ll = conv.shape[0], conv.shape[1]   # local shapes (shard_map)
            xc = conv[..., :d_in].reshape(bl, ll, H, hd)
            Bc = conv[..., d_in:d_in + gn].reshape(bl, ll, G, N)
            Cc = conv[..., d_in + gn:].reshape(bl, ll, G, N)
            y, h_fin = ssm_mod.ssd_sharded(xc, dt, A, Bc, Cc,
                                           _pick_chunk(ll, s_cfg.chunk_size),
                                           axis)
            y = y + xc.astype(jnp.float32) * p["D"].astype(
                jnp.float32)[:, None]
            return y.reshape(bl, ll, d_in), h_fin

        if ctx.dist.is_dist and axis is not None:
            sp2 = P(ctx.dp, axis, None)
            fn = _shard_map(ctx, inner,
                            in_specs=(sp2, sp2),
                            out_specs=(sp2, P(ctx.dp, None, None, None)))
            y, h_fin = fn(conv_in, dt)
        else:
            y, h_fin = inner(conv_in, dt)
        if ctx.mode == "prefill":
            width = s_cfg.d_conv
            new_cache = {"conv": conv_in[:, -(width - 1):],
                         "state": h_fin.astype(jnp.float32)}

    # gated RMSNorm + out projection
    y = rms_norm(y.astype(x.dtype) * silu(z), p["ssm_norm"], cfg.norm_eps)
    x = x + y @ p["out_proj"]
    return x, new_cache


# ===========================================================================
# FFN layers
# ===========================================================================


def apply_dense_ffn(p, x, ctx: Ctx):
    cfg = ctx.cfg
    if cfg.d_ff == 0 or "w_gate" not in p:
        return x, jnp.float32(0.0)
    xn = rms_norm(x, p["norm_ffn"], cfg.norm_eps)
    h = silu(_mm(xn, p, "w_gate")) * _mm(xn, p, "w_up")
    return x + _mm(h, p, "w_down"), jnp.float32(0.0)


def _moe_ff_axis(ctx: Ctx):
    """The mesh axis the expert ff dim is storage-sharded over, or None.
    Must mirror the divisibility rule in launch/sharding.py::AXIS_RULES."""
    if not ctx.dist.is_dist or "data" not in ctx.dist.mesh.axis_names:
        return None
    f = ctx.cfg.moe.expert_d_ff
    n = ctx.dist.mesh.shape["data"]
    return "data" if (f % n == 0 and f >= n) else None


def _dequant_moe_stacks(p, dtype):
    """INT4-resident MoE (plan ``moe_quant='int4'``): the routed expert
    stacks arrive packed (``w_gate#q``/``#s`` etc., per
    ``QuantPolicy.prepare_moe_params``) — unpack them under the
    ``vreg_fused_int4`` scope so the roofline analyzer prices packed
    bytes as the HBM traffic, same as the 2-D ``_mm`` path.  The router
    (``wg``) and shared experts stay at compute precision."""
    if "w_gate#q" not in p:
        return p
    from repro.quant.int4 import dequantize_int4_stack
    out = dict(p)
    with jax.named_scope("vreg_fused_int4"):
        for name in ("w_gate", "w_up", "w_down"):
            q, s = out.pop(name + "#q"), out.pop(name + "#s")
            out[name] = dequantize_int4_stack(q, s, dtype)
    return out


def apply_moe_ffn(p, x, ctx: Ctx):
    cfg = ctx.cfg
    m = cfg.moe
    b, s, d = x.shape
    xn = rms_norm(x, p["norm_ffn"], cfg.norm_eps)
    p = _dequant_moe_stacks(p, xn.dtype)
    moe_params = {k: p[k] for k in ("wg", "w_gate", "w_up", "w_down")}

    axis = ctx.dist.model_axis if ctx.dist.is_dist else None
    ff_axis = _moe_ff_axis(ctx) if axis is not None else None
    w_specs = (P(None, None),
               P(axis, None, ff_axis), P(axis, None, ff_axis),
               P(axis, ff_axis, None))

    if axis is None:
        out, aux = moe_mod.moe_ffn(xn.reshape(b * s, d), moe_params, m,
                                   axis=None)
        out = out.reshape(b, s, d)
    elif ctx.mode == "decode":
        if ff_axis is not None:
            # combine over exactly the sharded axes (ff partial-sums over
            # `data`, cross-expert over `model`); the pod axis is pure DP
            # with replicated x/weights — no reduction there.
            combine = (ff_axis, axis)

            def body(xn_, wg, wga, wup, wdn):
                T = xn_.shape[0] * xn_.shape[1]
                o, a = moe_mod.moe_ffn_decode(
                    xn_.reshape(T, d),
                    dict(wg=wg, w_gate=wga, w_up=wup, w_down=wdn), m,
                    ep_axis=axis, ff_axis=ff_axis, combine_axes=combine)
                return o.reshape(xn_.shape), a[None]
            fn = _shard_map(ctx, body,
                            in_specs=(P(None, None, None),) + w_specs,
                            out_specs=(P(None, None, None), P(None)))
        else:
            def body(xn_, wg, wga, wup, wdn):
                T = xn_.shape[0] * xn_.shape[1]
                o, a = moe_mod.moe_ffn_replicated(
                    xn_.reshape(T, d), dict(wg=wg, w_gate=wga, w_up=wup,
                                            w_down=wdn), m, axis=axis)
                if ctx.dp:
                    a = lax.pmean(a, ctx.dist.data_axes)
                return o.reshape(xn_.shape), a[None]
            fn = _shard_map(ctx, body,
                            in_specs=(P(ctx.dp, None, None),) + w_specs,
                            out_specs=(P(ctx.dp, None, None), P(None)))
        out, aux = fn(xn, *(moe_params[k] for k in
                            ("wg", "w_gate", "w_up", "w_down")))
        aux = aux[0]
    else:
        P_model = ctx.dist.model_size
        T_loc = (b // max(1, _dp_size(ctx) if ctx.dp else 1)) * (s // P_model)
        capacity = int(m.capacity_factor * T_loc * m.top_k / m.num_experts) + 1

        def body(xn_, wg, wga, wup, wdn):
            bl, sl, _ = xn_.shape
            if ff_axis is not None:
                # JIT FSDP gather of this layer's expert slices (ZeRO-3)
                wga = lax.all_gather(wga, ff_axis, axis=2, tiled=True)
                wup = lax.all_gather(wup, ff_axis, axis=2, tiled=True)
                wdn = lax.all_gather(wdn, ff_axis, axis=1, tiled=True)
            o, a = moe_mod.moe_ffn(
                xn_.reshape(bl * sl, d),
                dict(wg=wg, w_gate=wga, w_up=wup, w_down=wdn), m,
                axis=axis, capacity=capacity)
            a = lax.pmean(a, ctx.dist.data_axes + (axis,)) if ctx.dp \
                else lax.pmean(a, axis)
            return o.reshape(bl, sl, d), a[None]
        fn = _shard_map(ctx, body,
                        in_specs=(P(ctx.dp, axis, None),) + w_specs,
                        out_specs=(P(ctx.dp, axis, None), P(None)))
        out, aux = fn(xn, *(moe_params[k] for k in
                            ("wg", "w_gate", "w_up", "w_down")))
        aux = aux[0]

    x = x + out
    if m.num_shared:
        h = silu(xn @ p["ws_gate"]) * (xn @ p["ws_up"])
        x = x + h @ p["ws_down"]
    return x, jnp.mean(aux)


def _dp_size(ctx: Ctx):
    n = 1
    for a in ctx.dist.data_axes:
        n *= ctx.dist.mesh.shape[a]
    return n


# ===========================================================================
# Whole layer
# ===========================================================================


def apply_layer(p, x, ctx: Ctx, cache, spec: LayerSpec):
    if spec.mixer in (ATTN, ATTN_LOCAL, ENC):
        x, new_cache = apply_attention(p, x, ctx, cache, spec)
    elif spec.mixer == CROSS:
        x, new_cache = apply_cross_layer(p, x, ctx, cache, spec)
    elif spec.mixer == MLA:
        x, new_cache = apply_mla(p, x, ctx, cache, spec)
    elif spec.mixer == SSM:
        x, new_cache = apply_ssm(p, x, ctx, cache, spec)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn == MOE:
        x, aux = apply_moe_ffn(p, x, ctx)
    else:
        x, aux = apply_dense_ffn(p, x, ctx)
    return x, new_cache, aux


# ===========================================================================
# Embedding / LM head (vocab-sharded)
# ===========================================================================


def embed_tokens(p, tokens, ctx: Ctx):
    """tokens (b, s) -> (b, s, d); vocab-sharded masked-psum lookup."""
    cfg = ctx.cfg
    axis = ctx.dist.model_axis if ctx.dist.is_dist else None
    if axis is None:
        return jnp.take(p["emb"], tokens, axis=0)

    s_sharded = ctx.mode != "decode"

    def body(emb_loc, tok):
        V_loc = emb_loc.shape[0]
        start = lax.axis_index(axis) * V_loc
        if s_sharded:
            # tokens are s-sharded on the SAME axis as the vocab: every
            # shard must see every token (a shard can only resolve ids in
            # its own vocab slice) -> gather tokens (cheap ints), emit
            # partials for the full s, then reduce-scatter back to s-shards
            # (comm = 1/P of a full psum).
            tok = lax.all_gather(tok, axis, axis=1, tiled=True)
        rel = tok - start
        ok = (rel >= 0) & (rel < V_loc)
        e = jnp.take(emb_loc, jnp.clip(rel, 0, V_loc - 1), axis=0)
        e = jnp.where(ok[..., None], e, 0)
        if s_sharded:
            return lax.psum_scatter(e, axis, scatter_dimension=1, tiled=True)
        return lax.psum(e, axis)

    s_spec = ctx.dist.model_axis if s_sharded else None
    fn = _shard_map(ctx, body,
                    in_specs=(P(axis, None), P(ctx.dp, s_spec)),
                    out_specs=P(ctx.dp, s_spec, None))
    return fn(p["emb"], tokens)


def _w_out(p, cfg):
    return p["emb"].T if cfg.tie_embeddings else p["w_out"]


def lm_head_loss(p, x, labels, ctx: Ctx, s_chunk: int = 512):
    """Mean token cross-entropy with a vocab-sharded head.

    Distributed: x (b, s@model, d) is all-gathered over model, logits are
    computed per vocab shard in s-chunks, and the softmax statistics are
    psum-merged — full logits are never materialized globally.
    """
    cfg = ctx.cfg
    axis = ctx.dist.model_axis if ctx.dist.is_dist else None
    w = _w_out(p, cfg)
    V = cfg.vocab_size                                 # real vocab; pad masked

    if axis is None:
        logits = (x @ w).astype(jnp.float32)
        logits = jnp.where(jnp.arange(logits.shape[-1]) < V, logits, NEG_INF)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll)

    def body(x_loc, w_loc, labels_loc):
        # x_loc (b_loc, s_loc, d) -> gather full s on every model shard
        x_all = lax.all_gather(x_loc, axis, axis=1, tiled=True)
        lab = lax.all_gather(labels_loc, axis, axis=1, tiled=True)
        V_loc = w_loc.shape[1]
        start = lax.axis_index(axis) * V_loc
        pad_mask = (start + jnp.arange(V_loc)) < V     # mask vocab padding
        b_loc, s, d = x_all.shape
        n = max(1, s // s_chunk) if s % s_chunk == 0 else 1
        cs = s // n

        def chunk(args):
            xc, lc = args                              # (b, cs, d), (b, cs)
            lg = (xc @ w_loc).astype(jnp.float32)      # (b, cs, V_loc)
            lg = jnp.where(pad_mask, lg, NEG_INF)
            m = pmax(jnp.max(lg, axis=-1), axis)       # stop-grad pmax (exact)
            se = lax.psum(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1), axis)
            lse = m + jnp.log(se)
            rel = lc - start
            ok = (rel >= 0) & (rel < V_loc)
            ll = jnp.take_along_axis(
                lg, jnp.clip(rel, 0, V_loc - 1)[..., None], axis=-1)[..., 0]
            ll = lax.psum(jnp.where(ok, ll, 0.0), axis)
            return lse - ll

        xs = (jnp.moveaxis(x_all.reshape(b_loc, n, cs, d), 1, 0),
              jnp.moveaxis(lab.reshape(b_loc, n, cs), 1, 0))
        losses = lax.map(chunk, xs)                    # (n, b, cs)
        loss = jnp.mean(losses)
        # already invariant over `axis` (psum-reduced); average over data
        return lax.pmean(loss, ctx.dist.data_axes)[None]

    fn = _shard_map(ctx, body,
                    in_specs=(P(ctx.dp, axis, None), P(None, axis),
                              P(ctx.dp, axis)),
                    out_specs=P(None))
    return fn(x, w, labels)[0]


def lm_head_argmax(p, x, ctx: Ctx):
    """Greedy next token from the last position.  x: (b, 1, d) -> (b,)."""
    cfg = ctx.cfg
    axis = ctx.dist.model_axis if ctx.dist.is_dist else None
    w = _w_out(p, cfg)
    V = cfg.vocab_size
    if axis is None:
        logits = (x[:, -1] @ w).astype(jnp.float32)
        logits = jnp.where(jnp.arange(logits.shape[-1]) < V, logits, NEG_INF)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def body(x_loc, w_loc):
        V_loc = w_loc.shape[1]
        start = lax.axis_index(axis) * V_loc
        lg = (x_loc[:, -1] @ w_loc).astype(jnp.float32)   # (b_loc, V_loc)
        lg = jnp.where((start + jnp.arange(V_loc)) < V, lg, NEG_INF)
        m_loc = jnp.max(lg, axis=-1)
        i_loc = jnp.argmax(lg, axis=-1).astype(jnp.int32) + start
        m = lax.pmax(m_loc, axis)
        idx = lax.pmax(jnp.where(m_loc >= m, i_loc, -1), axis)
        return idx

    fn = _shard_map(ctx, body,
                    in_specs=(P(ctx.dp, None, None), P(None, axis)),
                    out_specs=P(ctx.dp))
    return fn(x, w)
