"""Model assembly: parameter init from tables, period-scan layer stack,
train/prefill/decode entry points.

Layer-pattern scan: each *pattern position* holds its params stacked over
``num_periods`` (leading axis), and ``lax.scan`` iterates periods with the
heterogeneous pattern unrolled inside the body.  This keeps the HLO small
(one period body) for 36–80 layer models — critical for 512-device SPMD
compile times — while supporting heterogeneous stacks (gemma3 local:global,
jamba SSM/attn/MoE interleave).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ATTN, ATTN_LOCAL, CROSS, DENSE, ENC, MLA, MOE,
                                SSM, LayerSpec, ModelConfig)
from repro.models import layers as L
from repro.models.common import Dist, init_leaf
from repro.models.rope import rope_angles, sinusoidal_positions

AUX_WEIGHT = 0.01  # load-balance loss weight


# ===========================================================================
# Tables & init
# ===========================================================================


def model_tables(cfg: ModelConfig):
    t = {
        "embed": L.embed_table(cfg),
        "final_norm": {"scale": L.ParamDef((cfg.d_model,), (None,), 0.0)},
        "pat": tuple(L.layer_table(cfg, s) for s in cfg.pattern),
        "rem": tuple(L.layer_table(cfg, s) for s in cfg.remainder),
    }
    if cfg.enc_dec:
        enc_spec = LayerSpec(ENC, DENSE)
        t["enc"] = {
            "pat": (L.layer_table(cfg, enc_spec),),
            "final_norm": {"scale": L.ParamDef((cfg.d_model,), (None,), 0.0)},
        }
    return t


def _init_entry(key, name, pd: L.ParamDef, stack: int, dtype):
    shape = ((stack,) + pd.shape) if stack else pd.shape
    if name.endswith("#q"):      # packed INT4 weights
        return jax.random.randint(key, shape, 0, 255, jnp.uint8)
    if name.endswith("#s"):      # groupwise scales
        return jax.random.uniform(key, shape, jnp.float32, 1e-3, 2e-3)
    if name == "A_log":
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(jnp.float32)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return jnp.log(jnp.expm1(u)).astype(jnp.float32)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    scale = pd.scale if pd.scale >= 0 else 1.0 / math.sqrt(max(1, pd.shape[0] if not stack else pd.shape[0]))
    # fan-in for matrices: first non-stacked dim
    if pd.scale < 0:
        fan = pd.shape[0] if len(pd.shape) > 1 else pd.shape[0]
        scale = 1.0 / math.sqrt(max(1, fan))
    return init_leaf(key, shape, scale, dtype)


def _init_table(table, key, stack: int, dtype):
    out = {}
    for i, (name, pd) in enumerate(sorted(table.items())):
        out[name] = _init_entry(jax.random.fold_in(key, i), name, pd, stack,
                                dtype)
    return out


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    tabs = model_tables(cfg)
    params = {
        "embed": _init_table(tabs["embed"], jax.random.fold_in(key, 0), 0,
                             dtype),
        "final_norm": _init_table(tabs["final_norm"],
                                  jax.random.fold_in(key, 1), 0, dtype),
        "pat": tuple(
            _init_table(t, jax.random.fold_in(key, 10 + i), cfg.num_periods,
                        dtype)
            for i, t in enumerate(tabs["pat"])),
        "rem": tuple(
            _init_table(t, jax.random.fold_in(key, 100 + i), 0, dtype)
            for i, t in enumerate(tabs["rem"])),
    }
    if cfg.enc_dec:
        params["enc"] = {
            "pat": tuple(
                _init_table(t, jax.random.fold_in(key, 200 + i),
                            cfg.num_encoder_layers, dtype)
                for i, t in enumerate(tabs["enc"]["pat"])),
            "final_norm": _init_table(tabs["enc"]["final_norm"],
                                      jax.random.fold_in(key, 299), 0, dtype),
        }
    return params


# SSM scalars kept at f32 whatever the stated precision (``param_struct``)
F32_NAMES = ("A_log", "dt_bias", "D")


def served_weight_dtype(cfg: ModelConfig, quant: Optional[str] = None) -> str:
    """The dtype a serving engine holds and streams a decoder layer's
    matrices at: the configuration's stated precision, or f32 for units
    streamed as INT4 (``quant="int4"``), which quantize from the f32 draw
    and dequantize back to f32."""
    return "float32" if quant == "int4" else jnp.dtype(cfg.dtype).name


def served_dtype(cfg: ModelConfig, name: str, quant: Optional[str] = None):
    """The dtype of decoder layer tensor ``name`` in a serving engine: a
    MoE router at ``router_dtype``, packed INT4 leaves, their scales and
    the SSM scalars as drawn, every other tensor (experts included) at
    ``served_weight_dtype``."""
    if name == "wg" and cfg.moe is not None:
        return jnp.dtype(cfg.moe.router_dtype)
    if name.endswith("#q"):
        return jnp.dtype(jnp.uint8)
    if name.endswith("#s") or name in F32_NAMES:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(served_weight_dtype(cfg, quant))


def served_layer(cfg: ModelConfig, tensors: dict,
                 quant: Optional[str] = None) -> dict:
    """One layer's tensors (name -> device or host array, drawn at f32)
    rounded once, to nearest even, to their ``served_dtype``.  The one
    rule every serving engine applies to the weights it draws, so that
    their tokens agree; a tensor already at its dtype is returned as is."""
    out = {}
    for name, a in tensors.items():
        dt = served_dtype(cfg, name, quant)
        out[name] = a if a.dtype == dt else a.astype(dt)
    return out


def served_params(cfg: ModelConfig, params):
    """``init_params`` output as a resident serving engine holds it: the
    ``pat``/``rem`` layer tensors through ``served_layer``; the embedding
    and the final norm as drawn."""
    return {**params,
            "pat": tuple(served_layer(cfg, t) for t in params["pat"]),
            "rem": tuple(served_layer(cfg, t) for t in params["rem"])}


def map_params_tree(cfg: ModelConfig, fn):
    """Build a pytree with the exact structure of ``init_params`` output,
    with leaf = fn(name, ParamDef, stacked: bool)."""
    tabs = model_tables(cfg)

    def tab(t, stacked):
        return {name: fn(name, pd, stacked) for name, pd in t.items()}

    out = {
        "embed": tab(tabs["embed"], False),
        "final_norm": tab(tabs["final_norm"], False),
        "pat": tuple(tab(t, True) for t in tabs["pat"]),
        "rem": tuple(tab(t, False) for t in tabs["rem"]),
    }
    if cfg.enc_dec:
        out["enc"] = {
            "pat": tuple(tab(t, True) for t in tabs["enc"]["pat"]),
            "final_norm": tab(tabs["enc"]["final_norm"], False),
        }
    return out


def param_struct(cfg: ModelConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree matching init_params (fp32 SSM scalars)."""
    def fn(name, pd, stacked):
        stack = (cfg.num_encoder_layers if False else
                 (cfg.num_periods if stacked else 0))
        shape = ((stack,) + pd.shape) if stack else pd.shape
        if name.endswith("#q"):
            dt = jnp.uint8
        elif name.endswith("#s") or name in F32_NAMES:
            dt = jnp.float32
        else:
            dt = dtype
        return jax.ShapeDtypeStruct(shape, dt)

    tree = map_params_tree(cfg, fn)
    if cfg.enc_dec:
        # encoder stacks over num_encoder_layers, not num_periods
        def fn_enc(name, pd, stacked):
            shape = ((cfg.num_encoder_layers,) + pd.shape) if stacked else pd.shape
            dt = jnp.float32 if name in F32_NAMES else dtype
            return jax.ShapeDtypeStruct(shape, dt)
        tabs = model_tables(cfg)
        tree["enc"]["pat"] = tuple(
            {name: fn_enc(name, pd, True) for name, pd in t.items()}
            for t in tabs["enc"]["pat"])
    return tree


def param_axes(cfg: ModelConfig):
    """Same pytree structure as params, leaves = logical axes tuples."""
    tabs = model_tables(cfg)

    def tab_axes(table, stacked):
        return {name: ((None,) + pd.axes if stacked else pd.axes)
                for name, pd in table.items()}

    out = {
        "embed": tab_axes(tabs["embed"], False),
        "final_norm": tab_axes(tabs["final_norm"], False),
        "pat": tuple(tab_axes(t, True) for t in tabs["pat"]),
        "rem": tuple(tab_axes(t, False) for t in tabs["rem"]),
    }
    if cfg.enc_dec:
        out["enc"] = {
            "pat": tuple(tab_axes(t, True) for t in tabs["enc"]["pat"]),
            "final_norm": tab_axes(tabs["enc"]["final_norm"], False),
        }
    return out


# ===========================================================================
# Caches
# ===========================================================================


def _layer_cache_shape(cfg: ModelConfig, spec: LayerSpec, b: int, L_: int):
    """dict name -> (shape, dtype, kind) for one layer; kind tags the
    sharding rule ('kv' = sequence-sharded, 'rep' = replicated)."""
    dh, hkv = cfg.head_dim, cfg.num_kv_heads
    bf = jnp.bfloat16
    if spec.mixer == ATTN:
        return {"k": ((b, L_, hkv, dh), bf, "kv"),
                "v": ((b, L_, hkv, dh), bf, "kv")}
    if spec.mixer == ATTN_LOCAL:
        W = cfg.window
        return {"k": ((b, W, hkv, dh), bf, "rep"),
                "v": ((b, W, hkv, dh), bf, "rep")}
    if spec.mixer == MLA:
        m = cfg.mla
        return {"c": ((b, L_, m.kv_lora_rank), bf, "kv"),
                "kr": ((b, L_, m.qk_rope_head_dim), bf, "kv")}
    if spec.mixer == SSM:
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        H = d_in // s.head_dim
        conv_ch = d_in + 2 * s.n_groups * s.d_state
        return {"conv": ((b, s.d_conv - 1, conv_ch), bf, "rep"),
                "state": ((b, H, s.head_dim, s.d_state), jnp.float32, "state")}
    if spec.mixer == CROSS:
        enc_s = cfg.encoder_seq_len
        return {"k": ((b, L_, hkv, dh), bf, "kv"),
                "v": ((b, L_, hkv, dh), bf, "kv"),
                "ck": ((b, enc_s, hkv, dh), bf, "rep"),
                "cv": ((b, enc_s, hkv, dh), bf, "rep")}
    raise ValueError(spec.mixer)


def cache_struct(cfg: ModelConfig, b: int, cache_len: int, enc_len=None):
    """ShapeDtypeStruct pytree of the decode cache (+ kind tree)."""
    def one(spec, stack):
        shapes = _layer_cache_shape(cfg, spec, b, cache_len)
        if enc_len is not None and spec.mixer == CROSS:
            shapes = {k: (((v[0][0], enc_len) + v[0][2:]) if k in ("ck", "cv")
                          else v[0], v[1], v[2]) for k, v in shapes.items()}
        sds = {k: jax.ShapeDtypeStruct(((stack,) + s) if stack else s, d)
               for k, (s, d, _) in shapes.items()}
        kinds = {k: kind for k, (_, _, kind) in shapes.items()}
        return sds, kinds
    pat, pat_kinds = [], []
    for spec in cfg.pattern:
        s, k = one(spec, cfg.num_periods)
        pat.append(s)
        pat_kinds.append(k)
    rem, rem_kinds = [], []
    for spec in cfg.remainder:
        s, k = one(spec, 0)
        rem.append(s)
        rem_kinds.append(k)
    return ({"pat": tuple(pat), "rem": tuple(rem)},
            {"pat": tuple(pat_kinds), "rem": tuple(rem_kinds)})


def init_cache(cfg: ModelConfig, b: int, cache_len: int, enc_len=None):
    struct, _ = cache_struct(cfg, b, cache_len, enc_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


# ===========================================================================
# Forward passes
# ===========================================================================


def _angles(cfg: ModelConfig, positions):
    if cfg.rope_theta == 0:
        return None
    rope_dim = (cfg.mla.qk_rope_head_dim if cfg.mla is not None
                else cfg.head_dim)
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(positions, (3,) + positions.shape)
        return rope_angles(pos3, rope_dim, cfg.rope_theta,
                           cfg.mrope_sections)
    return rope_angles(positions, rope_dim, cfg.rope_theta)


def _run_stack(params, x, ctx: L.Ctx, caches, cfg: ModelConfig,
               pattern, remainder, remat: bool):
    aux0 = jnp.float32(0.0)
    empty = caches is None
    pat_caches = (tuple({} for _ in pattern) if empty else caches["pat"])
    rem_caches = (tuple({} for _ in remainder) if empty else caches["rem"])

    def body(carry, xs):
        x, aux = carry
        ps, cs = xs
        # Barrier on the per-period param slices: without it, XLA:CPU hoists
        # the bf16->f32 dot-operand converts of loop-invariant stacked params
        # out of the while loop, doubling resident param memory (observed on
        # jamba/deepseek: +100GiB/device).  TPU has native bf16 dots; the
        # barrier is a no-op for performance there.
        ps = lax.optimization_barrier(ps)
        new_cs = []
        for idx, spec in enumerate(pattern):
            x, nc, a = L.apply_layer(ps[idx], x, ctx,
                                     cs[idx] if not empty else None, spec)
            new_cs.append(nc if nc is not None else {})
            aux = aux + a
        return (x, aux), tuple(new_cs)

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), new_pat = lax.scan(body, (x, aux0), (params["pat"], pat_caches))

    new_rem = []
    for i, spec in enumerate(remainder):
        x, nc, a = L.apply_layer(params["rem"][i], x, ctx,
                                 rem_caches[i] if not empty else None, spec)
        new_rem.append(nc if nc is not None else {})
        aux = aux + a
    new_caches = {"pat": new_pat, "rem": tuple(new_rem)}
    return x, aux, new_caches


def _encode(params, cfg: ModelConfig, dist: Dist, enc_embeds, mode):
    """Whisper-style encoder over precomputed frame embeddings (stub)."""
    b, s_enc, d = enc_embeds.shape
    x = enc_embeds + sinusoidal_positions(s_enc, d, enc_embeds.dtype)[None]
    ctx = L.Ctx(cfg=cfg, dist=dist, mode="train" if mode == "train" else
                "prefill", angles=None, is_encoder=True, batch_size=b)
    x = ctx.dist.constrain(x, *ctx.act_spec(), None)
    enc_params = {"pat": params["enc"]["pat"], "rem": ()}
    x, _, _ = _run_stack(enc_params, x, ctx, None, cfg,
                         (LayerSpec(ENC, DENSE),), (), remat=(mode == "train"))
    return L.rms_norm(x, params["enc"]["final_norm"]["scale"], cfg.norm_eps)


def _inputs_to_x(params, cfg, ctx, batch):
    if "embeds" in batch:
        x = batch["embeds"]
    else:
        key = "tokens" if "tokens" in batch else "token"
        x = L.embed_tokens(params["embed"], batch[key], ctx)
    if cfg.rope_theta == 0:  # sinusoidal positions (whisper decoder)
        s = x.shape[1]
        if ctx.mode == "decode":
            tab = sinusoidal_positions(cfg.max_seq_len, cfg.d_model, x.dtype)
            if jnp.ndim(ctx.pos) == 1:
                x = x + jnp.take(tab, ctx.pos, axis=0)[:, None]
            else:
                x = x + lax.dynamic_slice(tab, (ctx.pos, 0),
                                          (1, cfg.d_model))[None]
        else:
            x = x + sinusoidal_positions(s, cfg.d_model, x.dtype)[None]
    return x


def train_loss(params, batch, cfg: ModelConfig, dist: Dist):
    """batch: tokens|embeds (+ enc_embeds for enc-dec), labels."""
    lab = batch["labels"]
    b, s = lab.shape
    positions = jnp.arange(s)
    memory = None
    if cfg.enc_dec:
        memory = _encode(params, cfg, dist, batch["enc_embeds"], "train")
    ctx = L.Ctx(cfg=cfg, dist=dist, mode="train", angles=_angles(cfg, positions),
                memory=memory, batch_size=b)
    x = _inputs_to_x(params, cfg, ctx, batch)
    x = dist.constrain(x, *ctx.act_spec(), None)
    x, aux, _ = _run_stack(params, x, ctx, None, cfg, cfg.pattern,
                           cfg.remainder, remat=True)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    loss = L.lm_head_loss(params["embed"], x, lab, ctx)
    n_moe = (cfg.num_periods * sum(1 for sp in cfg.pattern if sp.ffn == MOE)
             + sum(1 for sp in cfg.remainder if sp.ffn == MOE))
    if n_moe:
        loss = loss + AUX_WEIGHT * aux / n_moe
    return loss


def prefill(params, batch, cfg: ModelConfig, dist: Dist, cache_len: int):
    """Process the prompt; returns (next_token (b,), caches)."""
    key = "embeds" if "embeds" in batch else "tokens"
    b, s = batch[key].shape[:2]
    positions = jnp.arange(s)
    memory = None
    if cfg.enc_dec:
        memory = _encode(params, cfg, dist, batch["enc_embeds"], "prefill")
    ctx = L.Ctx(cfg=cfg, dist=dist, mode="prefill",
                angles=_angles(cfg, positions), memory=memory,
                cache_len=cache_len, batch_size=b)
    x = _inputs_to_x(params, cfg, ctx, batch)
    x = dist.constrain(x, *ctx.act_spec(), None)
    x, _, caches = _run_stack(params, x, ctx, None, cfg, cfg.pattern,
                              cfg.remainder, remat=False)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    next_tok = L.lm_head_argmax(params["embed"], x[:, -1:], ctx)
    return next_tok, caches


def decode_step(params, batch, caches, cfg: ModelConfig, dist: Dist):
    """One decode step.  batch: {"token": (b,1) or "embeds": (b,1,d),
    "pos": scalar}.  Returns (next_token (b,), caches')."""
    pos = batch["pos"]
    b = (batch["token"] if "token" in batch else batch["embeds"]).shape[0]
    # pos may be scalar (uniform batch) or (b,) ragged (continuous batching)
    positions = pos[None] if jnp.ndim(pos) == 0 else pos[:, None]
    ctx = L.Ctx(cfg=cfg, dist=dist, mode="decode",
                angles=_angles(cfg, positions) if cfg.rope_theta else None,
                pos=pos, batch_size=b)
    x = _inputs_to_x(params, cfg, ctx, batch)
    x = dist.constrain(x, *ctx.act_spec(), None)
    x, _, new_caches = _run_stack(params, x, ctx, caches, cfg, cfg.pattern,
                                  cfg.remainder, remat=False)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    next_tok = L.lm_head_argmax(params["embed"], x, ctx)
    return next_tok, new_caches
