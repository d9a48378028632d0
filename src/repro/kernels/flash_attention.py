"""Pallas TPU kernel: blocked causal flash attention with GQA + sliding
window.

This is the HBM-traffic fix the roofline analysis demands: the jnp
attention path writes (sq x sk) score tensors to HBM every layer (the
dominant memory term in the baseline dry-runs); here scores live in VMEM
scratch only — HBM traffic is q, k, v, o.  Grid: (batch, q_heads,
q_blocks, kv_blocks) with the kv dim 'arbitrary' (sequential) so the
(m, l, acc) online-softmax state lives in VMEM scratch across kv steps.
GQA maps each q head to its kv head in the k/v index_maps (no kv
duplication in HBM or VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, n_k: int, causal: bool, window: int,
            q_offset: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                      # (bq, dh)
    k = k_ref[...]                                      # (bk, dh)
    v = v_ref[...]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)          # fully-masked rows
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(m_prev > NEG_INF / 2, alpha, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _out():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """q (b, sq, h, dh); k/v (b, sk, hkv, dh) -> (b, sq, h, dh).

    The kernel runs on head-major copies, (b, h, s, dh), so that a block
    is a (rows, dh) tile of one head, which the TPU tiles as it is."""
    b, sq, h, dh = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    n_k = sk // block_k
    grid = (b, h, sq // block_q, n_k)
    scale = 1.0 / (dh ** 0.5)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))

    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               n_k=n_k, causal=causal, window=window,
                               q_offset=q_offset, scale=scale)
    q_spec = pl.BlockSpec((None, None, block_q, dh),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, dh),
                           lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")) if not interpret else None,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)
