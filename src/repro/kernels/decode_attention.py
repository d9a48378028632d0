"""Pallas TPU kernel: single-token GQA decode attention over a (possibly
huge) KV cache.

The decode roofline is pure memory: reading the KV cache once is the
floor.  This kernel streams the cache through VMEM in (block_s) chunks
with online-softmax state in scratch — HBM traffic = cache + q + o, the
paper's "KV-cache loading" rendered as HBM->VMEM streaming.  Emits
normalized output.

``decode_attention_int4_kernel`` is the INT4-KV variant for
``core.kvstore``'s ``kv_mode="int4"`` row layout: the cache arrives as
per-(batch, position) rows of ``F = hkv*dh`` features as nibble pairs
(``(b, S, F//2)`` uint8) + groupwise f32 scales (``(b, S, F//g)``) — and
the dequant happens IN-KERNEL, in VREGs, after the packed bytes crossed
HBM->VMEM.  Only INT4 bytes pay the memory floor; no f32 cache is ever
materialized (the cache rendering of the paper's §3.4 "no dequantization
pass").  The served path does not call it yet: there the store
dequantizes on the transfer thread over live rows only (``kvstore.load``,
post-link) — numerics are identical (asserted in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# Layout.  A cache block is (block_s, F) with F = hkv * dh: each position's
# heads side by side along the lanes, which the TPU tiles densely (an
# (hkv, dh) minor pair would pad to (8, 128) tiles).  The query arrives as
# a block-diagonal (h, F) matrix, q head i holding its values in the
# columns of its kv head and zeros elsewhere, so one (h, F) x (F, block_s)
# matmul scores every head against its own kv head, and p @ V gives each
# q head an (h, F) row whose own kv head's columns are its output.  The
# wrappers build that query and pick the output columns outside the
# kernel.


def _online_softmax_step(s, accs, pos, si, m_ref, l_ref, *, block_s: int):
    """Fold one block's scores ``s`` (h, block_s) into the running
    (m, l) state and each ``(acc_ref, v)`` pair's ``p @ v`` sum."""
    kv_pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kv_pos <= pos
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]                                  # (h, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(m_prev > NEG_INF / 2, alpha, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    for acc_ref, v in accs:
        acc_ref[...] = acc_ref[...] * alpha + _dot(p, v)
    m_ref[...] = m_new


def _init_state(si, refs):
    @pl.when(si == 0)
    def _init():
        m_ref, l_ref, *accs = refs
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        for a in accs:
            a[...] = jnp.zeros_like(a)


def _dot_t(a, b):
    """a (m, k) x b (n, k) -> (m, n), f32 accumulate."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_s: int, n_s: int):
    si = pl.program_id(1)
    _init_state(si, (m_ref, l_ref, acc_ref))
    k = k_ref[...].astype(jnp.float32)                   # (bs, F)
    v = v_ref[...].astype(jnp.float32)
    s = _dot_t(q_ref[...], k)                            # (h, bs)
    _online_softmax_step(s, [(acc_ref, v)], pos_ref[0], si, m_ref, l_ref,
                         block_s=block_s)

    @pl.when(si == n_s - 1)
    def _out():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _block_diag_q(q, hkv: int):
    """(b, h, dh) -> (b, h, hkv * dh): q head i in its kv head's columns,
    scaled by 1/sqrt(dh), zeros elsewhere."""
    b, h, dh = q.shape
    g = h // hkv
    own = (jnp.arange(h) // g)[:, None] == jnp.arange(hkv)[None, :]
    qs = q.astype(jnp.float32) / (dh ** 0.5)
    return jnp.where(own[None, :, :, None], qs[:, :, None, :],
                     0.0).reshape(b, h, hkv * dh)


def _own_columns(out, hkv: int, dh: int):
    """(b, h, hkv * dh) kernel rows -> (b, h, dh): each q head's own kv
    head's columns."""
    b, h, _ = out.shape
    g = h // hkv
    out = out.reshape(b, h, hkv, dh)
    return jnp.take_along_axis(
        out, (jnp.arange(h) // g)[None, :, None, None], axis=2)[:, :, 0]


def _smem_pos(pos, interpret: bool):
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1,))
    spec = (pl.BlockSpec((1,), lambda bi, si: (0,)) if interpret
            else pl.BlockSpec(memory_space=pltpu.SMEM))
    return pos_arr, spec


def decode_attention_kernel(q, k_cache, v_cache, pos, *, block_s: int = 512,
                            interpret: bool = False):
    """q (b, h, dh); caches (b, S, hkv, dh); pos scalar -> (b, h, dh)."""
    b, h, dh = q.shape
    _, S, hkv, _ = k_cache.shape
    F = hkv * dh
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    n_s = S // block_s
    pos_arr, pos_spec = _smem_pos(pos, interpret)
    q_spec = pl.BlockSpec((None, h, F), lambda bi, si: (bi, 0, 0))
    kv_spec = pl.BlockSpec((None, block_s, F), lambda bi, si: (bi, si, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, n_s=n_s),
        grid=(b, n_s),
        in_specs=[pos_spec, q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, F), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, F), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        if not interpret else None,
    )(pos_arr, _block_diag_q(q, hkv), k_cache.reshape(b, S, F),
      v_cache.reshape(b, S, F))
    return _own_columns(out, hkv, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# INT4-KV variant: packed cache rows dequantized in VREGs
# ---------------------------------------------------------------------------


def _unpack_halves(pk_ref, sc_ref, *, group: int):
    """One VMEM block of packed cache rows -> its even and odd features,
    each (bs, F//2) f32, in the store's layout (``core.kvstore``: feature
    2i in the low nibble of byte i, 2i+1 in the high one, one f32 scale
    per ``group`` features).  Keeping the halves apart means no lane
    interleave in the kernel."""
    pk = pk_ref[...].astype(jnp.int32)                  # (bs, F//2)
    sc = sc_ref[...]                                    # (bs, F//group)
    col = jax.lax.broadcasted_iota(jnp.int32, pk.shape, 1)
    grp = col // (group // 2)
    scale = jnp.zeros(pk.shape, jnp.float32)
    for r in range(sc.shape[1]):
        scale = jnp.where(grp == r, sc[:, r:r + 1], scale)
    lo = ((pk & 0xF) - 8).astype(jnp.float32) * scale
    hi = (((pk >> 4) & 0xF) - 8).astype(jnp.float32) * scale
    return lo, hi


def _kernel_int4(pos_ref, qe_ref, qo_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                 oe_ref, oo_ref, m_ref, l_ref, acc_e, acc_o, *,
                 block_s: int, n_s: int, group: int):
    si = pl.program_id(1)
    _init_state(si, (m_ref, l_ref, acc_e, acc_o))
    # dequant HERE, after the packed bytes crossed HBM->VMEM — INT4 bytes
    # are the only cache traffic the roofline sees
    k_lo, k_hi = _unpack_halves(kq_ref, ks_ref, group=group)
    v_lo, v_hi = _unpack_halves(vq_ref, vs_ref, group=group)
    s = _dot_t(qe_ref[...], k_lo) + _dot_t(qo_ref[...], k_hi)   # (h, bs)
    _online_softmax_step(s, [(acc_e, v_lo), (acc_o, v_hi)], pos_ref[0], si,
                         m_ref, l_ref, block_s=block_s)

    @pl.when(si == n_s - 1)
    def _out():
        denom = jnp.maximum(l_ref[...], 1e-30)
        oe_ref[...] = (acc_e[...] / denom).astype(oe_ref.dtype)
        oo_ref[...] = (acc_o[...] / denom).astype(oo_ref.dtype)


def decode_attention_int4_kernel(q, k_packed, k_scale, v_packed, v_scale,
                                 pos, *, hkv: int, group: int,
                                 block_s: int = 512,
                                 interpret: bool = False):
    """q (b, h, dh); packed caches (b, S, hkv*dh//2) uint8 with scales
    (b, S, hkv*dh//group) f32 (``core.kvstore`` row layout) ->
    (b, h, dh).  The same online softmax as ``decode_attention_kernel``
    over the dequantized cache, while only packed bytes stream
    HBM->VMEM: scores add the even-feature and odd-feature halves, and
    the output's halves are interleaved back outside the kernel."""
    b, h, dh = q.shape
    _, S, F2 = k_packed.shape
    F = hkv * dh
    assert F2 * 2 == F and group % 2 == 0, (F2, hkv, dh, group)
    Fg = k_scale.shape[-1]
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    n_s = S // block_s
    pos_arr, pos_spec = _smem_pos(pos, interpret)
    qf = _block_diag_q(q, hkv)                           # (b, h, F)
    q_spec = pl.BlockSpec((None, h, F2), lambda bi, si: (bi, 0, 0))
    pk_spec = pl.BlockSpec((None, block_s, F2), lambda bi, si: (bi, si, 0))
    sc_spec = pl.BlockSpec((None, block_s, Fg), lambda bi, si: (bi, si, 0))
    even, odd = pl.pallas_call(
        functools.partial(_kernel_int4, block_s=block_s, n_s=n_s,
                          group=group),
        grid=(b, n_s),
        in_specs=[pos_spec, q_spec, q_spec, pk_spec, sc_spec, pk_spec,
                  sc_spec],
        out_specs=[q_spec, q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, F2), jnp.float32)] * 2,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, F2), jnp.float32),
            pltpu.VMEM((h, F2), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        if not interpret else None,
    )(pos_arr, qf[..., 0::2], qf[..., 1::2], k_packed, k_scale, v_packed,
      v_scale)
    out = jnp.stack([even, odd], axis=-1).reshape(b, h, F)
    return _own_columns(out, hkv, dh).astype(q.dtype)
