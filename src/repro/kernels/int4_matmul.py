"""Pallas TPU kernel: fused INT4-dequant matmul (paper §3.4, TPU-native).

The paper's GPU kernel computes matvec directly on 4-bit weights to skip
the dequantization pass.  The TPU adaptation: only INT4 bytes cross
HBM->VMEM (the expensive hop — the PCIe analogue); nibbles are unpacked
and scaled in VREGs and fed straight to the MXU with fp32 accumulation.
The packed layout (quant/int4.py) is column-pair packing so the
contraction dim K stays unpacked (free K-blocking); the kernel multiplies
the low and high nibbles separately and the output columns are
interleaved after it.

Blocks are TPU-tiled: a packed block is 128 lanes wide (256 output
columns), and a K block holds whole quantization groups, eight scale rows
at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 128


def _kernel(x_ref, p_ref, se_ref, so_ref, oe_ref, oo_ref, acc_e, acc_o, *,
            n_k: int, group: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_e[...] = jnp.zeros_like(acc_e)
        acc_o[...] = jnp.zeros_like(acc_o)

    x = x_ref[...].astype(jnp.float32)              # (bm, bk)
    packed = p_ref[...].astype(jnp.int32)           # (bk, bn2)
    bk, bn2 = packed.shape

    def dequant(q, s_ref):
        # groupwise scaling in VREGs: (bk//G, G, bn2) * (bk//G, 1, bn2)
        q = q.astype(jnp.float32).reshape(bk // group, group, bn2)
        return (q * s_ref[...][:, None, :]).reshape(bk, bn2)

    # column 2j sits in the low nibble of packed column j, column 2j+1 in
    # the high one: each half is its own matmul, so no lane interleave
    # happens in the kernel
    for q, s_ref, acc in (((packed & 0xF) - 8, se_ref, acc_e),
                          (((packed >> 4) & 0xF) - 8, so_ref, acc_o)):
        acc[...] += jax.lax.dot(
            x, dequant(q, s_ref), precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _out():
        oe_ref[...] = acc_e[...].astype(oe_ref.dtype)
        oo_ref[...] = acc_o[...].astype(oo_ref.dtype)


def _block_k(K: int, group: int, want: int) -> int:
    """Largest K block <= ``want`` that divides K, holds whole groups and
    gives the scale block a sublane count the TPU tiles (a multiple of 8
    scale rows, or all of them)."""
    for c in range(min(want, K), group - 1, -group):
        if K % c == 0 and ((c // group) % 8 == 0 or c == K):
            return c
    return K


def int4_matmul(x, packed, scale, *, group: int = GROUP, block_m: int = 128,
                block_n: int = 256, block_k: int = 1024,
                out_dtype=jnp.float32, interpret: bool = False):
    """x (M, K) bf16/f32 @ int4-packed W -> (M, N) out_dtype.

    packed: (K, N//2) uint8, scale: (K//group, N) f32 (see quant/int4.py).
    The kernel computes even and odd output columns as two matmuls over
    the low and high nibbles; the columns are interleaved back outside
    it.  ``block_n`` counts output columns, so a packed block is
    ``block_n // 2`` lanes wide (128 at the default).
    """
    M, K = x.shape
    Kp, N2 = packed.shape
    N = N2 * 2
    assert Kp == K and K % group == 0, (K, Kp, group)
    block_m = min(block_m, M)
    bn2 = min(block_n // 2, N2)
    block_k = _block_k(K, group, block_k)
    assert M % block_m == 0 and N2 % bn2 == 0, (M, N, block_m, block_n)
    n_k = K // block_k
    # de-interleave the scales once: (K//G, N) -> even / odd columns
    s_even, s_odd = scale[:, 0::2], scale[:, 1::2]

    grid = (M // block_m, N2 // bn2, n_k)
    s_spec = pl.BlockSpec((block_k // group, bn2), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((block_m, bn2), lambda i, j, k: (i, j))
    even, odd = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, bn2), lambda i, j, k: (k, j)),
            s_spec, s_spec,
        ],
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((M, N2), out_dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_m, bn2), jnp.float32)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
    )(x, packed, s_even, s_odd)
    return jnp.stack([even, odd], axis=-1).reshape(M, N)
