"""jit'd dispatch wrappers for the Pallas kernels.

The kernels compile natively; a caller that wants the Pallas interpreter
(e.g. to validate on CPU) passes ``interpret=True`` through ``**kw``.
``use_kernels(False)`` forces the pure-jnp reference path (used by the
dry-run, whose compiled artifact must consist of ops the roofline analyzer
models).
"""
from __future__ import annotations

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import int4_matmul

_STATE = {"enabled": True}


def use_kernels(flag: bool):
    _STATE["enabled"] = flag


def int4_matmul_op(x, packed, scale, **kw):
    if not _STATE["enabled"]:
        return R.int4_matmul_ref(x, packed, scale)
    return int4_matmul(x, packed, scale, **kw)


def flash_attention_op(q, k, v, *, causal=True, window=0, q_offset=0, **kw):
    if not _STATE["enabled"]:
        return R.flash_attention_ref(q, k, v, causal=causal, window=window,
                                     q_offset=q_offset)
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, **kw)


def decode_attention_op(q, k_cache, v_cache, pos, **kw):
    if not _STATE["enabled"]:
        return R.decode_attention_ref(q[:, None], k_cache, v_cache,
                                      pos)[:, 0]
    return decode_attention_kernel(q, k_cache, v_cache, pos, **kw)
