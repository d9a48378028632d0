"""Rotary position embeddings: standard RoPE, Qwen2-VL M-RoPE, sinusoidal.

M-RoPE [arXiv:2409.12191]: head_dim/2 frequency slots are split into
(t, h, w) sections; each section rotates by its own position component.
For the stubbed text-only path all three components equal the token index,
which makes M-RoPE coincide with 1-D RoPE (a property we test).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    """(half,) f32 inverse frequencies, computed on the host: a caller that
    runs eagerly and one under jit get the same constants (a TPU's own
    pow and divide round differently from the compile-time folding, which
    moved angles near position 63 by 2e-4)."""
    half = head_dim // 2
    exps = np.arange(0, half, dtype=np.float32) / np.float32(half)
    return np.float32(1.0) / np.power(np.float32(theta), exps)


def rope_angles(positions, head_dim: int, theta: float, mrope_sections=()):
    """positions: (..., s) int or (3, ..., s) for M-RoPE -> angles (..., s, half)."""
    freqs = rope_freqs(head_dim, theta)           # (half,)
    half = head_dim // 2
    if mrope_sections:
        assert positions.ndim >= 2 and positions.shape[0] == len(mrope_sections)
        assert sum(mrope_sections) == half, (mrope_sections, half)
        parts = []
        start = 0
        for i, sec in enumerate(mrope_sections):
            f = freqs[start:start + sec]
            parts.append(positions[i][..., None].astype(jnp.float32) * f)
            start += sec
        return jnp.concatenate(parts, axis=-1)    # (..., s, half)
    return positions[..., None].astype(jnp.float32) * freqs


def apply_rope(x, angles):
    """x: (..., s, n_heads, head_dim), angles: broadcastable (..., s, half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # angles (..., s, half) -> (..., s, 1, half): broadcast over the heads axis.
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def sinusoidal_positions(seq_len: int, d_model: int, dtype=jnp.float32):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    out = jnp.zeros((seq_len, d_model), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(angle))
    out = out.at[:, 1::2].set(jnp.cos(angle))
    return out.astype(dtype)
