"""Shared model utilities: distribution context, norms, online-softmax math.

The ``Dist`` context makes every model function runnable in two worlds:
  * ``Dist.local()`` — no mesh; all collectives degenerate to identity.
    Used by CPU smoke tests and as the numerical oracle.
  * a real mesh — the same code routes through ``shard_map`` islands
    (ring attention, flash-decode, EP all-to-all, sharded CE).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class Dist:
    """Distribution context threaded through every model function."""

    mesh: Optional[Mesh] = None
    data_axes: tuple[str, ...] = ()      # batch axes, e.g. ("pod", "data")
    model_axis: Optional[str] = None     # TP/SP/EP axis ("model")
    # axes the decode KV cache's sequence dim is sharded over; defaults to
    # (model_axis,) — long_500k (batch 1) uses ("data", "model").
    kv_axes: tuple[str, ...] = ()

    @staticmethod
    def local() -> "Dist":
        return Dist()

    @property
    def is_dist(self) -> bool:
        return self.mesh is not None

    @property
    def model_size(self) -> int:
        if not self.is_dist or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def kv_shard_axes(self) -> tuple[str, ...]:
        if self.kv_axes:
            return self.kv_axes
        return (self.model_axis,) if self.model_axis else ()

    def kv_shards(self) -> int:
        n = 1
        for a in self.kv_shard_axes:
            n *= self.mesh.shape[a]
        return n

    def constrain(self, x, *spec):
        """``with_sharding_constraint`` that no-ops locally."""
        if not self.is_dist:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, P(*spec)))

    def sharding(self, *spec) -> Optional[NamedSharding]:
        if not self.is_dist:
            return None
        return NamedSharding(self.mesh, P(*spec))


# ---------------------------------------------------------------------------
# Axis-optional collectives (identity when axis is None) — lets shard_map
# bodies double as single-device reference implementations.
# ---------------------------------------------------------------------------

def psum(x, axis):
    return lax.psum(x, axis) if axis else x


def pmax(x, axis):
    """pmax for softmax/logsumexp stabilization.  jax has no differentiation
    rule for lax.pmax, but every use here stabilizes an exp() whose final
    value is exactly invariant to the max — so stop_gradient is exact."""
    return lax.pmax(lax.stop_gradient(x), axis) if axis else x


def pmean(x, axis):
    return lax.pmean(x, axis) if axis else x


def axis_index(axis):
    if not axis:
        return jnp.int32(0)
    if isinstance(axis, (tuple, list)):
        idx = jnp.int32(0)
        for a in axis:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return idx
    return lax.axis_index(axis)


def axis_size(axis):
    if not axis:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= lax.axis_size(a)
        return n
    return lax.axis_size(axis)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jax.nn.softplus(x)


def swiglu(x, w_gate, w_up, w_down):
    g = x @ w_gate
    u = x @ w_up
    return (silu(g) * u) @ w_down


# ---------------------------------------------------------------------------
# Online-softmax partials: the algebra shared by flash attention, ring
# attention, and the cross-shard decode merge.  A partial is (m, l, o):
#   m = running max of scores, l = sum exp(score - m), o = sum exp(..) * v
# (o unnormalized).  ``merge_partials`` is associative & commutative —
# property-tested in tests/test_properties.py.
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def merge_partials(a, b):
    m_a, l_a, o_a = a
    m_b, l_b, o_b = b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    l = l_a * ca + l_b * cb
    o = o_a * ca[..., None] + o_b * cb[..., None]
    return m, l, o


def finalize_partials(m, l, o):
    return o / jnp.maximum(l, 1e-30)[..., None]


def empty_partials(shape_ml, d, dtype=jnp.float32):
    m = jnp.full(shape_ml, NEG_INF, dtype)
    l = jnp.zeros(shape_ml, dtype)
    o = jnp.zeros((*shape_ml, d), dtype)
    return m, l, o


def match_vma(x, like):
    """Promote x's varying-manual-axes to match ``like`` (shard_map carries).

    Under shard_map, loop carries initialized with jnp.zeros are 'unvarying'
    while computed outputs vary over the mapped axes; lax.fori_loop/scan then
    reject the carry.  No-op outside shard_map.
    """
    vma = jax.typeof(like).vma
    if not vma:
        return x
    def fix(t):
        missing = tuple(sorted(vma - jax.typeof(t).vma))
        if not missing:
            return t
        return lax.pcast(t, missing, to="varying")
    return jax.tree.map(fix, x)


def init_leaf(key, shape, scale: float, dtype):
    if scale == 0.0:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
