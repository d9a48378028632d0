"""Attention variants vs the reference oracle (local, single device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (decode_attention, local_decode_attention,
                                    mla_decode_attention, ref_attention,
                                    ring_attention)

KEY = jax.random.PRNGKey(0)


def _qkv(b, s, h, hkv, dh, dtype=jnp.float32):
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, h, dh), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, dh), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, hkv, dh), dtype)
    return q, k, v


@pytest.mark.parametrize("h,hkv", [(8, 8), (8, 4), (8, 1)])
@pytest.mark.parametrize("window", [0, 7])
@pytest.mark.parametrize("q_chunk", [0, 8])
def test_ring_local_matches_ref(h, hkv, window, q_chunk):
    q, k, v = _qkv(2, 32, h, hkv, 16)
    ref = ref_attention(q, k, v, causal=True, window=window)
    out = ring_attention(q, k, v, axis=None, causal=True, window=window,
                         q_chunk=q_chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("pos", [0, 17, 31])
def test_decode_matches_ref(pos):
    b, s, h, hkv, dh, S = 2, 32, 8, 4, 16, 32
    q, k, v = _qkv(b, s, h, hkv, dh)
    kc = jnp.zeros((b, S, hkv, dh)).at[:, :s].set(k)
    vc = jnp.zeros((b, S, hkv, dh)).at[:, :s].set(v)
    qd = q[:, pos:pos + 1]
    out, kc2, vc2 = decode_attention(qd, kc, vc, k[:, pos:pos + 1],
                                     v[:, pos:pos + 1], jnp.int32(pos),
                                     axes=())
    ref = ref_attention(qd, k[:, :pos + 1], v[:, :pos + 1], causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert bool((kc2[:, pos] == k[:, pos]).all())


@pytest.mark.parametrize("pos", [3, 9, 23])
def test_local_decode_rolling_buffer(pos):
    b, s, h, hkv, dh, W = 2, 32, 4, 2, 16, 8
    q, k, v = _qkv(b, s, h, hkv, dh)
    kcw = jnp.zeros((b, W, hkv, dh))
    vcw = jnp.zeros((b, W, hkv, dh))
    for p in range(pos):
        kcw = kcw.at[:, p % W].set(k[:, p])
        vcw = vcw.at[:, p % W].set(v[:, p])
    qd = q[:, pos:pos + 1]
    out, _, _ = local_decode_attention(qd, kcw, vcw, k[:, pos:pos + 1],
                                       v[:, pos:pos + 1], jnp.int32(pos), W)
    lo = max(0, pos - W + 1)
    ref = ref_attention(qd, k[:, lo:pos + 1], v[:, lo:pos + 1], causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_mla_decode_matches_naive():
    b, S, pos, r, dr, H = 2, 32, 17, 12, 6, 4
    ql = jax.random.normal(jax.random.fold_in(KEY, 5), (b, 1, H, r))
    qr = jax.random.normal(jax.random.fold_in(KEY, 6), (b, 1, H, dr))
    cc = jax.random.normal(jax.random.fold_in(KEY, 7), (b, S, r))
    kr = jax.random.normal(jax.random.fold_in(KEY, 8), (b, S, dr))
    cn = jax.random.normal(jax.random.fold_in(KEY, 9), (b, 1, r))
    krn = jax.random.normal(jax.random.fold_in(KEY, 10), (b, 1, dr))
    scale = 1.0 / np.sqrt(r + dr)
    ctx, cc2, kr2 = mla_decode_attention(ql, qr, cc, kr, cn, krn,
                                         jnp.int32(pos), scale=scale, axes=())
    s = (jnp.einsum("bqhr,bsr->bhqs", ql, cc2[:, :pos + 1])
         + jnp.einsum("bqhd,bsd->bhqs", qr, kr2[:, :pos + 1])) * scale
    p = jax.nn.softmax(s, -1)
    ref = jnp.moveaxis(jnp.einsum("bhqs,bsr->bhqr", p, cc2[:, :pos + 1]), 2, 1)
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(ref), atol=2e-5)
    assert bool((cc2[:, pos] == cn[:, 0]).all())


def test_rope_angles_same_eager_and_jitted():
    """The offloaded engine computes its rope angles eagerly between
    layer programs while the resident engine computes them inside its
    jit: both must get the same bits (the inverse frequencies are host
    constants, not a pow and divide rounded by the device)."""
    from repro.models.rope import rope_angles
    pos = jnp.arange(2048)
    eager = rope_angles(pos, 64, 10000.0)
    jitted = jax.jit(lambda p: rope_angles(p, 64, 10000.0))(pos)
    folded = jax.jit(lambda: rope_angles(jnp.arange(2048), 64, 10000.0))()
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(folded))
