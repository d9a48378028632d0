"""Plain float32 reference of the served decoder stacks, and the recipe of
their random weights.

A configuration file names the reference that judges it (its
``reference`` key, a path from the checkout's root), and
``bench/cell.py`` loads that file by path.  A reference file provides,
for the configuration dict ``m``:

- ``init_weights(m, seed)``: the weights, drawn from ``seed`` by the
  same recipe as the served engine, and nothing taken from the program;
- ``hidden(m, w, toks, fp8=False)``: the last layer's output (n, s, d)
  for token rows ``toks`` (n, s), causal; ``fp8=True`` is the control,
  the same forward one precision step below the stated one;
- ``judge(m, w, x, tokens, fp8=False)``: per position of ``x``, the best
  logit, the logit of ``tokens`` (n, s) and the token that comes first,
  as numpy arrays;
- ``padded_vocab(m)``: the rows of the embedding and the head as the
  served engine holds them.

This file is the reference of the dense and Mixtral-style stacks below.

Written from the published descriptions, independent of ``src/``:

- Llama-style decoder layer (Granite Code, arXiv:2405.04324; Mixtral,
  arXiv:2401.04088): pre-norm RMSNorm, grouped-query attention with
  rotary positions (the rotate-half form, theta from the configuration,
  query head ``h`` reading key/value head ``h // (heads / kv_heads)``),
  causal softmax scaled by ``1/sqrt(head_dim)``, then a SwiGLU MLP
  ``(silu(x W_gate) * (x W_up)) W_down``.
- Mixtral's sparse MLP: router logits ``x W_router``, the top-k experts
  of each token, their logits softmaxed over the k chosen, and the
  weighted sum of those experts' SwiGLU outputs, with no token dropped.
- A final RMSNorm and the output head (the embedding's transpose where
  the embeddings are tied).

Random weights follow one recipe, from ``PRNGKey(seed)``: a table of
named tensors per group (embedding, final norm, the stacked layers), each
group keyed by ``fold_in(key, group)``, each tensor within it by
``fold_in(group_key, i)`` over the names in sorted order; matrices are
standard normal times ``1/sqrt(first dim)`` (the expert stacks, whose
first dim counts experts, included), the embedding times
``1/sqrt(d_model)``, and every norm weight is one.  The served engine
draws its weights from the seed by the same recipe, so the two share the
seed and nothing else.

Every matmul runs at ``precision="highest"``: on a TPU a float32 matmul
otherwise runs in single bfloat16 passes.  The forward runs layer by
layer, with queries in blocks, so that the full configuration fits one
chip once the served engine is gone.  ``logits(..., fp8=True)`` is the
control: the same forward with every matrix rounded to float8 (e4m3, one
scale per output column), the precision step below the stated bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # queries per attention block
EMBED_GROUP, LAYER_GROUP = 0, 10     # group 1, the final norm, is all ones


def _layer_table(m: dict) -> dict:
    """{name: (unstacked shape, scale)} of one layer; scale None = ones."""
    d, h, hkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    t = {"norm_mixer": ((d,), None), "norm_ffn": ((d,), None),
         "wq": ((d, h * hd), -1.0), "wk": ((d, hkv * hd), -1.0),
         "wv": ((d, hkv * hd), -1.0), "wo": ((h * hd, d), -1.0)}
    moe = m.get("moe")
    if moe:
        e, f = moe["num_experts"], moe["expert_d_ff"]
        t.update({"wg": ((d, e), -1.0), "w_gate": ((e, d, f), -1.0),
                  "w_up": ((e, d, f), -1.0), "w_down": ((e, f, d), -1.0)})
    else:
        f = m["d_ff"]
        t.update({"w_gate": ((d, f), -1.0), "w_up": ((d, f), -1.0),
                  "w_down": ((f, d), -1.0)})
    return t


def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def _embed_table(m: dict) -> dict:
    v, d = padded_vocab(m), m["d_model"]
    t = {"emb": ((v, d), 1.0 / math.sqrt(d))}
    if not m["tie_embeddings"]:
        t["w_out"] = ((d, v), -1.0)
    return t


@partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _draw(key, shape, scale, stack=0):
    full = ((stack,) + shape) if stack else shape
    if scale is None:
        return jnp.ones(full, jnp.float32)
    if scale < 0:
        scale = 1.0 / math.sqrt(max(1, shape[0]))
    return _normal(key, full, float(scale))


def init_weights(m: dict, seed: int) -> dict:
    """The configuration's random float32 weights from ``seed``:
    ``{"emb", "w_out"?, "final_norm", "layers": {name: (L, ...)}}``, drawn
    on the default device."""
    key = jax.random.PRNGKey(seed)
    out = {}
    ek = jax.random.fold_in(key, EMBED_GROUP)
    for i, (name, (shape, scale)) in enumerate(sorted(_embed_table(m).items())):
        out[name] = _draw(jax.random.fold_in(ek, i), shape, scale)
    out["final_norm"] = jnp.ones((m["d_model"],), jnp.float32)
    lk = jax.random.fold_in(key, LAYER_GROUP)
    out["layers"] = {
        name: _draw(jax.random.fold_in(lk, i), shape, scale, m["num_layers"])
        for i, (name, (shape, scale)) in enumerate(sorted(_layer_table(m).items()))}
    return out


def _fp8(a):
    """Round a matrix to float8 e4m3 with one scale per output column (the
    last axis), back in float32."""
    amax = jnp.max(jnp.abs(a), axis=-2, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rope(x, pos, theta):
    """x (n, s, heads, hd); rotate-half rotary embedding at positions pos."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (np.float32(theta) ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal GQA. q (n, s, h, hd); k, v (n, s, hkv, hd) -> (n, s, h*hd),
    queries in blocks of Q_BLOCK."""
    n, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    kpos = jnp.arange(s)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK].reshape(n, -1, hkv, g, hd)
        sc = jnp.einsum("nqkgd,nskd->nkgqs", qb, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None, None, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("nkgqs,nskd->nqkgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        outs.append(o.reshape(n, -1, h * hd))
    return jnp.concatenate(outs, axis=1)


def _swiglu(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def _take(w, li, fp8, e=None):
    """Layer ``li``'s (and expert ``e``'s) slice of a stacked tensor,
    rounded to float8 for the control."""
    a = jax.lax.dynamic_index_in_dim(w, li, keepdims=False)
    if e is not None:
        a = jax.lax.dynamic_index_in_dim(a, e, keepdims=False)
    return _fp8(a) if fp8 and a.ndim >= 2 else a


@partial(jax.jit, static_argnames=("m", "fp8"))
def _mixer(x, layers, li, *, m, fp8):
    """x + attention(norm(x)) of layer ``li`` over (n, s, d) rows at
    positions 0..s-1."""
    n, s, d = x.shape
    h, hkv, hd = m.num_heads, m.num_kv_heads, m.head_dim
    t = partial(_take, li=li, fp8=fp8)
    pos = jnp.arange(s)
    y = _rms(x, t(layers["norm_mixer"]), m.norm_eps)
    q = _rope(_mm(y, t(layers["wq"])).reshape(n, s, h, hd), pos, m.rope_theta)
    k = _rope(_mm(y, t(layers["wk"])).reshape(n, s, hkv, hd), pos, m.rope_theta)
    v = _mm(y, t(layers["wv"])).reshape(n, s, hkv, hd)
    return x + _mm(_attention(q, k, v), t(layers["wo"]))


@partial(jax.jit, static_argnames=("m", "fp8"))
def _dense_ffn(x, layers, li, *, m, fp8):
    t = partial(_take, li=li, fp8=fp8)
    y = _rms(x, t(layers["norm_ffn"]), m.norm_eps)
    return x + _swiglu(y, t(layers["w_gate"]), t(layers["w_up"]),
                       t(layers["w_down"]))


@partial(jax.jit, static_argnames=("m", "fp8"))
def _router(x, layers, li, *, m, fp8):
    """Normed rows and each token's weight on every expert: the softmax
    of its top-k router logits, zero off the k chosen."""
    t = partial(_take, li=li, fp8=fp8)
    y = _rms(x, t(layers["norm_ffn"]), m.norm_eps)
    logits = _mm(y, t(layers["wg"]))                          # (n, s, E)
    vals, ids = jax.lax.top_k(logits, m.top_k)
    gates = jax.nn.softmax(vals, axis=-1)                     # over the k chosen
    onehot = jax.nn.one_hot(ids, logits.shape[-1], dtype=gates.dtype)
    return y, jnp.einsum("nsk,nske->nse", gates, onehot)


@partial(jax.jit, static_argnames=("fp8",))
def _expert(y, gate, layers, li, e, *, fp8):
    t = partial(_take, li=li, fp8=fp8, e=e)
    return gate[..., None] * _swiglu(y, t(layers["w_gate"]), t(layers["w_up"]),
                                     t(layers["w_down"]))


@partial(jax.jit, static_argnames=("m", "fp8"))
def _head(x, final_norm, w_out, *, m, fp8):
    y = _rms(x, final_norm, m.norm_eps)
    return _mm(y, _fp8(w_out) if fp8 else w_out)[..., :m.vocab_size]


class _Static:
    """Hashable static view of the sizes the jitted forward needs."""

    def __init__(self, m: dict):
        self.num_heads = m["num_heads"]
        self.num_kv_heads = m["num_kv_heads"]
        self.head_dim = m["head_dim"]
        self.rope_theta = float(m["rope_theta"])
        self.norm_eps = float(m["norm_eps"])
        self.vocab_size = m["vocab_size"]
        self.top_k = (m.get("moe") or {}).get("top_k", 0)
        self._key = (self.num_heads, self.num_kv_heads, self.head_dim,
                     self.rope_theta, self.norm_eps, self.vocab_size, self.top_k)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Static) and other._key == self._key


def hidden(m: dict, w: dict, toks, *, fp8: bool = False):
    """The last layer's output (n, s, d) for token rows ``toks`` (n, s):
    the layers one at a time, the experts of a layer one at a time."""
    st = _Static(m)
    layers = w["layers"]
    emb = _fp8(w["emb"].T).T if fp8 else w["emb"]
    x = jnp.take(emb, jnp.asarray(toks), axis=0)
    for li in range(m["num_layers"]):
        li = jnp.int32(li)
        x = _mixer(x, layers, li, m=st, fp8=fp8)
        if st.top_k:
            y, gates = _router(x, layers, li, m=st, fp8=fp8)
            for e in range(gates.shape[-1]):
                x = x + _expert(y, gates[..., e], layers, li, jnp.int32(e),
                                fp8=fp8)
        else:
            x = _dense_ffn(x, layers, li, m=st, fp8=fp8)
    return x


def _w_out(m: dict, w: dict):
    return w["emb"].T if m["tie_embeddings"] else w["w_out"]


@partial(jax.jit, static_argnames=("m", "fp8"))
def _judge(x, final_norm, w_out, tokens, *, m, fp8):
    lg = _head(x, final_norm, w_out, m=m, fp8=fp8)           # (n, s, vocab)
    at = jnp.take_along_axis(lg, tokens[..., None], axis=-1)[..., 0]
    return lg.max(-1), at, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def judge(m: dict, w: dict, x, tokens, *, fp8: bool = False):
    """Per position of hidden rows ``x`` (n, s, d): the best logit, the
    logit of ``tokens`` (n, s), and the token that comes first, as numpy
    arrays; the logits themselves never leave the device."""
    best, at, top = _judge(x, w["final_norm"], _w_out(m, w),
                           jnp.asarray(tokens, jnp.int32), m=_Static(m),
                           fp8=fp8)
    return np.asarray(best), np.asarray(at), np.asarray(top)


def logits(m: dict, w: dict, seqs, *, fp8: bool = False,
           pad_to: int = 128) -> list:
    """Reference logits (len(seq), vocab) as numpy float32 for each token
    sequence in ``seqs``; ``fp8`` runs the control.  The rows are padded
    at the end to one length (a multiple of ``pad_to``), which causal
    attention never lets earlier positions see."""
    st = _Static(m)
    s = max(len(q) for q in seqs)
    s = -(-s // pad_to) * pad_to
    toks = np.zeros((len(seqs), s), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    x = hidden(m, w, toks, fp8=fp8)
    lg = np.asarray(_head(x, w["final_norm"], _w_out(m, w), m=st, fp8=fp8))
    return [lg[i, :len(q)] for i, q in enumerate(seqs)]
