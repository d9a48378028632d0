"""Tiered KV store: first-class residency for the decode cache.

The PIPO engines used to keep the KV cache as ad-hoc numpy dicts inside
each engine and ship the entire allocated ``(b_max, max_len)`` slab on
every ``KV_LOAD``.  Post the INT4 weight work, decode is KV-dominated
(see docs/BENCHMARKS.md) — the cache bytes, not the weight bytes, bound
the step.  ``TieredKVStore`` extracts KV ownership into one subsystem
(mirroring ``core.transfer.TieredWeightStore`` for weights) and attacks
the KV bytes two ways:

* **live-row slabs** — ``load(j, live_b, live_len)`` moves only the
  actually-occupied rows over the link: slots ``0..live_b-1`` and, for
  sequence-extent (kind ``"kv"``) leaves, positions ``0..live_len-1``.
  The device-side result is still the full-slab shape (zero-padded after
  the link) so jitted consumers never retrace; rows outside the live
  extent are masked by decode attention (``kv_pos <= pos``) and written
  before they are read, so the padding is value-invisible — ``kv_mode=
  "fp32"`` stays bit-exact with the old whole-slab path.
  ``load_nbytes`` prices exactly the bytes that crossed, which is what
  ``Task.nbytes``/``Trace`` record and what ``AdaptiveDepth`` prices the
  window with (exact, not modeled).

* **INT4 KV streaming** (``kv_mode="int4"``, the ``QuantPolicy.kv_mode``
  seam) — sequence-extent cache rows are stored *packed*: each
  ``(slot, position)`` row is group-quantized over its flattened feature
  dim (symmetric, groups of ``gcd(F, 32)``, two nibbles per byte +
  f32 group scales — the KV rendering of ``quant/int4.py``).  Rows are
  quantized once, when saved (write-once per position), so the
  quantize→dequantize roundtrip is applied exactly once per row and a
  resident reference that roundtrips newly-written rows reproduces the
  streamed tokens exactly (``serving.engine.KVRoundtripServingEngine``).
  Loads ship packed bytes (+scales) over the link; the dequant runs on
  the *transfer thread* right after the link, bounded by the live
  ``(slots, positions)`` extent — never the allocated slab — exactly
  like the weights path (``transfer._maybe_dequant``), so it overlaps
  main-thread compute instead of competing with it inside the decode
  jit (on TPU the in-kernel rendering is
  ``kernels/decode_attention.py::decode_attention_int4_kernel``).
  Consumers receive plain compute-precision leaves in every mode — the
  packed layout never escapes the store.  ``dequant_nbytes`` /
  ``dequant_bytes_total`` account the unpacked bytes so the live-extent
  bound is assertable on traces.
  Non-sequence leaves (rolling windows, SSM conv/state) are rewritten
  every step — requantizing them would compound error and break the
  roundtrip-once reference — so they stream at full precision.

Thread affinity: construction and ``alloc`` run on the main thread at
engine build; ``load``/``save_*``/``spill``/``restore`` run on transfer
pool threads (numpy + jax ops only, no engine state).  The ``link``
(``transfer.SimLink``) floors each load at ``bytes / bw`` like every
other transfer, so the live-row/INT4 byte reductions show up as wall
time under the deterministic benchmark link.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TieredKVStore", "PhasedKVExtents", "KV_GROUP", "kv_group",
    "kv_eligible", "quantize_kv_rows", "dequantize_kv_rows",
    "kv_roundtrip_rows",
]

# canonical KV quantization group: rows are short (hkv*dh features), so
# the group is the gcd with 32 — full-size heads get 32, scaled-down
# test configs a smaller power of two (same spirit as transfer.int4_group
# for weights, which uses 128 against the much longer contraction dims)
KV_GROUP = 32


def kv_group(n_features: int) -> int:
    """Group size for one cache row of ``n_features`` values."""
    return math.gcd(int(n_features), KV_GROUP)


def kv_eligible(kind: str, feat_shape: Sequence[int]) -> bool:
    """Whether a cache leaf quantizes under ``kv_mode='int4'``: only
    sequence-extent (kind ``'kv'``) rows — written once per position, so
    the quantize-once invariant holds — with an even flattened feature
    count (nibble pairs).  Rolling-window/conv/state leaves are rewritten
    every step and stream at full precision."""
    f = int(np.prod(feat_shape)) if len(feat_shape) else 1
    return kind == "kv" and f % 2 == 0 and f >= 2


@partial(jax.jit, static_argnums=(1,))
def _quantize_rows(x, group: int):
    """x (..., F) f32 -> (packed (..., F//2) uint8, scale (..., F//g) f32).
    Symmetric groupwise over the trailing feature dim; nibble pairs packed
    along adjacent feature columns."""
    *lead, F = x.shape
    xg = x.reshape(*lead, F // group, group)
    scale = jnp.max(jnp.abs(xg), axis=-1) / 7.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(xg / scale[..., None]).astype(jnp.int32)
    q = jnp.clip(q, -8, 7).reshape(*lead, F)
    qu = (q + 8).astype(jnp.uint8)
    lo = qu[..., 0::2]
    hi = qu[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8), scale


def _dequant_impl(packed, scale, group: int):
    """Traceable inverse of ``_quantize_rows`` -> (..., F) f32.  Plain
    function so consumers can inline it inside their own jit (the fused
    path: XLA folds the unpack+scale into the attention compute)."""
    lo = (packed & 0xF).astype(jnp.int32) - 8
    hi = ((packed >> 4) & 0xF).astype(jnp.int32) - 8
    *lead, F2 = packed.shape
    q = jnp.stack([lo, hi], axis=-1).reshape(*lead, F2 * 2)
    w = (q.reshape(*lead, (F2 * 2) // group, group).astype(jnp.float32)
         * scale[..., None])
    return w.reshape(*lead, F2 * 2)


_dequantize_rows = jax.jit(_dequant_impl, static_argnums=(2,))


@partial(jax.jit, static_argnums=(2, 3, 4))
def _dequant_pad_rows(packed, scale, group: int, full: Tuple[int, ...],
                      dtype):
    """One-dispatch load body for INT4 leaves: dequantize the bucketed
    live rows, cast to compute precision, and scatter them into a zeroed
    full-slab array — fused so the f32 intermediate never materializes
    (the eager chain costs real transfer-thread CPU per load)."""
    rows = _dequant_impl(packed, scale, group)
    rows = rows.reshape(rows.shape[:-1] + full[2:]).astype(dtype)
    dev = jnp.zeros(full, dtype)
    return dev.at[:rows.shape[0], :rows.shape[1]].set(rows)


# live_len bucket for on-load shapes: the dequant/pad ops are shape-
# specialized (jit / dispatch caches) and decode presents a FRESH
# live_len every step — unbucketed that is a recompile per step, which
# on real clocks dwarfs the dead-byte win this store exists to claim.
# Rounding the sliced extent up to 32 positions caps the distinct
# shapes at max_len/32.  The bucket's tail rows are zero-filled on the
# host side (zero packed bytes under zero scales dequantize to exact
# zeros), so padded rows stay value-invisible and the link still
# prices only the true live bytes.
KV_LEN_BUCKET = 32


def quantize_kv_rows(x, group: Optional[int] = None):
    """Quantize cache rows (..., F) -> (packed, scale) numpy arrays.  The
    single quantization the store, the spill path, and the parity
    reference all share — any drift breaks the roundtrip-once parity.
    Accepts host or device arrays directly (no forced host bounce)."""
    x = jnp.asarray(x, jnp.float32)
    g = group or kv_group(x.shape[-1])
    packed, scale = _quantize_rows(x, g)
    return np.asarray(packed), np.asarray(scale)


def dequantize_kv_rows(packed, scale, group: int, dtype=jnp.bfloat16):
    """Inverse of ``quantize_kv_rows`` -> (..., F) numpy array of
    ``dtype`` (the cache's compute precision).  Accepts host or device
    arrays directly (no forced host bounce)."""
    out = _dequantize_rows(jnp.asarray(packed), jnp.asarray(scale), group)
    return np.asarray(out.astype(dtype))


def kv_roundtrip_rows(x, group: Optional[int] = None):
    """quantize -> dequantize rows through the exact jitted ops the INT4
    streaming path uses, cast back to the input dtype — the reference
    transformation ``KVRoundtripServingEngine`` applies to newly-written
    cache rows so its tokens match the streamed engine's exactly."""
    g = group or kv_group(x.shape[-1])
    packed, scale = quantize_kv_rows(x, g)
    return dequantize_kv_rows(packed, scale, g, jnp.dtype(x.dtype))


def kv_roundtrip_traceable(x):
    """Traceable in-graph form of ``kv_roundtrip_rows`` for cache rows
    shaped ``(b, s, *feat)`` — the SAME quantize/dequantize ops
    ``save_decode``/``load`` run, so the result is bitwise what the host
    tier will serve back for these rows.  The speculative verify pass
    uses it so query ``t`` attends rows ``pos..pos+t-1`` at exactly the
    precision sequential decode would have read them at (they went
    through the store between sequential steps; in the fused verify pass
    they never left the device).  Ineligible leaves (odd flattened
    feature count) stream at full precision in the store, so they pass
    through unchanged here too.  Shape/group resolve at trace time."""
    feat = x.shape[2:]
    if not kv_eligible("kv", feat):
        return x
    F = int(np.prod(feat))
    g = kv_group(F)
    flat = x.reshape(x.shape[0], x.shape[1], F).astype(jnp.float32)
    packed, scale = _quantize_rows(flat, g)
    return _dequant_impl(packed, scale, g).reshape(x.shape).astype(x.dtype)


@dataclass
class _LeafMeta:
    """Per-leaf layout (kept public via ``leaf_meta`` for tests and
    byte-accounting consumers; the packed layout itself never leaves the
    store — ``load`` returns compute-precision leaves in every mode)."""
    kind: str                 # transformer cache kind ("kv"/"rep"/...)
    feat: Tuple[int, ...]     # trailing feature shape after (b[, L])
    dtype: Any                # compute-precision dtype of the leaf
    quant: bool = False       # stored packed INT4 (dequant on load)
    group: int = 0            # quant group over the flattened features


@dataclass
class _RawLeaf:
    arr: np.ndarray           # (b, ...) full precision


@dataclass
class _QuantLeaf:
    packed: np.ndarray        # (b, L, F//2) uint8
    scale: np.ndarray         # (b, L, F//g) f32
    group: int
    feat: Tuple[int, ...]     # original trailing feature shape
    dtype: Any                # original compute dtype


class TieredKVStore:
    """Host-resident decode cache with live-row loads and optional INT4
    row packing (see module docstring).

    ``unit_shapes``/``unit_kinds``: one dict per schedulable unit, name ->
    ((b_max, [max_len,] *feat) shape, dtype) / name -> cache kind, as
    produced by ``models.transformer.cache_struct`` (the engine strips
    the period-stack dim).  ``link`` is a ``transfer.SimLink`` (or any
    object with ``floor(nbytes, t0)``) shared with the weight store so KV
    pays the same simulated link.  ``target`` is the jax device loads land
    on (None: the default device)."""

    def __init__(self, unit_shapes: List[Dict[str, tuple]],
                 unit_kinds: List[Dict[str, str]], *, b_max: int,
                 max_len: int, kv_mode: str = "fp32", link=None,
                 target=None):
        assert kv_mode in ("fp32", "int4"), kv_mode
        self.b_max = b_max
        self.max_len = max_len
        self.kv_mode = kv_mode
        self.link = link
        self.target = target
        self.kinds: List[Dict[str, str]] = [dict(k) for k in unit_kinds]
        # running total of compute-precision bytes the load-side dequant
        # materialized — bounded by live extents, never the slab
        # (asserted in tests/test_kvstore.py); 0 forever under fp32
        self.dequant_bytes_total = 0
        self._units: List[Dict[str, Any]] = []
        self._meta: List[Dict[str, _LeafMeta]] = []
        for shapes, kinds in zip(unit_shapes, unit_kinds):
            leaves: Dict[str, Any] = {}
            meta: Dict[str, _LeafMeta] = {}
            for name, (shape, dtype) in shapes.items():
                kind = kinds[name]
                feat = tuple(shape[2:]) if kind == "kv" else tuple(shape[1:])
                m = _LeafMeta(kind, feat, np.dtype(dtype))
                if kv_mode == "int4" and kv_eligible(kind, feat):
                    F = int(np.prod(feat))
                    g = kv_group(F)
                    m.quant, m.group = True, g
                    leaves[name] = _QuantLeaf(
                        np.zeros((shape[0], shape[1], F // 2), np.uint8),
                        np.zeros((shape[0], shape[1], F // g), np.float32),
                        g, feat, np.dtype(dtype))
                else:
                    leaves[name] = _RawLeaf(np.zeros(shape, dtype))
                meta[name] = m
            self._units.append(leaves)
            self._meta.append(meta)

    # ---- layout introspection (main thread, build time) --------------------
    def __len__(self):
        return len(self._units)

    def leaf_meta(self, j: int) -> Dict[str, _LeafMeta]:
        """Per-leaf layout for unit ``j`` (introspection / tests)."""
        return self._meta[j]

    def has_kv(self, j: int) -> bool:
        return bool(self.kinds[j])

    # ---- byte accounting (any thread; non-blocking) ------------------------
    def _leaf_arrays(self, j: int, name: str):
        leaf = self._units[j][name]
        if isinstance(leaf, _QuantLeaf):
            return (leaf.packed, leaf.scale)
        return (leaf.arr,)

    def load_nbytes(self, j: int, live_b: Optional[int] = None,
                    live_len: Optional[int] = None) -> int:
        """Bytes one ``load(j, live_b, live_len)`` moves over the link —
        exactly the sliced rows (packed bytes for INT4 leaves).  This is
        what ``Task.nbytes`` records on KV_LOAD trace events and what
        ``AdaptiveDepth`` prices the window's KV term with."""
        lb = self.b_max if live_b is None else min(int(live_b), self.b_max)
        ll = self.max_len if live_len is None else min(int(live_len),
                                                      self.max_len)
        total = 0
        for name, m in self._meta[j].items():
            for a in self._leaf_arrays(j, name):
                shape = list(a.shape)
                shape[0] = lb
                if m.kind == "kv":
                    shape[1] = ll
                total += int(np.prod(shape)) * a.itemsize
        return total

    def slab_nbytes(self, j: int) -> int:
        """Bytes the full allocated ``(b_max, max_len)`` slab would move
        — the pre-live-row KV_LOAD payload, kept for tests/pricing."""
        return self.load_nbytes(j, self.b_max, self.max_len)

    def save_nbytes(self, j: int, live_b: Optional[int] = None,
                    rows: int = 1) -> int:
        """Bytes one decode ``save_decode`` payload moves device->host:
        the freshly-written rows of ``live_b`` slots at compute precision
        (quantization happens at the host tier, after the transfer).
        ``rows`` is the per-slot row count — 1 for plain decode, ``k+1``
        for a speculative verify pass (non-kv kinds ship full per-slot
        state either way)."""
        lb = self.b_max if live_b is None else min(int(live_b), self.b_max)
        total = 0
        for name, m in self._meta[j].items():
            row = int(np.prod(m.feat)) * np.dtype(m.dtype).itemsize
            if m.kind == "kv":
                row *= max(1, int(rows))
            total += lb * row
        return total

    def prefill_save_nbytes(self, j: int, live_b: int = 1,
                            length: Optional[int] = None) -> int:
        """Bytes a prefill save moves: ``live_b`` slots' rows at compute
        precision, ``length`` positions each for kv kinds (default the
        full per-slot extent — one slot's whole rows, the serving
        engine's per-slot admission payload)."""
        ll = self.max_len if length is None else min(int(length),
                                                     self.max_len)
        total = 0
        for name, m in self._meta[j].items():
            n = int(np.prod(m.feat)) * np.dtype(m.dtype).itemsize
            if m.kind == "kv":
                n *= ll
            total += n
        return total * max(1, int(live_b))

    def dequant_nbytes(self, j: int, live_b: Optional[int] = None,
                       live_len: Optional[int] = None) -> int:
        """Compute-precision bytes one ``load(j, live_b, live_len)``
        materializes on the transfer thread when unpacking INT4 leaves —
        the dequant cost, bounded by the live extent (0 in fp32 mode)."""
        lb = self.b_max if live_b is None else min(int(live_b), self.b_max)
        ll = self.max_len if live_len is None else min(int(live_len),
                                                      self.max_len)
        total = 0
        for name, m in self._meta[j].items():
            if m.quant:
                total += lb * ll * int(np.prod(m.feat)) \
                    * np.dtype(m.dtype).itemsize
        return total

    def max_live_load_nbytes(self, live_b: int, live_len: int) -> int:
        """Largest per-unit live KV_LOAD payload at the given extents —
        the exact per-layer KV price ``AdaptiveDepth`` feeds the memory
        model instead of the modeled slab."""
        return max(self.load_nbytes(j, live_b, live_len)
                   for j in range(len(self._units))) if self._units else 0

    def host_nbytes(self) -> int:
        """Total host bytes the store pins (packed bytes under INT4)."""
        return sum(a.nbytes for j in range(len(self._units))
                   for name in self._units[j]
                   for a in self._leaf_arrays(j, name))

    # ---- loads (transfer-pool thread) --------------------------------------
    def _bucket_len(self, ll: int) -> int:
        """``live_len`` rounded up to the shape bucket (see
        ``KV_LEN_BUCKET``), clamped to the slab extent."""
        return min(self.max_len,
                   -(-int(ll) // KV_LEN_BUCKET) * KV_LEN_BUCKET)

    @staticmethod
    def _bucketed(arr: np.ndarray, lb: int, ll: int, ll_b: int):
        """Host-side ``(lb, ll_b, ...)`` slice of a ``(b, L, ...)`` slab
        with the ``ll..ll_b`` tail zero-filled — the fixed-shape payload
        the shape-specialized device ops consume."""
        if ll_b == ll:
            return np.ascontiguousarray(arr[:lb, :ll])
        out = np.zeros((lb, ll_b) + arr.shape[2:], arr.dtype)
        out[:, :ll] = arr[:lb, :ll]
        return out

    def _put_padded(self, arr: np.ndarray, lb: int, ll: int, seq: bool):
        sl = arr[:lb, :ll] if seq else arr[:lb]
        if sl.shape == arr.shape:
            return jax.device_put(arr, self.target)
        if seq:
            ll_b = self._bucket_len(ll)
            rows = jax.device_put(self._bucketed(arr, lb, ll, ll_b),
                                  self.target)
            dev = jnp.zeros(arr.shape, rows.dtype, device=self.target)
            return dev.at[:lb, :ll_b].set(rows)
        rows = jax.device_put(np.ascontiguousarray(sl), self.target)
        dev = jnp.zeros(arr.shape, rows.dtype, device=self.target)
        return dev.at[tuple(slice(0, s) for s in sl.shape)].set(rows)

    def load(self, j: int, live_b: Optional[int] = None,
             live_len: Optional[int] = None) -> Dict[str, Any]:
        """KV_LOAD body: host rows -> device, sliced to the live extent
        and zero-padded back to the full slab shape (device side, after
        the link) so jitted consumers keep one signature.  INT4 leaves
        cross the link packed, then dequantize HERE — on the transfer
        thread, over only the live rows rounded up to the shape bucket
        (never the slab), the same post-link discipline as
        ``transfer._maybe_dequant`` for weights — so consumers receive
        plain compute-precision leaves in every mode.  Pays the link
        floor on exactly the (packed) live bytes; ``dequant_bytes_total``
        likewise prices the live extent (bucket padding is a
        compile-amortization detail, not modeled cost)."""
        t0 = time.perf_counter()
        lb = self.b_max if live_b is None else \
            max(1, min(int(live_b), self.b_max))
        ll = self.max_len if live_len is None else \
            max(1, min(int(live_len), self.max_len))
        out: Dict[str, Any] = {}
        for name, m in self._meta[j].items():
            leaf = self._units[j][name]
            if isinstance(leaf, _QuantLeaf):
                ll_b = self._bucket_len(ll)
                packed = jax.device_put(
                    self._bucketed(leaf.packed, lb, ll, ll_b), self.target)
                scale = jax.device_put(
                    self._bucketed(leaf.scale, lb, ll, ll_b), self.target)
                full = (self.b_max, self.max_len) + m.feat
                out[name] = _dequant_pad_rows(packed, scale, leaf.group,
                                              full, m.dtype)
                self.dequant_bytes_total += lb * ll \
                    * int(np.prod(m.feat)) * np.dtype(m.dtype).itemsize
            else:
                out[name] = self._put_padded(leaf.arr, lb, ll,
                                             seq=m.kind == "kv")
        for a in out.values():
            a.block_until_ready()
        if self.link is not None:
            self.link.floor(self.load_nbytes(j, lb, ll), t0)
        return out

    # ---- saves (transfer-pool thread) --------------------------------------
    def save_prefill(self, j: int, slot: int,
                     rows: Dict[str, np.ndarray]) -> None:
        """Scatter one slot's freshly-prefilled rows (name -> the slot's
        full per-slot extent, e.g. ``(max_len, *feat)`` for kv kinds).
        INT4 leaves quantize here — once per row; positions beyond the
        prompt are zeros and roundtrip to zeros exactly."""
        for name, m in self._meta[j].items():
            leaf = self._units[j][name]
            row = np.asarray(rows[name])
            if isinstance(leaf, _QuantLeaf):
                # cast to the cache's compute precision FIRST: the fp32
                # store path downcasts on assignment into the bf16 host
                # array, and the parity reference roundtrips bf16 cache
                # rows — quantizing the pre-cast f32 activations would
                # pick (slightly) different scales and break parity
                row = row.astype(m.dtype)
                F = int(np.prod(m.feat))
                packed, scale = quantize_kv_rows(
                    row.reshape(row.shape[0], F), leaf.group)
                leaf.packed[slot] = packed
                leaf.scale[slot] = scale
            else:
                leaf.arr[slot] = row

    def save_prefill_batch(self, j: int, rows: Dict[str, np.ndarray],
                           length: Optional[int] = None) -> None:
        """Scatter ALL slots' freshly-prefilled rows at once (name ->
        ``(b, length, *feat)`` live rows for kv kinds, ``(b, *feat)``
        for per-slot state) — the batch-generation admission path
        (``PipelinedLM``), where every slot prefills together.  Positions
        beyond ``length`` reset to zeros (and zeros roundtrip to zeros
        under INT4, so the tail stays value-invisible)."""
        for name, m in self._meta[j].items():
            leaf = self._units[j][name]
            row = np.asarray(rows[name])
            if isinstance(leaf, _QuantLeaf):
                row = row.astype(m.dtype)     # compute precision first
                ll = row.shape[1] if length is None else int(length)
                F = int(np.prod(m.feat))
                b = row.shape[0]
                packed, scale = quantize_kv_rows(
                    row[:, :ll].reshape(b, ll, F), leaf.group)
                leaf.packed[:b, :ll] = packed
                leaf.packed[:b, ll:] = 0
                leaf.scale[:b, :ll] = scale
                leaf.scale[:b, ll:] = 0
            elif m.kind == "kv":
                ll = row.shape[1] if length is None else int(length)
                b = row.shape[0]
                leaf.arr[:b, :ll] = row[:, :ll]
                leaf.arr[:b, ll:] = 0
            else:
                leaf.arr[:row.shape[0]] = row

    def save_decode(self, j: int, rows: Dict[str, np.ndarray],
                    active: Sequence[int], pos: np.ndarray) -> None:
        """Scatter a decode step's new rows: for kv kinds ``rows[name]``
        is ``(live_b, n, *feat)`` (slot s's ``n`` new rows at positions
        ``pos[s]..pos[s]+n-1`` — ``n == 1`` for plain decode, ``k+1``
        for a speculative verify pass), other kinds carry the full
        per-slot state.  INT4 leaves quantize the new rows — the only
        time they are ever quantized."""
        for name, m in self._meta[j].items():
            leaf = self._units[j][name]
            row = np.asarray(rows[name])
            if isinstance(leaf, _QuantLeaf):
                row = row.astype(m.dtype)     # compute precision first
                F = int(np.prod(m.feat))
                n = row.shape[1]
                packed, scale = quantize_kv_rows(
                    row.reshape(row.shape[0], n, F), leaf.group)
                for s in active:
                    p = int(pos[s])
                    leaf.packed[s, p:p + n] = packed[s]
                    leaf.scale[s, p:p + n] = scale[s]
            elif m.kind == "kv":
                n = row.shape[1]
                for s in active:
                    p = int(pos[s])
                    leaf.arr[s, p:p + n] = row[s]
            else:
                for s in active:
                    leaf.arr[s] = row[s]

    def truncate(self, slot: int, new_len: int) -> None:
        """Shrink one slot's live position extent to ``new_len`` rows:
        positions ``new_len..max_len-1`` reset to zeros across every
        unit's sequence-extent (kind ``'kv'``) leaves.  Packed-INT4-safe:
        zero packed bytes under zero scales dequantize to exact zeros
        (the same invariant ``save_prefill_batch`` tail-zeroing relies
        on), so a truncate-then-append round-trip is bit-exact in both
        modes.  This is the rejection path of speculative decoding — a
        verify pass appends ``k+1`` rows, then the engine truncates back
        to the accepted prefix.  Non-sequence leaves (rolling windows,
        SSM state) are rewritten every step and carry no position
        extent, so they are left untouched."""
        nl = max(0, min(int(new_len), self.max_len))
        for j in range(len(self._units)):
            for name, m in self._meta[j].items():
                leaf = self._units[j][name]
                if isinstance(leaf, _QuantLeaf):
                    leaf.packed[slot, nl:] = 0
                    leaf.scale[slot, nl:] = 0
                elif m.kind == "kv":
                    leaf.arr[slot, nl:] = 0

    # ---- slot spill/restore (transfer-pool / main thread) ------------------
    def spill(self, host, ns: str, slot: int) -> None:
        """Copy one slot's rows into ``host`` under ``{ns}/{unit}/{name}``
        keys.  INT4 rows spill packed (lossless; ~0.625 B/value against
        the 2 B bf16 cache, ~3x) under ``...{name}#q`` /
        ``...{name}#s``."""
        for j in range(len(self._units)):
            for name in self._units[j]:
                leaf = self._units[j][name]
                if isinstance(leaf, _QuantLeaf):
                    host.put(f"{ns}/{j}/{name}#q", leaf.packed[slot].copy())
                    host.put(f"{ns}/{j}/{name}#s", leaf.scale[slot].copy())
                else:
                    host.put(f"{ns}/{j}/{name}", leaf.arr[slot].copy())

    def restore(self, host, ns: str, slot: int) -> None:
        """Inverse of ``spill``: bring a parked request's rows back into
        ``slot``.  Bit-lossless in both modes (packed rows round-trip
        untouched)."""
        for j in range(len(self._units)):
            for name in self._units[j]:
                leaf = self._units[j][name]
                if isinstance(leaf, _QuantLeaf):
                    leaf.packed[slot] = host.get(f"{ns}/{j}/{name}#q")
                    leaf.scale[slot] = host.get(f"{ns}/{j}/{name}#s")
                else:
                    leaf.arr[slot] = host.get(f"{ns}/{j}/{name}")


class PhasedKVExtents:
    """Phase-aware KV hooks for the ``PipelineScheduler`` — one home for
    the prefill special-cases and live-extent pricing that used to be
    duplicated (asymmetrically) between ``OffloadedServingEngine`` and
    ``PipelinedLM``.

    The host engine answers what an iteration is doing and what is live;
    the mixin derives the scheduler-facing ``kv_nbytes`` / ``kv_extent``
    / ``kv_save_nbytes`` / ``load_kv`` from the answers, so both engines
    share one statement of the invariants:

      * a **prefill** iteration builds fresh caches in-pass — no KV
        loads cross the link (``load_kv`` returns None; a warm tail
        preload issued during a prefill is thereby *poisoned* and must
        be dropped by the engine before the next decode consumes it),
        and the save ships the whole prompt's rows;
      * a **decode** iteration loads the live ``(slots, positions)``
        extent and saves one (or ``k+1`` speculative) fresh row(s) per
        live slot;
      * a **chunk** iteration (chunked-prefill-only engine step) loads
        nothing — the chunk attends the engine-held fp32 prefix, not
        the store — and only the chunk's append crosses on the save.

    Pricing (``kv_nbytes``/``kv_save_nbytes``) and shipping (``load_kv``)
    share the same ``_kv_live`` extents, so trace bytes never overstate
    what crossed.  Host hooks::

        _kv_phase(i)   -> "prefill" | "decode" | "chunk"
        _kv_live(i)    -> (live_batch, live_len) of iteration i's load
        _kv_streams(j) -> does unit j's cache cross the link at all?
        _kv_prefill_save_nbytes(j)   whole-prompt save payload bytes
        _kv_chunk_save_nbytes(j)     in-flight chunk append bytes (0
                                     unless a chunked engine overrides)

    plus ``self.kvstore`` (a ``TieredKVStore``).  Engines with a
    device-resident tier override ``load_kv`` and fall through to
    ``super()`` for the streamed path."""

    kvstore: "TieredKVStore"

    # ---- host hooks ---------------------------------------------------------
    def _kv_phase(self, i: int) -> str:
        raise NotImplementedError

    def _kv_live(self, i: int) -> Tuple[int, int]:
        raise NotImplementedError

    def _kv_streams(self, j: int) -> bool:
        raise NotImplementedError

    def _kv_prefill_save_nbytes(self, j: int) -> int:
        raise NotImplementedError

    def _kv_chunk_save_nbytes(self, j: int) -> int:
        return 0

    def _kv_save_rows(self) -> int:
        """Rows per live slot a decode save ships (k+1 for a speculative
        verify pass)."""
        return getattr(self, "_spec_s", 1)

    # ---- derived PipelineScheduler callbacks (any thread) -------------------
    def kv_nbytes(self, i: int, j: int) -> int:
        """Bytes iteration i's KV_LOAD of unit j moves over the link —
        the LIVE rows only (packed bytes under ``kv_mode='int4'``), 0
        outside decode.  Recorded on trace events so transfer volume
        (and the live-row saving) is assertable from ``Trace.report()``."""
        if not self._kv_streams(j) or self._kv_phase(i) != "decode":
            return 0
        lb, ll = self._kv_live(i)
        return self.kvstore.load_nbytes(j, lb, ll)

    def kv_extent(self, i: int, j: int):
        """Live (batch, len) of iteration i's KV_LOAD payload — recorded
        on the trace event (None outside decode)."""
        if not self._kv_streams(j) or self._kv_phase(i) != "decode":
            return None
        return self._kv_live(i)

    def kv_save_nbytes(self, i: int, j: int) -> int:
        """Bytes iteration i's KV_SAVE payload moves device->host:
        prefill ships whole prompt rows, decode the live slots' fresh
        rows, and an in-flight prefill chunk adds its append on top."""
        if not self._kv_streams(j):
            return 0
        phase = self._kv_phase(i)
        if phase == "prefill":
            return self._kv_prefill_save_nbytes(j)
        n = self._kv_chunk_save_nbytes(j)
        if phase == "decode":
            lb, _ = self._kv_live(i)
            n += self.kvstore.save_nbytes(j, lb, rows=self._kv_save_rows())
        return n

    def load_kv(self, i: int, j: int):
        """KV_LOAD body (transfer-pool thread): live host rows -> device
        slab via the tiered store.  None outside decode — prefill/chunk
        iterations build or extend caches in-pass."""
        if not self._kv_streams(j) or self._kv_phase(i) != "decode":
            return None
        lb, ll = self._kv_live(i)
        return self.kvstore.load(j, lb, ll)
