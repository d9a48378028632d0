"""Resident-weight continuous-batching serving engine.

All parameters stay in device memory; each engine step decodes ALL active
slots with *ragged* per-slot positions (one jitted whole-model decode for
the batch).  Slot admission / completion / preemption policy lives in
``serving.base.SlotEngineBase``; the offloaded twin that streams weights
through the PIPO pipeline is ``serving.offload_engine``.

Slot KV spill/restore (``offload_slot``/``restore_slot``) snapshots the
immutable cache pytree, so when a transfer pool is attached the spill runs
as a PIPO KV_SAVE task overlapping subsequent decode steps.

This engine also carries the architectures the offloaded engine can't
(``serving.spec.offload_capability``): encoder-decoder stacks (whisper —
per-request ``Request.enc_embeds`` frames, zero-frame stub when absent)
and embeds-frontend configs (qwen2-vl — token prompts run through the
shared embedding table, the text-only stub), so ``create_engine`` has a
resident fallback for every registry config.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pipeline import ThreadPool
from repro.models import Dist, build_model
from repro.models import transformer as T
from repro.serving.base import Request, SlotEngineBase
from repro.serving.spec import ResolvedPlan

__all__ = ["Request", "ServingEngine", "KVRoundtripServingEngine"]


class ServingEngine(SlotEngineBase):
    def __init__(self, cfg: "ModelConfig | ResolvedPlan", *, b_max: int = 4,
                 max_len: int = 256, seed: int = 0,
                 kv_pool: Optional[ThreadPool] = None, spill_cap: int = 32):
        if isinstance(cfg, ResolvedPlan):
            self.plan: Optional[ResolvedPlan] = cfg
            cfg = self.plan.model_config()
            b_max, max_len = self.plan.b_max, self.plan.max_len
            seed, spill_cap = self.plan.seed, self.plan.spill_cap
        else:
            self.plan = None
        super().__init__(cfg, b_max=b_max, max_len=max_len, kv_pool=kv_pool,
                         spill_cap=spill_cap)
        self.dist = Dist.local()
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed), jnp.float32)
        if self.plan is not None and self.plan.moe_quant:
            # INT4-resident MoE: pack the routed expert stacks once at
            # load, from the f32 draw; decode unpacks them through the
            # fused-int4 path
            from repro.serving.spec import quant_policy_for
            self.params = quant_policy_for(
                self.plan.quant, self.plan.kv_mode,
                self.plan.moe_quant).prepare_moe_params(self.params)
        # layer tensors at the configuration's dtype, as every serving
        # engine holds them
        self.params = T.served_params(cfg, self.params)
        self.caches = self.model.init_cache(
            b_max, max_len, cfg.encoder_seq_len if cfg.enc_dec else None)
        self._jit()

    def _jit(self):
        m, dist = self.model, self.dist

        def decode(params, tok, pos, caches):
            return m.decode_step(params, {"token": tok, "pos": pos}, caches,
                                 dist)
        self._decode = jax.jit(decode, donate_argnums=(3,))

        def prefill1(params, batch, cache_len):
            return m.prefill(params, batch, dist, cache_len)
        self._prefill = jax.jit(prefill1, static_argnums=(2,))

    def _prefill_batch(self, req: Request) -> dict:
        """b=1 prompt batch: token prompts always embed through the
        shared table (the text-only stub for embeds-frontend configs);
        enc-dec configs additionally carry encoder frames — the
        request's ``enc_embeds`` or a zero-frame stub."""
        batch = {"tokens": jnp.asarray(req.prompt)[None]}
        if self.cfg.enc_dec:
            enc = req.enc_embeds
            if enc is None:
                enc = np.zeros((self.cfg.encoder_seq_len, self.cfg.d_model),
                               np.float32)
            batch["enc_embeds"] = jnp.asarray(enc)[None]
        return batch

    # ---- compute ------------------------------------------------------------
    def _prefill_into_slot(self, slot: int, req: Request) -> int:
        nt, cache1 = self._prefill(self.params, self._prefill_batch(req),
                                   self.max_len)
        # scatter the b=1 cache rows into the slot (KV "admission")
        self.caches = self._map_slot(
            self.caches, cache1,
            lambda big, one, idx: big.at[idx].set(one.astype(big.dtype)),
            slot)
        return int(np.asarray(nt)[0])

    def _decode_active(self, active: List[int]) -> np.ndarray:
        tok = jnp.asarray(self.tokens)[:, None]
        pos = jnp.asarray(self.pos)
        nt, self.caches = self._decode(self.params, tok, pos, self.caches)
        return np.asarray(nt)

    # ---- slot cache plumbing ------------------------------------------------
    @staticmethod
    def _batch_axis(path) -> int:
        """Cache leaves under 'pat' are stacked (periods, b, ...); under
        'rem' they are (b, ...)."""
        head = str(getattr(path[0], "key", getattr(path[0], "idx", path[0])))
        return 1 if head == "pat" else 0

    def _map_slot(self, big_tree, one_tree, fn, slot):
        flat_big, treedef = jax.tree_util.tree_flatten_with_path(big_tree)
        flat_one = treedef.flatten_up_to(one_tree) if one_tree is not None \
            else [None] * len(flat_big)
        out = []
        for (path, big), one in zip(flat_big, flat_one):
            ax = self._batch_axis(path)
            idx = [slice(None)] * big.ndim
            idx[ax] = slice(slot, slot + 1)
            out.append(fn(big, one, tuple(idx)))
        return jax.tree_util.tree_unflatten(treedef, out)

    # ---- PIPO KV offload at slot granularity --------------------------------
    def _offload_snapshot(self, slot: int):
        # Slice the slot's rows into fresh device arrays NOW: ``_decode`` is
        # jitted with donate_argnums, so the current cache buffers are
        # deleted by the next decode step — a bare reference would be read
        # after free on the transfer thread.  The slices are small
        # device-side copies; the expensive device->host transfer still
        # happens on the pool thread.
        flat_big, _ = jax.tree_util.tree_flatten_with_path(self.caches)
        rows = []
        for path, leaf in flat_big:
            ax = self._batch_axis(path)
            idx = [slice(None)] * leaf.ndim
            idx[ax] = slot
            rows.append(leaf[tuple(idx)])
        for r in rows:
            r.block_until_ready()
        return rows

    def _offload_write(self, ns: str, rows):
        """Device->host spill of one slot's cache rows under ``{ns}/{i}``
        keys.  Runs on a transfer-pool thread when kv_pool is attached."""
        for i, row in enumerate(rows):
            self.host.put(f"{ns}/{i}", np.asarray(row))

    def restore_slot(self, slot: int, ns: str):
        """KV-load: bring an offloaded request's rows (namespace ``ns``)
        back into a slot.  Main thread; blocking."""
        flat_big, treedef = jax.tree_util.tree_flatten_with_path(self.caches)
        out = []
        for i, (path, leaf) in enumerate(flat_big):
            ax = self._batch_axis(path)
            row = jnp.asarray(self.host.get(f"{ns}/{i}"))
            idx = [slice(None)] * leaf.ndim
            idx[ax] = slot
            out.append(leaf.at[tuple(idx)].set(row.astype(leaf.dtype)))
        self.caches = jax.tree_util.tree_unflatten(
            treedef, out)


class KVRoundtripServingEngine(ServingEngine):
    """The ``kv_mode="int4"`` parity reference: a resident engine whose
    newly-written cache rows are roundtripped through the EXACT
    quantize->dequantize the tiered KV store applies to streamed rows
    (``core.kvstore.kv_roundtrip_rows``) — once per row, right after it
    is written, mirroring the store's quantize-at-save discipline.  An
    offloaded engine with ``kv_mode="int4"`` must decode token-identical
    to this reference (the KV analogue of ``quant_roundtrip_params`` for
    weights; asserted per depth x weight-quant in
    tests/test_serving_offload.py).

    Only sequence-extent (kind ``"kv"``) leaves with an even feature
    count roundtrip — the same ``kv_eligible`` predicate the store uses,
    so the two can never drift."""

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        from repro.models import transformer as T
        _, self._kv_kinds = T.cache_struct(
            self.cfg, self.b_max, self.max_len,
            self.cfg.encoder_seq_len if self.cfg.enc_dec else None)

    def _leaf_kind(self, path) -> str:
        head = str(getattr(path[0], "key", path[0]))
        idx = int(getattr(path[1], "idx", getattr(path[1], "key", path[1])))
        name = str(getattr(path[2], "key", path[2]))
        return self._kv_kinds[head][idx][name]

    def _roundtrip_slot_rows(self, slot: int, pos=None):
        """Roundtrip slot ``slot``'s eligible cache rows in place: every
        position (after a prefill scattered the whole slot row) or just
        position ``pos`` (after a decode step wrote one row)."""
        from repro.core.kvstore import kv_eligible, kv_roundtrip_rows
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.caches)
        out = []
        for path, leaf in flat:
            ax = self._batch_axis(path)
            kind = self._leaf_kind(path)
            feat = leaf.shape[ax + 2:]
            if not kv_eligible(kind, feat):
                out.append(leaf)
                continue
            idx = [slice(None)] * leaf.ndim
            idx[ax] = slot
            if pos is not None:
                idx[ax + 1] = pos
            rows = np.asarray(leaf[tuple(idx)])
            f = int(np.prod(feat))
            lead = rows.shape[:rows.ndim - len(feat)]
            rt = kv_roundtrip_rows(rows.reshape(lead + (f,)))
            rt = rt.reshape(rows.shape)
            out.append(leaf.at[tuple(idx)].set(
                jnp.asarray(rt).astype(leaf.dtype)))
        self.caches = jax.tree_util.tree_unflatten(treedef, out)

    def _prefill_into_slot(self, slot: int, req: Request) -> int:
        tok = super()._prefill_into_slot(slot, req)
        self._roundtrip_slot_rows(slot)
        return tok

    def _decode_active(self, active):
        nt = super()._decode_active(active)
        for s in active:
            # base increments pos AFTER this returns: pos[s] is the row
            # this step just wrote — roundtrip it exactly once
            self._roundtrip_slot_rows(s, int(self.pos[s]))
        return nt
