"""PipelinedLM: a generation engine whose weights/KV live in memory tiers
and move through the PIPO pipeline (the paper's system, end to end).

Layer granularity follows the paper ("treating MHA and MLP as separate
layers"): the schedulable unit list is [mha_0, mlp_0, mha_1, mlp_1, ...].
Per unit, weights are *merged* into one contiguous buffer (transfer suite
§3.3) living on the placement tier; the KV cache lives in the SAME
``core.kvstore.TieredKVStore`` the serving engines use (``cache_on=
"host"``): every KV_LOAD ships only the live ``(batch, positions)``
rows, ``kv_mode="int4"`` streams them packed (dequantized post-link on
the transfer thread), and both are byte-accounted on the trace.  With
``cache_on="device"`` the cache is device-resident — KV_SAVE refreshes
the device store and nothing crosses the link.

Compute units are jitted once per (kind, phase) and run on the main
thread; weight-load / kv-load / kv-save run on the 3-thread pool per
Algorithm 1.  INT4 weights halve..quarter transfer bytes and the fused
dequant-matmul path is the paper's compute-kernel optimization (§3.4).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MOE, ModelConfig
from repro.core.draft import accept_length
from repro.core.kvstore import (PhasedKVExtents, TieredKVStore,
                                kv_roundtrip_traceable)
from repro.core.offload import DeviceStore, DiskStore, HostStore
from repro.core.pipeline import PipelineScheduler, ThreadPool
from repro.core.tasks import Trace
from repro.core.transfer import Manifest, TieredWeightStore
from repro.models import transformer as T
from repro.models.attention import (decode_attention, ref_attention,
                                    spec_decode_attention)
from repro.models.common import rms_norm, silu
from repro.models.rope import apply_rope, rope_angles
from repro.quant.int4 import quantize_int4

# pre-spec constructor defaults: the deprecation shim overlays provided
# kwargs on these so a legacy call resolves to the exact plan the old
# constructor acted on (note depth defaulted to 1 here, NOT auto)
_LEGACY_DEFAULTS = dict(
    batch=4, max_len=256, placement="host", cache_on="host",
    pipeline="performance", quant=None, kv_mode=None, fused_int4=True,
    disk_root="/tmp/pipo_disk", block_bytes=None, n_io_threads=3,
    cold_reads=False, seed=0, depth=1)


# ---------------------------------------------------------------------------
# Per-unit compute (jitted)
# ---------------------------------------------------------------------------


def _qkv(x, w, pos, cfg: ModelConfig):
    b, s, d = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    xn = rms_norm(x, w["norm"], cfg.norm_eps)
    q = (xn @ w["wq"]).reshape(b, s, h, dh)
    k = (xn @ w["wk"]).reshape(b, s, hkv, dh)
    v = (xn @ w["wv"]).reshape(b, s, hkv, dh)
    angles = rope_angles(pos + jnp.arange(s), dh, cfg.rope_theta)
    return apply_rope(q, angles), apply_rope(k, angles), v


def _attn_prefill_unit(x, w, *, cfg: ModelConfig):
    """Prefill attends within the prompt only — no cache is consumed.
    Returns (x', k_new, v_new) with k/v (b, s, hkv, dh)."""
    b, s, d = x.shape
    q, k, v = _qkv(x, w, jnp.int32(0), cfg)
    out = ref_attention(q, k, v, causal=True)
    return x + out.reshape(b, s, -1) @ w["wo"], k, v


def _attn_decode_unit(x, w, kc, vc, pos, *, cfg: ModelConfig,
                      kv_roundtrip=None):
    """x (b, s, d) — s == 1 for plain decode, k+1 for a speculative
    verify pass (the current token plus the draft's proposals, scored in
    one ragged step); kc/vc (b, L, hkv, dh) device copies of the tiered
    cache.  ``kv_roundtrip`` (host cache tier + kv_mode='int4') lets the
    verify pass attend its own earlier rows at the precision sequential
    decode would reload them at.  Returns (x', k_new, v_new, kc', vc')
    — the functionally updated caches back the ``cache_on="device"``
    store refresh; host mode persists through the KV store instead and
    drops them."""
    b, s, d = x.shape
    q, k, v = _qkv(x, w, pos, cfg)
    if s > 1:
        out, kc, vc = spec_decode_attention(q, kc, vc, k, v, pos,
                                            kv_roundtrip=kv_roundtrip)
    else:
        out, kc, vc = decode_attention(q, kc, vc, k, v, pos, axes=())
    return x + out.reshape(b, s, -1) @ w["wo"], k, v, kc, vc


def _mlp_unit(x, w, *, cfg: ModelConfig):
    xn = rms_norm(x, w["norm"], cfg.norm_eps)
    hdn = silu(xn @ w["w_gate"]) * (xn @ w["w_up"])
    return x + hdn @ w["w_down"]


def _gate_unit(x, wg, *, top_k: int):
    """Router: returns (weights (b*s, k), ids (b*s, k)) for the flat batch."""
    b, s, d = x.shape
    logits = x.reshape(b * s, d) @ wg
    vals, ids = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(vals.astype(jnp.float32), axis=-1)
    return w, ids


def _expert_unit(x, w, *, cfg: ModelConfig):
    """One expert's FFN on the full batch (combined with router weights
    outside)."""
    xn = rms_norm(x, w["norm"], cfg.norm_eps)
    hdn = silu(xn @ w["w_gate"]) * (xn @ w["w_up"])
    return hdn @ w["w_down"]


def _embed_unit(tokens, emb):
    return jnp.take(emb, tokens, axis=0)


def _head_unit(x, emb):
    return jnp.argmax(x[:, -1].astype(jnp.float32) @ emb.T, axis=-1)


def _spec_head_unit(x, emb):
    """Per-POSITION greedy argmax for the verify pass: each of the b*s
    rows goes through exactly ``_head_unit``'s row arithmetic, so the
    per-position tokens match what s sequential single-token heads
    would emit.  x (b, s, d) -> (b, s) int32."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d).astype(jnp.float32) @ emb.T
    return jnp.argmax(flat, axis=-1).reshape(b, s)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclass
class UnitSpec:
    kind: str           # "mha" | "mlp"
    layer: int
    key: str            # store key


class PipelinedLM(PhasedKVExtents):
    """Offloaded generation per PIPO.

    placement: "device" | "host" | "disk" — where the merged unit weights
    live (paper's Weight-on GPU/CPU/Disk).  cache_on: "host" | "device".
    pipeline: "performance" | "memory" | "sequential".
    quant: None | "int4".  Unit weights are drawn at f32 and held at the
    configuration's dtype (``transformer.served_layer``), like every
    serving engine's.
    depth: performance-pipeline preload window (layers in flight beyond
    the computing one; 1 = the paper's two-resident-layer invariant).
    """

    def __init__(self, plan=None, **legacy_kwargs):
        """Canonical construction takes ONE argument: a ``ResolvedPlan``
        (``serving.spec.build_lm(plan)``; the plan's ``b_max`` is the
        generation batch).  Passing a ``ModelConfig`` plus the pre-spec
        keyword arguments still works through a deprecation shim — the
        kwargs are converted to an ``EngineSpec`` and resolved, so both
        paths act on an identical plan."""
        from repro.serving.spec import (EngineSpec, ResolvedPlan,
                                        draft_policy_for,
                                        warn_deprecated_once)
        if isinstance(plan, ModelConfig):
            warn_deprecated_once(
                "PipelinedLM.legacy_kwargs",
                "PipelinedLM(cfg, **kwargs) is deprecated; build an "
                "EngineSpec and pass its resolved plan "
                "(serving.spec.build_lm) instead")
            unknown = set(legacy_kwargs) - set(_LEGACY_DEFAULTS)
            if unknown:
                raise TypeError(f"unknown kwargs {sorted(unknown)}")
            kw = {**_LEGACY_DEFAULTS, **legacy_kwargs}
            spec = EngineSpec(
                arch=plan.name, cfg=plan, offload=True,
                placement=kw["placement"],
                b_max=kw["batch"], max_len=kw["max_len"],
                pipeline=kw["pipeline"], quant=kw["quant"],
                kv_mode=kw["kv_mode"],
                fused_int4=kw["fused_int4"], depth=kw["depth"],
                cache_on=kw["cache_on"], disk_root=kw["disk_root"],
                block_bytes=kw["block_bytes"],
                n_io_threads=kw["n_io_threads"],
                cold_reads=kw["cold_reads"], seed=kw["seed"])
            plan = spec.resolve()
        elif not isinstance(plan, ResolvedPlan):
            raise TypeError(f"PipelinedLM takes a ResolvedPlan or a "
                            f"ModelConfig, got {type(plan).__name__}")
        elif legacy_kwargs:
            raise TypeError("plan construction takes no kwargs; set the "
                            "fields on the EngineSpec instead")
        cfg = plan.model_config()
        self.plan = plan
        self.cfg = cfg
        self.batch = plan.b_max
        self.max_len = plan.max_len
        self.placement = plan.placement
        self.cache_on = plan.cache_on
        self.quant = plan.quant
        self.kv_mode = plan.kv_mode or "fp32"
        self.depth = max(1, plan.depth)
        self.trace = Trace()
        self.host = HostStore()
        self.device = DeviceStore()
        self.disk = DiskStore(plan.disk_root)
        self.weights = TieredWeightStore(
            placement=plan.placement, host=self.host, device=self.device,
            disk=self.disk, quant=plan.quant, fused_int4=plan.fused_int4,
            block_bytes=plan.block_bytes, n_io_threads=plan.n_io_threads,
            cold_reads=plan.cold_reads, sim_bw=plan.sim_bw)
        self.pipeline_mode = plan.pipeline
        self.units: list[UnitSpec] = []
        self._build(plan.seed)
        self._kv_init()
        self._jit_units()
        # speculative decoding (core.draft): device-resident draft
        # proposes, the streamed target verifies k+1 positions per trip
        self.draft = None
        self._spec_k = 0
        self._spec_s = 1                 # rows the current step writes
        self._spec_mode = False
        self._iter_pos: Dict[int, int] = {}   # global iter -> start pos
        dp = draft_policy_for(plan)
        if dp is not None:
            self.attach_draft(
                dp.build(b_max=plan.b_max, max_len=plan.max_len), dp.k)

    def attach_draft(self, draft, k: int):
        """Enable speculative decoding with ``draft`` — anything with
        ``prefill_batch(tokens)`` and ``propose(tokens, pos, k) ->
        (batch, k)`` (``core.draft.ResidentDraft``, or a test fake).
        The uniform-batch engine advances all rows in lockstep, so a
        step accepts min-over-rows proposals; rows that accepted more
        re-derive their surplus next step (greedy decode is
        deterministic, so the stream stays bit-identical).  Main
        thread, before ``generate``."""
        if self.cfg.moe is not None:
            raise ValueError(
                "speculative decoding needs a dense stack: routing k+1 "
                "tokens jointly would change MoE capacity assignment "
                "versus sequential decode, breaking token parity")
        self.draft = draft
        self._spec_k = max(1, int(k))

    # -- weights -------------------------------------------------------------
    def _unit_tensors(self, kind: str, rng: np.random.Generator):
        cfg = self.cfg
        d, h, hkv, dh = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                         cfg.head_dim)
        s = 1.0 / math.sqrt(d)
        mk = lambda *shape: (rng.standard_normal(shape) * s).astype(np.float32)
        if kind == "mha":
            t = {"wq": mk(d, h * dh), "wk": mk(d, hkv * dh),
                 "wv": mk(d, hkv * dh), "wo": mk(h * dh, d),
                 "norm": np.zeros((d,), np.float32)}
        else:
            t = {"w_gate": mk(d, cfg.d_ff), "w_up": mk(d, cfg.d_ff),
                 "w_down": mk(cfg.d_ff, d) * (1.0 / math.sqrt(cfg.d_ff / d)),
                 "norm": np.zeros((d,), np.float32)}
        if self.quant == "int4":
            qt = {}
            for name, arr in t.items():
                if arr.ndim == 2 and arr.shape[0] % 128 == 0:
                    packed, scale = quantize_int4(jnp.asarray(arr))
                    qt[name + "#q"] = np.asarray(packed)
                    qt[name + "#s"] = np.asarray(scale)
                else:
                    qt[name] = arr
            t = qt
        return T.served_layer(cfg, t, self.quant)

    def _put_tier(self, key: str, tensors: dict):
        self.weights.put(key, tensors)

    @property
    def manifests(self) -> Dict[str, Manifest]:
        return self.weights.manifests

    def _build(self, seed: int):
        cfg = self.cfg
        rng = np.random.default_rng(seed)
        emb = (rng.standard_normal((cfg.vocab_size, cfg.d_model))
               * (1.0 / math.sqrt(cfg.d_model))).astype(np.float32)
        self.device.put("emb", emb)      # embeddings stay on device (small)
        moe = cfg.moe
        for l in range(cfg.num_layers):
            key = f"mha[{l}]"
            self._put_tier(key, self._unit_tensors("mha", rng))
            self.units.append(UnitSpec("mha", l, key))
            if moe is not None:
                # router stays on device (tiny; needed before any prefetch)
                d = cfg.d_model
                self.device.put(f"wg[{l}]",
                                (rng.standard_normal((d, moe.num_experts))
                                 / math.sqrt(d)).astype(np.float32)
                                .astype(T.served_dtype(cfg, "wg")))
                for e in range(moe.num_experts):
                    self._put_tier(f"exp[{l}][{e}]",
                                   self._unit_tensors("mlp", rng))
                if moe.num_shared:
                    self._put_tier(f"shx[{l}]",
                                   self._unit_tensors("mlp", rng))
                self.units.append(UnitSpec("moe", l, f"shx[{l}]"))
            else:
                key = f"mlp[{l}]"
                self._put_tier(key, self._unit_tensors("mlp", rng))
                self.units.append(UnitSpec("mlp", l, key))

    # -- KV cache --------------------------------------------------------------
    def _kv_init(self):
        """One KV path for both engines: the host cache is a
        ``TieredKVStore`` indexed by schedulable unit (mha units carry
        ``k``/``v`` slabs, mlp/moe units are empty), sharing the weight
        store's link so live-row/INT4 byte reductions pay the same
        simulated interconnect serving pays.  ``cache_on="device"``
        keeps plain device arrays (nothing ever crosses the link)."""
        cfg = self.cfg
        shape = (self.batch, self.max_len, cfg.num_kv_heads, cfg.head_dim)
        if self.cache_on == "host":
            shapes = [({"k": (shape, np.float32), "v": (shape, np.float32)}
                       if u.kind == "mha" else {}) for u in self.units]
            kinds = [({"k": "kv", "v": "kv"} if u.kind == "mha" else {})
                     for u in self.units]
            self.kvstore = TieredKVStore(
                shapes, kinds, b_max=self.batch, max_len=self.max_len,
                kv_mode=self.kv_mode, link=self.weights.link)
        else:
            self.kvstore = None
            for l in range(cfg.num_layers):
                self.device.put(f"kc[{l}]", np.zeros(shape, np.float32))
                self.device.put(f"vc[{l}]", np.zeros(shape, np.float32))

    # -- jitted units ------------------------------------------------------------
    def _jit_units(self):
        cfg = self.cfg
        self._attn_prefill = jax.jit(partial(_attn_prefill_unit, cfg=cfg))
        rt = (kv_roundtrip_traceable
              if self.cache_on == "host" and self.kv_mode == "int4" else None)
        self._attn_decode = jax.jit(partial(_attn_decode_unit, cfg=cfg,
                                            kv_roundtrip=rt))
        self._mlp = jax.jit(partial(_mlp_unit, cfg=cfg))
        self._embed = jax.jit(_embed_unit)
        self._head = jax.jit(_head_unit)
        self._spec_head = jax.jit(_spec_head_unit)
        if cfg.moe is not None:
            self._gate = jax.jit(partial(_gate_unit, top_k=cfg.moe.top_k))
            self._expert = jax.jit(partial(_expert_unit, cfg=cfg))
        self._pool = None  # set by generate()

    # -- scheduler callbacks ------------------------------------------------------
    def is_mha(self, j: int) -> bool:
        return self.units[j].kind == "mha"

    def _load_key(self, key: str):
        return self.weights.load(key)

    def load_weights(self, j: int):
        u = self.units[j]
        if u.kind == "moe" and self.cfg.moe.num_shared == 0:
            return {}
        return self._load_key(u.key)

    def weight_nbytes(self, j: int) -> int:
        """Bytes unit j's base WEIGHT_LOAD moves (trace byte accounting)."""
        u = self.units[j]
        if u.kind == "moe" and self.cfg.moe.num_shared == 0:
            return 0
        return self.weights.nbytes(u.key)

    def release_weights(self, j: int, handle):
        del handle  # device arrays freed by GC; stores unaffected

    def _live_len(self, i: int) -> int:
        """Sequence rows iteration ``i``'s decode attention actually
        reads: the prompt plus the decode rows already saved (rows
        ``0..pos-1``; the rows at ``pos..`` arrive with the step's own
        k/v).  Iteration 0 is the prefill — no cache is consumed.
        Non-speculative decode is a pure function of ``i`` (one row per
        iteration) so warm cross-call preloads price exactly what they
        later ship; speculative steps advance by a variable 1..k+1 rows,
        so the per-iteration start positions are PLANNED on the main
        thread before submission (``_iter_pos``; the next iteration is
        planned at full acceptance — a superset when rows are rejected,
        and superset rows are zeros the attention mask ignores)."""
        if self._spec_mode:
            return min(self._iter_pos.get(i, self.max_len), self.max_len)
        return min(self._prompt_len + i - 1, self.max_len)

    # ``kv_nbytes``/``kv_extent``/``kv_save_nbytes``/``load_kv`` come
    # from ``PhasedKVExtents`` (the phase-aware logic shared with the
    # serving engines); the host hooks below feed it.
    def _kv_phase(self, i: int) -> str:
        """Iteration 0 is the batch prefill.  Phase is a pure function
        of the GLOBAL iteration index — never the ``_phase`` mode flag —
        so warm cross-call preloads price exactly what they later
        ship."""
        return "prefill" if i == 0 else "decode"

    def _kv_live(self, i: int):
        return (self.batch, self._live_len(i))

    def _kv_streams(self, j: int) -> bool:
        return self.cache_on == "host" and self.is_mha(j)

    def _kv_prefill_save_nbytes(self, j: int) -> int:
        return self.kvstore.prefill_save_nbytes(j, self.batch,
                                                self._prompt_len)

    def load_kv(self, i: int, j: int):
        if self.cache_on == "device":
            l = self.units[j].layer
            return {"k": self.device.get(f"kc[{l}]"),
                    "v": self.device.get(f"vc[{l}]")}
        return super().load_kv(i, j)

    def save_kv(self, i: int, j: int, new_kv):
        phase, k_new, v_new, pos, length = new_kv
        if self.cache_on == "device":
            # device-resident cache: refresh the store with the updated
            # arrays; the scheduler's save-before-load ordering makes
            # them visible to the next iteration's load (no bytes cross
            # the link).  Decode ships the functionally-updated caches
            # whole; the prefill ships the prompt's rows, scattered here.
            l = self.units[j].layer
            if phase == "prefill":
                k_new = self.device.get(f"kc[{l}]").at[:, :length].set(k_new)
                v_new = self.device.get(f"vc[{l}]").at[:, :length].set(v_new)
            self.device.put(f"kc[{l}]", k_new)
            self.device.put(f"vc[{l}]", v_new)
            return
        rows = {"k": k_new, "v": v_new}
        if phase == "prefill":
            self.kvstore.save_prefill_batch(j, rows, length)
        else:
            self.kvstore.save_decode(j, rows, active=range(self.batch),
                                     pos=np.full(self.batch, pos, np.int32))

    def compute(self, i: int, j: int, x, weights, kv):
        u = self.units[j]
        if u.kind == "mlp":
            return self._mlp(x, weights), None
        if u.kind == "moe":
            return self._compute_moe(u, x, weights), None
        pos = self._pos
        if self._phase == "prefill":
            x, k, v = self._attn_prefill(x, weights)
            return x, ("prefill", k, v, 0, x.shape[1])
        x, k, v, kc, vc = self._attn_decode(x, weights, kv["k"], kv["v"],
                                            jnp.int32(pos))
        if self.cache_on == "device":
            # ship the whole updated caches to the save task (device
            # puts, no link crossing); host mode ships only the new
            # rows (1 plain, k+1 for a speculative verify pass)
            return x, ("decode", kc, vc, int(pos), x.shape[1])
        return x, ("decode", k, v, int(pos), x.shape[1])

    def _compute_moe(self, u, x, shared_w):
        """Paper Appendix C.4: the gate forces a sync (experts unknown until
        it runs); then the union of routed experts is loaded through the
        pool while the shared expert (and earlier-arrived experts) compute —
        one expert's compute overlaps the next one's weight load."""
        from repro.core.tasks import Task, TaskType
        cfg = self.cfg
        moe = cfg.moe
        b, s, d = x.shape
        wts, ids = self._gate(x, self.device.get(f"wg[{u.layer}]"))
        ids_np = np.asarray(ids)                    # sync point (paper)
        union = sorted(set(ids_np.reshape(-1).tolist()))
        tasks = []
        for e in union:
            t = Task(TaskType.WEIGHT_LOAD, f"exp[{u.layer}][{e}]",
                     lambda e=e: self._load_key(f"exp[{u.layer}][{e}]"))
            t.nbytes = self.weights.nbytes(f"exp[{u.layer}][{e}]")
            self._pool.submit(t)
            tasks.append((e, t))
        out = jnp.zeros_like(x)
        if moe.num_shared and shared_w:
            out = out + self._expert(x, shared_w)   # overlaps expert loads
        wts_np = wts
        for e, t in tasks:
            we = t.wait()
            ye = self._expert(x, we)                # (b, s, d) all tokens
            w_e = jnp.sum(jnp.where(ids == e, wts_np, 0.0),
                          axis=-1).reshape(b, s, 1)
            out = out + ye * w_e.astype(ye.dtype)
        return x + out

    def finalize(self, i: int, x):
        if self._phase == "decode" and x.shape[1] > 1:
            # speculative verify: per-position argmax, (b, k+1)
            tok = self._spec_head(x, self.device.get("emb"))
        else:
            tok = self._head(x, self.device.get("emb"))
        self._last_tokens = np.asarray(tok)
        return self._last_tokens

    # -- public API -----------------------------------------------------------
    def generate(self, prompt: np.ndarray, gen_len: int, pool=None):
        """prompt (b, s) int32.  Greedy-generates gen_len tokens.  Returns
        (tokens (b, gen_len), stats dict).  ``pool`` injects a transfer
        pool (e.g. ``VirtualPool`` for virtual-clock byte/cost tests);
        its trace becomes the engine's."""
        b, s = prompt.shape
        assert b == self.batch and s + gen_len <= self.max_len
        cfg = self.cfg
        self._prompt_len = s        # KV hooks derive live extents from this
        if pool is not None and getattr(pool, "trace", None) is not None:
            self.trace = pool.trace
        # warm: the scheduler persists across the per-token generate()
        # calls below, pre-submitting token t+1's first weight/KV loads
        # during token t's tail compute (performance mode only).  load_kv
        # depends only on the (global, deterministic) iteration index —
        # never on the phase flag — so warm cross-call preloads stay
        # valid; saves drain at shutdown().
        sched = PipelineScheduler(len(self.units), self.pipeline_mode,
                                  pool=pool, trace=self.trace,
                                  warm=self.pipeline_mode == "performance",
                                  depth=self.depth)
        self._pool = sched.pool
        # link/precision stamps: a dumped trace replays without the model
        self.trace.meta.update(
            arch=cfg.name, b_max=self.batch, max_len=self.max_len,
            sim_bw=self.plan.sim_bw, quant=self.quant,
            kv_mode=self.kv_mode)
        t0 = time.perf_counter()
        outs = []

        emb = self.device.get("emb")

        # ---- prefill (iteration 0 processes the whole prompt) ----
        self._phase, self._pos = "prefill", 0
        x_prompt = self._embed(jnp.asarray(prompt), emb)
        first = sched.generate(self._model_view(), lambda i: x_prompt, 1)
        outs.append(first[-1])
        t_first = time.perf_counter() - t0

        # ---- decode ----
        self._phase = "decode"
        spec = {"spec_steps": 0, "spec_proposed": 0, "spec_accepted": 0}
        if self.draft is None:
            for t in range(1, gen_len):
                self._pos = s + t - 1
                x_tok = self._embed(jnp.asarray(outs[-1][:, None]), emb)
                nxt = sched.generate(self._model_view(), lambda i: x_tok, 1)
                outs.append(nxt[-1])
        else:
            self._decode_spec(sched, prompt, gen_len, outs, emb, spec)
        sched.shutdown()
        dt = time.perf_counter() - t0
        toks = np.stack(outs, axis=1)
        stats = {
            "ttft_s": t_first,
            "total_s": dt,
            "decode_tok_s": b * (gen_len - 1) / max(1e-9, dt - t_first),
            "throughput_tok_s": b * gen_len / dt,
            "compute_busy": self.trace.busy_fraction("compute"),
            "host_peak_gb": self.host.peak_bytes / 2**30,
            "device_peak_gb": self.device.peak_bytes / 2**30,
            "pipeline": self.trace.report(),
            **spec,
        }
        return toks, stats

    def _decode_spec(self, sched, prompt, gen_len, outs, emb, spec):
        """Draft-then-verify decode loop (main thread).  Each step: the
        draft proposes ``k`` tokens while ``prime_weights`` streams the
        verify pass's first weight loads over the idle link; the target
        scores all ``k+1`` positions in one trip through the layer
        stack; the batch advances by the MINIMUM accepted run over rows
        (uniform-batch lockstep — surplus accepted tokens re-derive
        next step, so the stream is bit-identical to plain greedy).
        Rejection truncates the tiered store's rows and drops the
        now-stale warm KV preloads; full acceptance keeps them (their
        planned extent was exact)."""
        s = prompt.shape[1]
        self._iter_pos.clear()
        # seed the first decode iteration's plan BEFORE flipping the
        # mode flag: the prefill's warm tail preload may still be in
        # flight and must resolve the same extent it was priced at
        self._iter_pos[sched._iter0] = s
        self._spec_mode = True
        self.draft.prefill_batch(prompt)
        try:
            while len(outs) < gen_len:
                pos = s + len(outs) - 1
                self._pos = pos
                remaining = gen_len - len(outs)
                k = min(self._spec_k, remaining - 1, self.max_len - 1 - pos)
                gi = sched._iter0
                if k < 1:
                    self._spec_s = 1
                    self._iter_pos[gi] = pos
                    self._iter_pos[gi + 1] = pos + 1
                    x_tok = self._embed(jnp.asarray(outs[-1][:, None]), emb)
                    nxt = sched.generate(self._model_view(),
                                         lambda i: x_tok, 1)
                    outs.append(nxt[-1])
                    continue
                self._spec_s = k + 1
                self._iter_pos[gi] = pos
                self._iter_pos[gi + 1] = pos + k + 1   # full-accept plan
                t0 = time.perf_counter()
                primed = sched.prime_weights(self._model_view())
                props = np.asarray(self.draft.propose(
                    outs[-1], np.full(self.batch, pos, np.int32), k),
                    np.int32)                          # (b, k)
                draft_s = time.perf_counter() - t0
                seq = np.concatenate(
                    [np.asarray(outs[-1], np.int32)[:, None], props], axis=1)
                x_tok = self._embed(jnp.asarray(seq), emb)
                nxt = sched.generate(self._model_view(), lambda i: x_tok, 1)
                tgt = np.asarray(nxt[-1])              # (b, k+1)
                a_min = min(accept_length(props[r], tgt[r])
                            for r in range(self.batch))
                emitted = min(a_min + 1, remaining)
                for t in range(emitted):
                    outs.append(tgt[:, t])
                if emitted < k + 1:
                    # rejected (or generation-capped) rows: the saves in
                    # flight would re-write them after the truncate, and
                    # the warm KV preloads priced the full-accept extent
                    # — drain, invalidate, drop (weight preloads stay)
                    sched.drain_saves()
                    sched.drop_kv_preloads()
                    if self.kvstore is not None:
                        for r in range(self.batch):
                            self.kvstore.truncate(r, pos + emitted)
                spec["spec_steps"] += 1
                spec["spec_proposed"] += k * self.batch
                spec["spec_accepted"] += int(a_min) * self.batch
                self.trace.meta.setdefault("spec_steps", []).append(dict(
                    k=int(k), primed=int(primed), draft_s=float(draft_s),
                    accepts=[int(a_min)] * self.batch))
        finally:
            self._spec_mode = False

    def _model_view(self):
        return self
