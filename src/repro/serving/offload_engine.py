"""Offloaded continuous-batching serving engine: the PIPO pipeline under a
serving workload.

Where ``ServingEngine`` keeps every parameter resident, this engine keeps
only the embedding/final-norm (and MoE routers) on device; each
transformer layer's weights live as ONE merged buffer (+manifest) on the
host or disk tier (``TieredWeightStore``, shared with
``core.engine.PipelinedLM``) and stream through the 3-thread
``ThreadPool`` + ``PipelineScheduler`` per decode step.  The per-layer KV
cache lives in host memory and moves as ``KV_LOAD``/``KV_SAVE`` pipeline
tasks, so the repo can serve models whose weights + KV exceed device
memory — the paper's headline scenario.

Warm pipeline (default in performance mode): the scheduler persists
across ``generate()`` calls (``PipelineScheduler(warm=True)``), so while
step *t*'s tail layers compute, step *t+1*'s first weight/KV loads are
already in flight — steady-state decode pays no cold-start transfer
bubble per token (ROADMAP item; FlexInfer-style cross-step preloading).
Disable with ``warm=False`` to reproduce the cold per-step baseline.

Preload depth (``depth``): how many layers' transfers the pipeline keeps
in flight beyond the computing one (``depth + 1`` resident).  The
default ``depth=None`` sizes it from the memory budget
(``autoconfig.serving_preload_depth``: device headroom after the KV
cache, host headroom after ``spill_cap`` retained spills, quant mode);
pass an int (or ``launch.serve --preload-depth``) to override.  On
weight-dominated links depth >= 2 keeps multiple transfer workers busy
and cuts ms/step below the paper's two-resident-layer invariant — see
docs/TUNING.md.

INT4 weight streaming (``quant="int4"``): eligible 2-D projections are
stored packed (uint8 nibbles + groupwise scales), so only a quarter-ish
of the FP32 bytes cross the offload link; the dequant runs on a
transfer-pool thread as one jitted op overlapping the main thread's
compute (paper §3.4).  Decoded tokens are bit-identical to a resident
engine holding the same quantize->dequantize roundtripped weights
(``quant_roundtrip_params`` builds that reference).

MoE layers load only the *union of routed experts* per step (paper
Appendix C.4, ported from ``core.engine.PipelinedLM``): the tiny router
stays device-resident, each expert is its own tiered buffer, and after
the gate runs (the paper's sync point) only the experts the batch routed
to are submitted as WEIGHT_LOAD tasks — the shared expert computes while
they stream.  Union bytes << whole-bank bytes at decode batch sizes.

Tiered KV (``core.kvstore.TieredKVStore``): the per-unit decode cache is
owned by the store, not the engine.  KV_LOAD payloads are sliced to the
LIVE extent — occupied slots × written positions, zero-padded back to
the slab shape device-side so the jitted decode fns never retrace — and
``kv_mode="int4"`` (``--kv-mode int4``) stores/streams cache rows packed
with the dequant fused into the decode jit.  Trace events carry the live
extent and the exact link bytes; ``AdaptiveDepth`` prices its window
from those measured bytes plus a bytes/busy bandwidth EWMA fed back from
the Trace each step (see ``_observe_trace``).

Numerics are *identical* to the resident engine: both run the same
``models.layers`` / ``models.moe`` functions on params from the same
``model.init`` seed, so decoded tokens match exactly (asserted in
tests/test_serving_offload.py).

Pipeline modes (pick with ``pipeline=``):
  * "performance" — preload layer j+1's weights during layer j's compute;
    highest throughput, two layers resident (default; ``warm`` adds the
    cross-step preload on top).
  * "memory"      — single layer resident, KV-save synchronized; lowest
    device footprint.
  * "sequential"  — FlexGen-like full serialization; baseline for the
    utilization benchmark (Fig. 9 analogue in benchmarks/run.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MOE, ModelConfig, LayerSpec
from repro.core.draft import accepted_tokens
from repro.core.kvstore import TieredKVStore, kv_roundtrip_traceable
from repro.core.offload import DeviceStore, DiskStore
from repro.core.pipeline import PipelineScheduler, StagedScheduler, ThreadPool
from repro.core.tasks import Task, TaskType, Trace, _merged_busy
from repro.core.transfer import TieredWeightStore, int4_roundtrip
from repro.launch.mesh import stage_devices
from repro.models import Dist, build_model
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.common import silu
from repro.serving.base import Request, SlotEngineBase
from repro.serving.spec import (AdaptiveDepth, EngineSpec, Pressure,
                                ResolvedPlan, StaticDepth,
                                UnsupportedModelError, draft_policy_for,
                                offload_capability, preload_policy_for,
                                quant_policy_for, sched_policy_for,
                                spec_decode_capability,
                                warn_deprecated_once)

__all__ = ["Request", "OffloadedServingEngine", "quant_roundtrip_params"]

# the pre-spec constructor signature's defaults: the deprecation shim
# overlays provided kwargs on these so a legacy call resolves to the
# exact plan the old constructor would have acted on (kv_mode post-dates
# the shim but rides it for test ergonomics: None = auto -> fp32)
_LEGACY_DEFAULTS = dict(
    b_max=4, max_len=256, seed=0, placement="host", pipeline="performance",
    quant=None, kv_mode=None, fused_int4=True, warm=None, depth=None,
    disk_root="", block_bytes=None, n_io_threads=3,
    cold_reads=False, sim_bw=None, spill_cap=32)


@dataclass
class _Unit:
    """One schedulable layer: period ``p`` of pattern position ``q``
    ('pat'), or remainder layer q ('rem').  MoE layers additionally carry
    a device-resident router and one tiered store key per expert."""
    group: str          # "pat" | "rem"
    p: int              # period index (0 for rem)
    q: int              # pattern / remainder position
    spec: LayerSpec
    key: str            # TieredWeightStore key (mixer + norms + shared)
    moe: bool = False
    router: Any = None                     # device (d, E) gate weights
    expert_keys: List[str] = field(default_factory=list)


def quant_roundtrip_params(cfg: ModelConfig, params):
    """INT4 quantize->dequantize exactly the leaves the offloaded engine
    streams as INT4 — per-layer 2-D projections and per-expert MoE slices
    — leaving embeddings/final-norm/routers (device-resident, never
    streamed) untouched.  Feeding the result to a resident
    ``ServingEngine`` builds the reference the INT4 offloaded engine must
    match token-for-token (tests/test_serving_offload.py)."""
    def do_tab(tab, spec, stacked):
        out = {}
        for name, leaf in tab.items():
            arr = np.asarray(leaf)
            if spec.ffn == MOE and name == "wg":
                out[name] = leaf                      # router: resident
            elif spec.ffn == MOE and name in ("w_gate", "w_up", "w_down"):
                if stacked:                           # (periods, E, ..)
                    new = np.stack([
                        np.stack([int4_roundtrip(arr[p, e])
                                  for e in range(arr.shape[1])])
                        for p in range(arr.shape[0])])
                else:
                    new = np.stack([int4_roundtrip(arr[e])
                                    for e in range(arr.shape[0])])
                out[name] = jnp.asarray(new)
            elif stacked:
                out[name] = jnp.asarray(np.stack(
                    [int4_roundtrip(arr[p]) for p in range(arr.shape[0])]))
            else:
                out[name] = jnp.asarray(int4_roundtrip(arr))
        return out

    return {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "pat": tuple(do_tab(params["pat"][q], cfg.pattern[q], True)
                     for q in range(len(cfg.pattern))),
        "rem": tuple(do_tab(params["rem"][q], cfg.remainder[q], False)
                     for q in range(len(cfg.remainder))),
    }


def unit_programs(cfg: ModelConfig, dist, spec: LayerSpec,
                  kinds: Dict[str, str], *, max_len: int, kv_int4: bool):
    """The (decode, prefill, chunk) programs the engine jits for one kind
    of schedulable unit: plain functions of the unit's streamed weights,
    the activation and its cache, so they can also be lowered from shapes
    alone (tests/test_tpu_compile.py compiles them for a TPU)."""

    def decode_fn(w, x, cache, pos, angles):
        # INT4 KV already dequantized on the transfer thread
        # (kvstore.load, live rows only) — the cache arrives at compute
        # precision in every kv_mode.  kv_roundtrip hands the speculative
        # verify pass the tier's lossy write-back, so its later queries
        # attend the pass's earlier rows at the precision sequential
        # decode would reload them at
        ctx = L.Ctx(cfg=cfg, dist=dist, mode="decode", angles=angles,
                    pos=pos, batch_size=x.shape[0],
                    kv_roundtrip=kv_roundtrip_traceable if kv_int4 else None)
        x, new_cache, _ = L.apply_layer(w, x, ctx, cache, spec)
        # gather only the newly written sequence rows so KV_SAVE ships
        # (b, s, ...) instead of the whole cache — s new rows per slot at
        # pos..pos+s-1 (s=1 plain decode, k+1 for a speculative verify
        # pass)
        s = x.shape[1]
        rows = {}
        for name, kind in kinds.items():
            leaf = new_cache[name]
            if kind == "kv":
                locs = pos.reshape(-1, 1) + jnp.arange(s)[None, :]
                idx = locs.reshape((-1, s) + (1,) * (leaf.ndim - 2))
                rows[name] = jnp.take_along_axis(
                    leaf, idx.astype(jnp.int32), axis=1)
            else:
                rows[name] = leaf
        return x, rows

    def prefill_fn(w, x, angles):
        ctx = L.Ctx(cfg=cfg, dist=dist, mode="prefill", angles=angles,
                    cache_len=max_len, batch_size=x.shape[0])
        x, new_cache, _ = L.apply_layer(w, x, ctx, None, spec)
        return x, new_cache

    def chunk_fn(w, x, pk, pv, angles, q_off):
        # one prefill CHUNK: rows q_off..q_off+c-1 attend the engine-held
        # fp32 prefix (earlier chunks' post-rope k/v) plus themselves —
        # bit-identical to the same rows of a monolithic prefill
        # (attention.chunk_prefill_attention).  Retraces per (prefix_len,
        # chunk_len) shape pair, which the fixed chunk cap bounds.
        ctx = L.Ctx(cfg=cfg, dist=dist, mode="prefill", angles=angles,
                    batch_size=x.shape[0])
        return L.apply_layer_chunk(w, x, ctx, pk, pv, q_off)

    return decode_fn, prefill_fn, chunk_fn


def head_programs(cfg: ModelConfig, dist):
    """The (embed, head, speculative head) programs around the streamed
    stack; they run on the device-resident embedding and final norm."""

    def embed_fn(emb_p, tok, mode):
        ctx = L.Ctx(cfg=cfg, dist=dist, mode=mode, batch_size=tok.shape[0])
        return L.embed_tokens(emb_p, tok, ctx)

    def head_fn(emb_p, fn_p, x):
        ctx = L.Ctx(cfg=cfg, dist=dist, mode="decode",
                    batch_size=x.shape[0])
        x = L.rms_norm(x, fn_p["scale"], cfg.norm_eps)
        return L.lm_head_argmax(emb_p, x[:, -1:], ctx)

    def spec_head_fn(emb_p, fn_p, x):
        # per-POSITION greedy argmax for the verify pass: reshape
        # (b, s, d) -> (b*s, 1, d) so every position goes through the
        # exact lm_head_argmax row arithmetic the plain head uses —
        # per-row numerics identical, hence token parity
        b, s, d = x.shape
        ctx = L.Ctx(cfg=cfg, dist=dist, mode="decode", batch_size=b * s)
        x = L.rms_norm(x, fn_p["scale"], cfg.norm_eps)
        return L.lm_head_argmax(
            emb_p, x.reshape(b * s, 1, d), ctx).reshape(b, s)

    return embed_fn, head_fn, spec_head_fn


class _StagedWeightStore:
    """Key-routing facade over per-stage ``TieredWeightStore``s: each
    stage owns its own store (and therefore its own ``SimLink``), so N
    stages stream over N independent links — the aggregate-bandwidth
    mechanism of pipeline-parallel offload.  ``route(key) -> stage``
    parses the unit key; the host/device/disk tier OBJECTS are shared
    (keys are globally unique), only the link and IO workers split."""

    def __init__(self, stores, route):
        self.stores = list(stores)
        self._route = route

    def put(self, key: str, tensors):
        return self.stores[self._route(key)].put(key, tensors)

    def load(self, key: str):
        return self.stores[self._route(key)].load(key)

    def nbytes(self, key: str) -> int:
        return self.stores[self._route(key)].nbytes(key)


class _StagedKVStore:
    """Global-unit facade over per-stage ``TieredKVStore``s: unit-indexed
    calls route to the owning stage's store (stage-local index), slot
    ops fan out to every stage, and spill namespaces get a per-stage
    suffix so stage-local unit indices can't collide in the shared host
    tier (``{ns}/s{stage}/{unit}/{name}`` still matches the engine's
    prefix-based spill cleanup)."""

    _UNIT_METHODS = ("load", "load_nbytes", "slab_nbytes", "save_nbytes",
                     "prefill_save_nbytes", "dequant_nbytes",
                     "save_prefill", "save_prefill_batch", "save_decode",
                     "has_kv", "leaf_meta")

    def __init__(self, stores, bounds):
        self.stores = list(stores)
        self.bounds = [tuple(b) for b in bounds]
        self.b_max = self.stores[0].b_max
        self.max_len = self.stores[0].max_len
        self.kv_mode = self.stores[0].kv_mode
        for name in self._UNIT_METHODS:
            setattr(self, name, self._unit_call(name))

    def _unit_call(self, name):
        def call(j, *args, **kwargs):
            for (lo, hi), st in zip(self.bounds, self.stores):
                if lo <= j < hi:
                    return getattr(st, name)(j - lo, *args, **kwargs)
            raise IndexError(f"unit {j} outside staged bounds {self.bounds}")
        return call

    def __len__(self):
        return sum(len(st) for st in self.stores)

    @property
    def dequant_bytes_total(self) -> int:
        return sum(st.dequant_bytes_total for st in self.stores)

    def max_live_load_nbytes(self, live_b: int, live_len: int) -> int:
        return max(st.max_live_load_nbytes(live_b, live_len)
                   for st in self.stores)

    def host_nbytes(self) -> int:
        return sum(st.host_nbytes() for st in self.stores)

    def truncate(self, slot: int, new_len: int) -> None:
        for st in self.stores:
            st.truncate(slot, new_len)

    def spill(self, host, ns: str, slot: int) -> None:
        for s, st in enumerate(self.stores):
            st.spill(host, f"{ns}/s{s}", slot)

    def restore(self, host, ns: str, slot: int) -> None:
        for s, st in enumerate(self.stores):
            st.restore(host, f"{ns}/s{s}", slot)


class _MeshStagedScheduler(StagedScheduler):
    """``StagedScheduler`` whose activation handoff is a device-to-device
    ``device_put`` onto the receiving stage's device (round-robin over
    the local mesh; an on-device no-op when every stage shares one
    device, so single-GPU boxes still run the staged engine)."""

    def __init__(self, *args, devices=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.devices = list(devices or [])

    def handoff(self, stage: int, it: int, x):
        if self.devices and x is not None:
            return jax.device_put(x, self.devices[stage % len(self.devices)])
        return x


class OffloadedServingEngine(SlotEngineBase):
    """See module docstring.  Main-thread object: all public methods run
    on the caller's thread; weight/KV transfers run on the internal
    3-thread pool per Algorithm 1."""

    def __init__(self, plan: "ResolvedPlan | ModelConfig", **legacy_kwargs):
        """Canonical construction takes ONE argument: a ``ResolvedPlan``
        (``EngineSpec.resolve()``; usually via
        ``serving.spec.create_engine``).  Passing a ``ModelConfig`` plus
        the pre-spec keyword arguments still works through a deprecation
        shim — the kwargs are converted to an ``EngineSpec`` and
        resolved, so both paths act on an identical plan (asserted in
        tests/test_spec.py)."""
        if isinstance(plan, ModelConfig):
            warn_deprecated_once(
                "OffloadedServingEngine.legacy_kwargs",
                "OffloadedServingEngine(cfg, **kwargs) is deprecated; "
                "build an EngineSpec and pass its resolved plan "
                "(serving.spec.create_engine) instead")
            unknown = set(legacy_kwargs) - set(_LEGACY_DEFAULTS)
            if unknown:
                raise TypeError(f"unknown kwargs {sorted(unknown)}")
            spec = EngineSpec(arch=plan.name, cfg=plan, offload=True,
                              **{**_LEGACY_DEFAULTS, **legacy_kwargs})
            plan = spec.resolve()
        elif legacy_kwargs:
            raise TypeError("plan construction takes no kwargs; set the "
                            "fields on the EngineSpec instead")
        cfg = plan.model_config()
        cap = offload_capability(cfg)
        if cap is not None or plan.engine != "offloaded":
            raise UnsupportedModelError(
                cap or "resident_plan",
                f"offloaded serving supports token-frontend rope decoder "
                f"stacks only (failing capability: {cap or plan.engine}; "
                f"arch {plan.arch}); create_engine(plan) falls back to "
                f"the resident ServingEngine")
        self.plan = plan
        self.preload_policy = preload_policy_for(plan, cfg)
        self.quant_policy = quant_policy_for(plan.quant, plan.kv_mode)
        self.n_stages = max(1, int(getattr(plan, "stages", 1) or 1))
        self.stage_bounds = self._make_stage_bounds(cfg, plan)
        self.trace = Trace()
        if self.n_stages > 1:
            # one transfer pool per stage, each sized to that stage's
            # window (per-stage warm windows; the StagePlan depths came
            # from the resolver's per-stage budget split)
            sd = ([p.depth for p in plan.stage_plan]
                  if len(plan.stage_plan) == self.n_stages
                  else [max(1, plan.depth)] * self.n_stages)
            self._stage_depths = [
                PipelineScheduler.clamp_depth(plan.pipeline, hi - lo, d)
                for (lo, hi), d in zip(self.stage_bounds, sd)]
            self._stage_pools = [
                ThreadPool(PipelineScheduler.pool_size(d), self.trace)
                for d in self._stage_depths]
            depth = max(self._stage_depths)
            pool = self._stage_pools[0]
        else:
            # window ceiling: adaptive policies may deepen later, so the
            # pool (and its KV headroom) is sized once for the policy's
            # max depth
            max_depth = PipelineScheduler.clamp_depth(
                plan.pipeline, self._n_units(cfg),
                self.preload_policy.max_depth())
            depth = PipelineScheduler.clamp_depth(
                plan.pipeline, self._n_units(cfg), max(1, plan.depth))
            self._stage_depths = [depth]
            self._stage_pools = []
            # pool sized to the window (depth weight loads + KV load +
            # KV save)
            pool = ThreadPool(
                PipelineScheduler.pool_size(max(depth, max_depth)),
                self.trace)
        super().__init__(cfg, b_max=plan.b_max, max_len=plan.max_len,
                         kv_pool=pool, spill_cap=plan.spill_cap)
        self.dist = Dist.local()
        self.model = build_model(cfg)
        self.pipeline_mode = plan.pipeline
        self.quant = plan.quant
        self.warm = plan.warm
        self.device = DeviceStore()
        # the disk tier exists (and touches the filesystem) only when the
        # plan places weights there
        self.disk = (DiskStore(plan.disk_root) if plan.placement == "disk"
                     else None)
        # the jax device each stage's weights, KV and activations live on
        # (one per chip, round-robin); None keeps a single-stage engine on
        # the default device
        self.stage_devices = (stage_devices(self.n_stages)
                              if self.n_stages > 1 else [None])
        if self.n_stages > 1:
            # one tiered store per stage = one independent SimLink per
            # stage: each stage streams only its slice and the aggregate
            # host->device bandwidth scales with stage count
            self.weights = _StagedWeightStore(
                [TieredWeightStore(
                    placement=plan.placement, host=self.host,
                    device=self.device, disk=self.disk,
                    quant=self.quant_policy.weight_mode,
                    fused_int4=plan.fused_int4,
                    block_bytes=plan.block_bytes,
                    n_io_threads=plan.n_io_threads,
                    cold_reads=plan.cold_reads, sim_bw=plan.sim_bw,
                    target=dev)
                 for dev in self.stage_devices],
                lambda key: self._stage_of_unit(self._unit_of_key(key)))
        else:
            self.weights = TieredWeightStore(
                placement=plan.placement, host=self.host, device=self.device,
                disk=self.disk, quant=self.quant_policy.weight_mode,
                fused_int4=plan.fused_int4, block_bytes=plan.block_bytes,
                n_io_threads=plan.n_io_threads, cold_reads=plan.cold_reads,
                sim_bw=plan.sim_bw)
        params = self.model.init(jax.random.PRNGKey(plan.seed), jnp.float32)
        self._phase = "prefill"           # until the first _decode_active
        # chunked-prefill admission (SchedPolicy seam): at most ONE
        # prefill is in flight, advanced one chunk per engine step so it
        # shares the step's streamed weight window with the decode batch
        self.sched_policy = sched_policy_for(plan)
        self._chunk = None                # dict(slot, req, done, prefix)
        self._chunk_step = None           # (c0, c, final) during a step
        self._chunk_tok = 0               # first token, set at final chunk
        # bytes staged device-side into compact MoE combine stacks — the
        # |union|-proportionality proof (tests assert it equals loaded
        # experts x per-expert fp32 bytes, strictly below the full bank)
        self.stats["moe_stack_bytes"] = 0
        self.stats["preload_depth"] = depth
        self.stats["depth_resizes"] = 0
        self.units: List[_Unit] = []
        self._split_params(params)
        self._kv_init()
        assert len(self.units) == self._n_units(cfg)
        # live decode view, (scheduler iteration base, live_batch,
        # live_len): ONE tuple so transfer-thread reads are atomic under
        # the GIL.  Refreshed at the top of every _decode_active; a warm
        # tail preload for iteration base+1 prices itself at live_len+1
        # (the only way the extent can grow between steps without an
        # admission, and admissions drop KV preloads anyway).
        self._decode_view = (0, self.b_max, self.max_len)
        self._extent_memo: Dict[int, tuple] = {}
        # per-step Trace cursor + policy feedback (AdaptiveDepth only)
        self._trace_mark = 0
        if isinstance(self.preload_policy, AdaptiveDepth):
            self.preload_policy.set_link_profile(
                sum(self.weights.nbytes(u.key) for u in self.units)
                // max(1, len(self.units)))
        if self.n_stages > 1:
            self.sched = _MeshStagedScheduler(
                self.stage_bounds, plan.pipeline, pools=self._stage_pools,
                trace=self.trace, warm=self.warm,
                depths=self._stage_depths, devices=self.stage_devices)
        else:
            self.sched = PipelineScheduler(len(self.units), plan.pipeline,
                                           pool=pool, trace=self.trace,
                                           warm=self.warm, depth=depth)
        # stamp the link/precision knobs next to the scheduler's context
        # so a dumped trace is self-describing for core.replay
        self.trace.meta.update(
            arch=plan.arch, b_max=plan.b_max, max_len=plan.max_len,
            sim_bw=plan.sim_bw, quant=plan.quant,
            kv_mode=plan.kv_mode or "fp32")
        self._jit_units()
        # speculative decoding: a device-resident draft proposes spec_k
        # tokens per step; the streamed target verifies them in one
        # ragged k+1-position pass (core.draft module docstring)
        self.draft = None
        self._spec_k = 0
        self._spec_s = 1                  # rows the current step writes
        self._spec_emitted = None         # per-slot tokens of the last step
        for key in ("spec_steps", "spec_proposed", "spec_accepted"):
            self.stats[key] = 0
        dp = draft_policy_for(plan)
        if dp is not None:
            self.attach_draft(
                dp.build(b_max=plan.b_max, max_len=plan.max_len), dp.k)

    @staticmethod
    def _n_units(cfg: ModelConfig) -> int:
        """Schedulable unit count (needed before the units are built, to
        size the transfer pool from the clamped preload depth)."""
        return cfg.num_periods * len(cfg.pattern) + len(cfg.remainder)

    # ---- pipeline-parallel staging ------------------------------------------
    def _make_stage_bounds(self, cfg: ModelConfig, plan) -> List[tuple]:
        """Contiguous per-stage unit ranges: the resolver's ``stage_plan``
        when it tiles this config, else a balanced split (a hand-built
        plan may carry ``stages`` without slices)."""
        nu = self._n_units(cfg)
        if self.n_stages <= 1:
            return [(0, nu)]
        sp = plan.stage_plan
        if (len(sp) == self.n_stages and sp[0].layer_lo == 0
                and sp[-1].layer_hi == nu):
            return [(p.layer_lo, p.layer_hi) for p in sp]
        return [(round(s * nu / self.n_stages),
                 round((s + 1) * nu / self.n_stages))
                for s in range(self.n_stages)]

    def _unit_of_key(self, key: str) -> int:
        """Global unit index of a tiered-store key (``u[p][q]``,
        ``rem[q]``, or an expert sub-key of either)."""
        import re
        base = key.split("/", 1)[0]
        nums = [int(x) for x in re.findall(r"\[(\d+)\]", base)]
        if base.startswith("u["):
            return nums[0] * len(self.cfg.pattern) + nums[1]
        return self.cfg.num_periods * len(self.cfg.pattern) + nums[0]

    def _stage_of_unit(self, j: int) -> int:
        for s, (lo, hi) in enumerate(self.stage_bounds):
            if lo <= j < hi:
                return s
        raise IndexError(f"unit {j} outside stage bounds "
                         f"{self.stage_bounds}")

    # ---- weight tiering -----------------------------------------------------
    def _maybe_quant(self, tensors):
        return self.quant_policy.prepare_unit(tensors)

    def _split_params(self, params):
        """Embeddings/final norm stay on device (small, needed every step);
        each layer's params merge into one tiered buffer.  MoE layers
        split further: the router stays on device (tiny; needed before
        any expert prefetch), each expert becomes its own tiered buffer so
        decode can load just the routed union (paper Appendix C.4).
        Main thread, build time only.  The resident tensors live on the
        first stage's device, where the embedding and the head run."""
        self.resident = {
            "embed": jax.device_put(params["embed"], self.stage_devices[0]),
            "final_norm": jax.device_put(params["final_norm"],
                                         self.stage_devices[0]),
        }
        cfg = self.cfg
        for p in range(cfg.num_periods):
            for q, spec in enumerate(cfg.pattern):
                key = f"u[{p}][{q}]"
                tensors = {name: np.asarray(leaf[p])
                           for name, leaf in params["pat"][q].items()}
                self.units.append(self._make_unit("pat", p, q, spec, key,
                                                  tensors))
        for q, spec in enumerate(cfg.remainder):
            key = f"rem[{q}]"
            tensors = {name: np.asarray(leaf)
                       for name, leaf in params["rem"][q].items()}
            self.units.append(self._make_unit("rem", 0, q, spec, key,
                                              tensors))

    def _make_unit(self, group, p, q, spec, key, tensors) -> _Unit:
        u = _Unit(group, p, q, spec, key)
        if spec.ffn == MOE:
            u.moe = True
            m = self.cfg.moe
            dev = self.stage_devices[
                self._stage_of_unit(len(self.units)) if self.n_stages > 1
                else 0]
            u.router = jax.device_put(tensors.pop("wg"), dev)
            wga = tensors.pop("w_gate")
            wup = tensors.pop("w_up")
            wdn = tensors.pop("w_down")
            for e in range(m.num_experts):
                ek = f"{key}/exp[{e}]"
                self.weights.put(ek, self._maybe_quant(
                    {"w_gate": wga[e], "w_up": wup[e], "w_down": wdn[e]}))
                u.expert_keys.append(ek)
        self.weights.put(key, self._maybe_quant(tensors))
        return u

    # ---- tiered KV ----------------------------------------------------------
    def _kv_init(self):
        """Hand the per-unit decode cache to a ``TieredKVStore`` (the
        b_max cache the resident engine keeps on device, owned as a host
        tier here): live-row loads, INT4 row packing under
        ``kv_mode='int4'``, and slot spill/restore all route through it.
        KV shares the weight store's ``SimLink`` so both pay the same
        simulated interconnect."""
        struct, kinds = T.cache_struct(self.cfg, self.b_max, self.max_len)
        shapes, kk = [], []
        for u in self.units:
            sds = struct[u.group][u.q]
            shapes.append({n: ((s.shape[1:] if u.group == "pat"
                                else s.shape), s.dtype)
                           for n, s in sds.items()})
            kk.append(dict(kinds[u.group][u.q]))
        self.kv_kinds: List[Dict[str, str]] = kk
        if self.n_stages > 1:
            # one KV store per stage, sharing that stage's weight-store
            # SimLink so both directions pay the same per-stage link
            self.kvstore = _StagedKVStore(
                [TieredKVStore(
                    shapes[lo:hi], kk[lo:hi], b_max=self.b_max,
                    max_len=self.max_len,
                    kv_mode=self.quant_policy.kv_mode,
                    link=self.weights.stores[s].link,
                    target=self.stage_devices[s])
                 for s, (lo, hi) in enumerate(self.stage_bounds)],
                self.stage_bounds)
        else:
            self.kvstore = TieredKVStore(
                shapes, kk, b_max=self.b_max, max_len=self.max_len,
                kv_mode=self.quant_policy.kv_mode, link=self.weights.link)

    # ---- jitted per-unit compute --------------------------------------------
    def _jit_units(self):
        cfg, dist = self.cfg, self.dist
        self._decode_fns = {}
        self._prefill_fns = {}
        self._chunk_fns = {}
        self._moe_fns = {}
        for j, u in enumerate(self.units):
            sig = (u.group, u.q)
            if sig in self._decode_fns:
                continue
            # MoE units run the mixer through apply_layer with a DENSE ffn
            # spec: the base params carry no dense "w_gate", so the ffn
            # half no-ops and the MoE ffn runs in _compute_moe (expert
            # loads overlap compute there).
            spec = (LayerSpec(u.spec.mixer) if u.moe else u.spec)
            decode_fn, prefill_fn, chunk_fn = unit_programs(
                cfg, dist, spec, self.kv_kinds[j], max_len=self.max_len,
                kv_int4=self.quant_policy.kv_mode == "int4")
            self._decode_fns[sig] = jax.jit(decode_fn)
            self._prefill_fns[sig] = jax.jit(prefill_fn)
            self._chunk_fns[sig] = jax.jit(chunk_fn)
            if u.moe:
                self._moe_fns[sig] = self._jit_moe_fns()
        embed_fn, head_fn, spec_head_fn = head_programs(cfg, dist)
        self._embed = jax.jit(embed_fn, static_argnums=(2,))
        self._head = jax.jit(head_fn)
        self._spec_head = jax.jit(spec_head_fn)

    def _jit_moe_fns(self):
        """Four jitted stages replicating ``layers.apply_moe_ffn`` exactly
        (same ops, same order -> bit-identical to the resident engine)
        while exposing the gate output early enough to prefetch only the
        routed experts.  The combine is the compact ``moe_ffn_union``:
        its expert stacks are (|union|, ...)-shaped with remapped ids, so
        nothing bank-sized is ever materialized — it retraces per union
        size, which is bounded by ``num_experts`` distinct shapes."""
        cfg = self.cfg
        m = cfg.moe

        def pre_fn(w, x):
            return L.rms_norm(x, w["norm_ffn"], cfg.norm_eps)

        def gate_fn(xn, wg):
            b, s, d = xn.shape
            logits = (xn.reshape(b * s, d) @ wg).astype(jnp.float32)
            return moe_mod.router_topk(logits, m.top_k)

        def shared_fn(w, xn):
            if not m.num_shared:
                return jnp.zeros_like(xn)
            h = silu(xn @ w["ws_gate"]) * (xn @ w["ws_up"])
            return h @ w["ws_down"]

        def combine_fn(x, xn, gate_w, ids_u, wga, wup, wdn, shared_term):
            b, s, d = x.shape
            # full-bank capacity formula (moe_ffn's) — slot assignment and
            # overflow drops must match the resident path bit-for-bit
            capacity = int(m.capacity_factor * b * s * m.top_k
                           / m.num_experts) + 1
            out = moe_mod.moe_ffn_union(
                xn.reshape(b * s, d), gate_w, ids_u,
                dict(w_gate=wga, w_up=wup, w_down=wdn), capacity)
            x = x + out.reshape(b, s, d)
            if m.num_shared:
                x = x + shared_term
            return x

        return (jax.jit(pre_fn), jax.jit(gate_fn), jax.jit(shared_fn),
                jax.jit(combine_fn))

    # ---- PipelineScheduler callbacks ----------------------------------------
    def is_mha(self, j: int) -> bool:
        """'Has streamed KV state' in scheduler terms — true for every
        cached mixer (ATTN/MLA/SSM), so KV_LOAD/KV_SAVE are scheduled.
        Called on the main (submitter) thread."""
        return bool(self.kv_kinds[j])

    def load_weights(self, j: int):
        """WEIGHT_LOAD body: tier -> device for unit j's base buffer
        (mixer + norms + shared expert).  Transfer-pool thread; blocking
        on the simulated link."""
        return self.weights.load(self.units[j].key)

    def weight_nbytes(self, j: int) -> int:
        """Bytes unit j's base WEIGHT_LOAD moves (INT4: packed bytes) —
        recorded on trace events for transfer-volume assertions."""
        return self.weights.nbytes(self.units[j].key)

    def release_weights(self, j: int, handle):
        del handle  # device arrays freed by GC; tier stores unaffected

    def _live_extent(self, i: int):
        """(live_batch, live_len) iteration ``i``'s KV_LOAD ships.
        Computed from the atomic ``_decode_view`` snapshot — a warm tail
        preload (``i`` one past the current step's base) adds one
        position, the row the current step's save is writing, which the
        save-before-load check guarantees has landed before the preload
        executes — then MEMOIZED per iteration (first query wins, via
        setdefault): ``kv_nbytes`` prices the payload at submit time on
        the main thread and ``load_kv`` ships on a pool thread possibly
        after the view refreshed, and the two must agree or the trace
        would overstate what crossed (and bias the bandwidth EWMA).
        The memo only ever stores a superset-or-exact extent, so a
        later, smaller view never makes a priced load under-ship.  Any
        thread (dict ops atomic under the GIL)."""
        ext = self._extent_memo.get(i)
        if ext is None:
            base, lb, ll = self._decode_view
            ext = self._extent_memo.setdefault(
                i, (lb, min(ll + max(0, i - base), self.max_len)))
        return ext

    # ``kv_nbytes``/``kv_extent``/``kv_save_nbytes``/``load_kv`` come
    # from ``PhasedKVExtents`` (via SlotEngineBase — the phase-aware
    # logic shared with ``PipelinedLM``); the host hooks below feed it.
    # Loads return None outside decode (prefill builds, chunks extend,
    # caches in-pass) — warm cross-step preloads issued at the tail of a
    # monolithic prefill or a chunk-only step are therefore poisoned and
    # dropped before the next decode consumes them.
    def _kv_phase(self, i: int) -> str:
        return self._phase                # "prefill" | "decode" | "chunk"

    def _kv_live(self, i: int):
        return self._live_extent(i)

    def _kv_streams(self, j: int) -> bool:
        return bool(self.kv_kinds[j])

    def _kv_prefill_save_nbytes(self, j: int) -> int:
        return self.kvstore.prefill_save_nbytes(j)

    def _kv_chunk_save_nbytes(self, j: int) -> int:
        """The in-flight prefill chunk's KV append: one slot's ``c``
        fresh rows ride this step's KV_SAVE alongside the decode rows."""
        if self._chunk_step is None:
            return 0
        _, c, _ = self._chunk_step
        return self.kvstore.save_nbytes(j, 1, rows=c)

    def save_kv(self, i: int, j: int, new_kv):
        """KV_SAVE body: scatter freshly-written cache rows back into the
        tiered store (which quantizes them — once per row — under
        kv_mode='int4').  Transfer-pool thread; the scheduler guarantees
        the save lands before iteration i+1's KV_LOAD of the same
        unit."""
        phase, payload, meta = new_kv
        if phase == "prefill":
            slot = meta
            self.kvstore.save_prefill(
                j, slot, {n: np.asarray(l[0]) for n, l in payload.items()})
        elif phase == "mixed":
            # a step carrying a prefill chunk: the decode batch's rows
            # (when a decode rode along) plus the chunk's per-position
            # append — the same quantize-once ``save_decode`` row path,
            # so the stored bytes match a monolithic prefill's exactly
            if payload is not None:
                rows_d, (active, pos, live_b) = payload
                rows = {n: np.asarray(l[:live_b])
                        for n, l in rows_d.items()}
                self.kvstore.save_decode(j, rows, active, pos)
            k_ck, v_ck, slot, c0 = meta
            rows = {}
            for name, arr in (("k", k_ck), ("v", v_ck)):
                a = np.asarray(arr)                     # (1, c, *feat)
                buf = np.zeros((slot + 1,) + a.shape[1:], a.dtype)
                buf[slot] = a[0]
                rows[name] = buf
            self.kvstore.save_decode(
                j, rows, [slot], np.full(slot + 1, c0, np.int32))
        else:
            active, pos, live_b = meta
            rows = {n: np.asarray(l[:live_b])
                    for n, l in payload.items()}
            self.kvstore.save_decode(j, rows, active, pos)

    def compute(self, i: int, j: int, x, weights, kv):
        """COMPUTE body (main thread): one unit's jitted forward.  MoE
        units additionally gate, prefetch the routed-expert union through
        the pool, and combine (see _compute_moe)."""
        u = self.units[j]
        sig = (u.group, u.q)
        if self._phase == "prefill":
            x, cache1 = self._prefill_fns[sig](weights, x, self._angles)
            payload = ("prefill", cache1, self._slot)
        elif self._chunk_step is not None:
            return self._compute_mixed(sig, j, x, weights, kv)
        else:
            x, rows = self._decode_fns[sig](weights, x, kv, self._pos_dev,
                                            self._angles)
            payload = ("decode", rows,
                       (self._active, self._pos_snap, self._decode_view[1]))
        if u.moe:
            x = self._compute_moe(u, x, weights)
        return x, payload

    def _compute_mixed(self, sig, j: int, x, weights, kv):
        """One unit of a step carrying a prefill chunk (main thread):
        the decode batch (when present) and the chunk run back-to-back
        under the SAME streamed weights handle — one WEIGHT_LOAD per
        layer serves both, the tentpole invariant.  The chunk attends
        the engine-held fp32 prefix (earlier chunks' post-rope k/v —
        the same values a monolithic prefill attends in-pass) and the
        fresh rows append to the tiered store via the step's KV_SAVE.
        Capability gating guarantees dense global-attention units only
        (no MoE)."""
        x_dec, x_ck = x
        dec = None
        if x_dec is not None:
            x_dec, rows = self._decode_fns[sig](weights, x_dec, kv,
                                                self._pos_dev, self._angles)
            dec = (rows, (self._active, self._pos_snap,
                          self._decode_view[1]))
        pref = self._chunk["prefix"].get(j)
        pk, pv = pref if pref is not None else (None, None)
        c0, _, _ = self._chunk_step
        x_ck, k_ck, v_ck = self._chunk_fns[sig](
            weights, x_ck, pk, pv, self._chunk_angles, jnp.int32(c0))
        self._chunk["prefix"][j] = (
            k_ck if pk is None else jnp.concatenate([pk, k_ck], axis=1),
            v_ck if pv is None else jnp.concatenate([pv, v_ck], axis=1))
        ck = (k_ck, v_ck, self._chunk["slot"], c0)
        return (x_dec, x_ck), ("mixed", dec, ck)

    def _compute_moe(self, u: _Unit, x, weights):
        """Routed-union MoE (paper Appendix C.4, serving port): the gate
        forces a sync (experts unknown until it runs); then ONLY the union
        of routed experts streams through the pool as WEIGHT_LOAD tasks
        while the shared expert computes.  The combine is *compact*:
        expert ids are remapped onto the sorted union and the loaded
        device buffers are stacked into (|union|, ...) arrays, so the
        host->device boundary moves |union|-proportional bytes — the only
        link crossings are the per-expert WEIGHT_LOADs themselves (traced
        with their nbytes), never a bank-sized padded stack.  Numerics
        still match ``layers.apply_moe_ffn`` bit-for-bit (see
        ``moe.moe_ffn_union``).  Main thread (loads on pool threads)."""
        m = self.cfg.moe
        pre, gate, shared, combine = self._moe_fns[(u.group, u.q)]
        xn = pre(weights, x)
        gate_w, ids = gate(xn, u.router)          # sync point (paper)
        ids = np.asarray(ids)
        union = np.unique(ids.reshape(-1))        # sorted routed experts
        tasks = []
        for e in union:
            key = u.expert_keys[int(e)]
            t = Task(TaskType.WEIGHT_LOAD, f"w[{key}]",
                     lambda key=key: self.weights.load(key))
            t.nbytes = self.weights.nbytes(key)
            self.sched.pool.submit(t)
            tasks.append(t)
        shared_term = shared(weights, xn)         # overlaps expert loads
        ids_u = np.searchsorted(union, ids)       # order-preserving remap
        loaded = [t.wait() for t in tasks]        # device arrays (deq'd)
        wga = jnp.stack([we["w_gate"] for we in loaded])
        wup = jnp.stack([we["w_up"] for we in loaded])
        wdn = jnp.stack([we["w_down"] for we in loaded])
        self.stats["moe_stack_bytes"] += int(wga.nbytes + wup.nbytes
                                             + wdn.nbytes)
        return combine(x, xn, gate_w, jnp.asarray(ids_u), wga, wup, wdn,
                       shared_term)

    def finalize(self, i: int, x):
        if self.n_stages > 1:
            # the last stage's activation goes back to the head's device
            x = jax.device_put(x, self.stage_devices[0])
        if self._chunk_step is not None:
            x_dec, x_ck = x
            _, _, final = self._chunk_step
            if final:
                # first generated token of the chunked request: argmax
                # over the LAST prompt position, exactly what the
                # monolithic prefill head computes
                tok = self._head(self.resident["embed"],
                                 self.resident["final_norm"], x_ck)
                self._chunk_tok = int(np.asarray(tok)[0])
            if x_dec is None:
                return np.zeros(self.b_max, np.int32)
            x = x_dec
        if self._phase == "decode" and x.shape[1] > 1:
            # speculative verify: per-position argmax, (b, k+1)
            tok = self._spec_head(self.resident["embed"],
                                  self.resident["final_norm"], x)
        else:
            tok = self._head(self.resident["embed"],
                             self.resident["final_norm"], x)
        return np.asarray(tok)

    # ---- SlotEngineBase compute hooks ---------------------------------------
    def _begin_chunked_prefill(self, slot: int, req: Request) -> int:
        """Admission-time hook: under a chunked policy, claim the slot
        and stage the prompt for chunk-at-a-time prefill interleaved
        with decode steps.  At most ONE chunked prefill is in flight —
        a second arrival waits (BUSY) so its chunks don't compete for
        the same shared weight sweeps."""
        if not self.sched_policy.chunked:
            return self.CHUNK_OFF
        if self._chunk is not None:
            return self.CHUNK_BUSY
        self._chunk = dict(slot=slot, req=req, done=0, prefix={})
        return self.CHUNK_STARTED

    def _chunk_slot(self):
        return self._chunk["slot"] if self._chunk is not None else None

    def _mixed_step(self, active: List[int]) -> np.ndarray:
        """One pipeline step carrying the next prompt chunk of the
        in-flight chunked prefill — alongside the decode batch when one
        exists (main thread).  Both rides the SAME ``sched.generate``
        call, so each layer's weights stream exactly once for the pair.
        The decode view is widened to a SUPERSET covering the chunk
        slot/extent so warm tail preloads priced during this step stay
        valid once the chunk's rows land (stale rows are masked by
        ``kv_pos <= pos`` downstream, the established inactive-slot
        precedent)."""
        ck = self._chunk
        req, slot = ck["req"], ck["slot"]
        cap = max(1, self.sched_policy.chunk_cap())
        c0 = ck["done"]
        c1 = min(len(req.prompt), c0 + cap)
        final = c1 == len(req.prompt)
        self._chunk_step = (c0, c1 - c0, final)
        if active:
            self._step_setup(active)
            base, lb, ll = self._decode_view
            self._decode_view = (base, max(lb, slot + 1), max(ll, c1))
            self._pos_dev = jnp.asarray(self.pos)
            self._angles = T._angles(self.cfg, self._pos_dev[:, None])
            x_dec = self._embed(self.resident["embed"],
                                jnp.asarray(self.tokens)[:, None], "decode")
        else:
            # chunk-only step: nothing to load — the chunk attends only
            # the engine-held fp32 prefix of its own earlier chunks
            self._phase = "chunk"
            x_dec = None
        self._chunk_angles = T._angles(self.cfg, jnp.arange(c0, c1))
        x_ck = self._embed(self.resident["embed"],
                           jnp.asarray(req.prompt[c0:c1])[None], "prefill")
        toks = self.sched.generate(self, lambda i: (x_dec, x_ck), 1)
        self.stats["prefill_chunks"] += 1
        ck["done"] = c1
        chunk_only = x_dec is None
        self._chunk_step = None
        if chunk_only:
            # warm tail preloads captured phase "chunk" (value None)
            self.sched.drop_kv_preloads()
        if final:
            self._chunk = None
            if self.draft is not None:
                self.draft.prefill_slot(slot, req.prompt)
            self._finish_prefill(slot, req, self._chunk_tok)
        return (toks[-1] if not chunk_only
                else np.zeros(self.b_max, np.int32))

    def _prefill_into_slot(self, slot: int, req: Request) -> int:
        """b=1 prompt pass through the pipeline (main thread).  Any warm
        KV preload issued at the tail of this call captured the prefill
        phase (value None) and is dropped — the next decode step reloads
        fresh; its weight preload stays valid (weights are immutable)."""
        self._phase = "prefill"
        self._slot = slot
        s = len(req.prompt)
        positions = jnp.arange(s)
        self._angles = T._angles(self.cfg, positions)
        x0 = self._embed(self.resident["embed"],
                         jnp.asarray(req.prompt)[None], "prefill")
        toks = self.sched.generate(self, lambda i: x0, 1)
        self.sched.drop_kv_preloads()
        if self.draft is not None:
            # admit the prompt into the draft's device cache too (the
            # draft is slaved to the same slot/pos state)
            self.draft.prefill_slot(slot, req.prompt)
        # skip the prefill's trace window for the bandwidth feedback: a
        # full-prompt forward is far costlier per layer than a decode
        # step, and folding it into the compute EWMA would resolve the
        # window too shallow exactly while request load is ramping
        self._trace_mark = len(self.trace.events())
        return int(toks[-1][0])

    def _observe_trace(self):
        """Feed the Trace delta since the last step into the adaptive
        policy's bandwidth/compute EWMAs (main thread, between steps):
        transfer bytes over merged transfer busy time is the MEASURED
        link bandwidth — the feedback that replaces the budget's assumed
        bw in the window sizing."""
        observe = getattr(self.preload_policy, "observe", None)
        if observe is None:
            return
        evs = self.trace.events()
        new, self._trace_mark = evs[self._trace_mark:], len(evs)
        if not new:
            return
        xfer = [e for e in new if e.kind in ("weight_load", "kv_load")]
        comp = [e for e in new if e.kind == "compute"]
        observe(
            transfer_bytes=sum(e.nbytes for e in xfer),
            transfer_busy_s=_merged_busy((e.t_start, e.t_end)
                                         for e in xfer),
            compute_busy_s=_merged_busy((e.t_start, e.t_end)
                                        for e in comp),
            layers=len(comp))

    def _resize_window(self, active: List[int]):
        """Consult the preload policy with the LIVE pressure snapshot
        and re-size the scheduler's window between steps (main thread).
        ``StaticDepth`` always answers the same, so the pre-spec engines
        are reproduced bit for bit; ``AdaptiveDepth`` deepens under
        light load and shrinks as KV/spill pressure ramps — pricing the
        per-layer KV term at the store's EXACT live payload and the
        link at the measured-bandwidth EWMA."""
        if isinstance(self.preload_policy, StaticDepth):
            return
        self._observe_trace()
        lb = max(active) + 1
        max_pos = int(max(self.pos[s] for s in active))
        p = Pressure(active=len(active), max_pos=max_pos,
                     spills=len(self._spill_lru),
                     kv_layer_bytes=self.kvstore.max_live_load_nbytes(
                         lb, max(1, max_pos)))
        d = self.sched.set_depth(self.preload_policy.depth(p))
        if d != self.stats["preload_depth"]:
            self.stats["depth_resizes"] += 1
            self.stats["preload_depth"] = d

    def _step_setup(self, active: List[int]):
        """Shared per-step state refresh (main thread): preload-policy
        resize, phase flip, position snapshot, and the atomic live view
        for this step's (and its tail preloads') KV extents — scheduler
        iteration base + occupied slots + written positions.  live_len =
        max(pos) covers every row attention can read below the write
        position; the rows AT pos.. are written by this step's compute
        before they are attended."""
        self._resize_window(active)
        self._phase = "decode"
        self._active = list(active)
        self._pos_snap = self.pos.copy()
        base = self.sched._iter0
        self._decode_view = (base, max(active) + 1,
                             max(1, int(max(self.pos[s] for s in active))))
        # prune dead extent memos (iterations before this step can no
        # longer have loads in flight; main thread, GIL-atomic dels)
        for k in [k for k in self._extent_memo if k < base]:
            del self._extent_memo[k]

    def attach_draft(self, draft, k: int):
        """Enable speculative decoding with ``draft`` — anything with
        ``prefill_slot(slot, prompt)`` and ``propose(tokens, pos, k) ->
        (b_max, k)`` (``core.draft.ResidentDraft``, or a test fake).
        Greedy accept/reject keeps the emitted stream bit-identical to
        non-speculative decode for ANY proposal stream, so a draft whose
        cache went stale (e.g. a preemption resume skips the draft
        prefill) only costs acceptance length, never correctness.  Main
        thread, between steps."""
        cap = spec_decode_capability(self.cfg)
        if cap is not None:
            raise UnsupportedModelError(
                cap, f"speculative decoding needs a global-attention "
                     f"dense decoder target (failing capability: {cap})")
        self.draft = draft
        self._spec_k = max(1, int(k))
        self.trace.meta.update(spec_k=self._spec_k)

    def _emitted_tokens(self, active, nt):
        if self._spec_emitted is not None:
            return self._spec_emitted
        return super()._emitted_tokens(active, nt)

    def _decode_active(self, active: List[int]) -> np.ndarray:
        """One batched decode step through the pipeline (main thread).
        With a warm scheduler the step's first weight/KV loads were
        pre-submitted during the previous step's tail compute.  With a
        draft attached the step is a draft-then-verify pass emitting up
        to spec_k + 1 tokens per slot (``_emitted_tokens``)."""
        self._spec_emitted = None
        self._spec_s = 1
        if self._chunk is not None:
            # a chunked prefill is in flight: run the mixed step (decode
            # batch + one prompt chunk under shared weight loads).  Spec
            # decode resumes once the chunk completes.
            return self._mixed_step(active)
        k = 0
        if self.draft is not None:
            # headroom: the verify writes rows pos..pos+k, and the last
            # emitted token must still fit under the max_len-1 release
            # bound the base class enforces per token
            head = self.max_len - 1 - int(max(self.pos[s] for s in active))
            k = max(0, min(self._spec_k, head))
        if k >= 1:
            return self._decode_spec(active, k)
        self._step_setup(active)
        self._pos_dev = jnp.asarray(self.pos)
        self._angles = T._angles(self.cfg, self._pos_dev[:, None])
        x0 = self._embed(self.resident["embed"],
                         jnp.asarray(self.tokens)[:, None], "decode")
        toks = self.sched.generate(self, lambda i: x0, 1)
        return toks[-1]

    def _decode_spec(self, active: List[int], k: int) -> np.ndarray:
        """Draft-then-verify decode step (main thread): the resident
        draft proposes ``k`` tokens while ``prime_weights`` streams the
        verify pass's first weight loads over the otherwise-idle link;
        the target then scores all ``k+1`` positions in ONE trip through
        the streamed layer stack and the greedy accept rule
        (``core.draft.accepted_tokens``) emits the longest prefix that
        matches non-speculative decode — plus the target's bonus token
        at the divergence.  Rejected rows are invalidated in the tiered
        store (``truncate``) and the stale KV preloads dropped."""
        self._step_setup(active)
        self._spec_s = k + 1
        # verify-pass weight loads stream while the draft computes (the
        # warm-window generalization of the cross-step preload; a warm
        # tail already has them in flight, making this a no-op)
        t0 = time.perf_counter()
        primed = self.sched.prime_weights(self)
        props = np.asarray(self.draft.propose(self.tokens, self.pos, k),
                           np.int32)                       # (b_max, k)
        draft_s = time.perf_counter() - t0
        # verify input: [current token, d1..dk] at positions pos..pos+k
        seq = np.concatenate(
            [np.asarray(self.tokens, np.int32)[:, None], props], axis=1)
        self._pos_dev = jnp.asarray(self.pos)
        pos_mat = self._pos_dev[:, None] + jnp.arange(k + 1)[None, :]
        self._angles = T._angles(self.cfg, pos_mat)
        x0 = self._embed(self.resident["embed"], jnp.asarray(seq), "decode")
        toks = self.sched.generate(self, lambda i: x0, 1)
        tgt = np.asarray(toks[-1])                         # (b_max, k+1)
        # greedy accept/reject + row invalidation.  Saves may still be in
        # flight (warm mode) and would re-write rejected rows after the
        # truncate; drain first.  The in-flight KV preloads are stale
        # either way — a spec step advances the extent by up to k+1,
        # past the +1 the warm tail priced — so they are dropped and the
        # next step reloads fresh (weight preloads stay: immutable).
        self.sched.drain_saves()
        self.sched.drop_kv_preloads()
        # the dropped preloads memoized their extents (priced at the old
        # +1-per-step heuristic); with the tasks gone the memos are dead
        # weight, and the next step's fresh loads must re-price at the
        # advanced positions — a stale memo under-ships rows the verify
        # mask then admits as zeros, corrupting the softmax
        self._extent_memo.clear()
        emitted: Dict[int, List[int]] = {}
        accepts = []
        for i in active:
            acc = accepted_tokens(props[i], tgt[i])
            emitted[i] = acc
            accepts.append(len(acc) - 1)
            # valid rows: inputs [cur, d1..da] at pos..pos+a
            self.kvstore.truncate(i, int(self._pos_snap[i]) + len(acc))
        self._spec_emitted = emitted
        self.stats["spec_steps"] += 1
        self.stats["spec_proposed"] += k * len(active)
        self.stats["spec_accepted"] += int(sum(accepts))
        self.trace.meta.setdefault("spec_steps", []).append(dict(
            k=int(k), primed=int(primed), draft_s=float(draft_s),
            accepts=[int(a) for a in accepts]))
        nt = np.zeros(self.b_max, np.int32)
        for i in active:
            nt[i] = emitted[i][-1]
        return nt

    # ---- slot spill/restore (host<->host; rows already offloaded) -----------
    def _offload_snapshot(self, slot: int):
        """The KV already lives on host, so the snapshot is just the slot
        id — but in warm mode pipeline saves may still be in flight, and
        the spill's row reads must not race them (main thread; blocks on
        outstanding saves)."""
        self.sched.drain_saves()
        return slot

    def _offload_write(self, ns: str, slot: int):
        """Spill: row copies out of the tiered KV store under
        ``{ns}/{unit}/{name}`` keys so the slot can be reused while the
        request is parked (packed rows spill packed — lossless, ~3x
        below the bf16 rows under kv_mode='int4').  Transfer-pool
        thread when async."""
        self.kvstore.spill(self.host, ns, slot)

    def restore_slot(self, slot: int, ns: str):
        """Bring a parked request's rows back into a slot (main thread).
        Mutates the store's host rows outside the pipeline, so
        outstanding saves are drained first and any warm KV preloads
        (now stale device copies) are dropped."""
        self.sched.drain_saves()
        self.sched.drop_kv_preloads()
        self.kvstore.restore(self.host, ns, slot)

    # ---- lifecycle / introspection ------------------------------------------
    def pipeline_report(self):
        """Per-task-type busy time/bytes, compute-thread utilization and
        bubble accounting derived from the Trace (paper Fig. 8/9
        analogue).  Main thread; safe while transfers are in flight."""
        return self.trace.report()

    def shutdown(self):
        """Drain slot spills + pipeline saves, stop the pool(s) (main
        thread; blocking).  Staged engines own one pool per stage; pool 0
        doubles as the slot-spill pool and is stopped last."""
        super().shutdown()
        self.sched.shutdown()
        for p in self._stage_pools[1:]:
            p.shutdown()
        self._kv_pool.shutdown()
