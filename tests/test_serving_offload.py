"""Serving offload round-trips: OffloadedServingEngine (weights streamed
through the PIPO pipeline) must match the resident ServingEngine token for
token — warm or cold pipeline, FP16 or INT4 streaming, dense or MoE — and
slot offload -> restore -> resume must be lossless."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.core.pipeline import ThreadPool
from repro.serving import (EngineSpec, OffloadedServingEngine, Request,
                           ServingEngine, create_engine)
from repro.serving.offload_engine import quant_roundtrip_params


def _cfg():
    return scaled_down(get_config("tinyllama-1.1b"))


def _offload_spec(cfg, **kw):
    """Spec-path construction (the canonical create_engine route); most
    tests below keep the legacy kwarg shim on purpose — both must act on
    identical plans (tests/test_spec.py asserts that)."""
    kw.setdefault("placement", "host")
    return create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                    **kw))


def _moe_cfg():
    return scaled_down(get_config("llama4-scout-17b-a16e"))


def _prompts(cfg, n=4, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
            for i in range(n)]


def _serve(eng, prompts, max_new=5):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.copy(), max_new=max_new))
    done = eng.run()
    out = {r.rid: r.out for r in done}
    if isinstance(eng, OffloadedServingEngine):
        eng.shutdown()
    return out


@pytest.fixture(scope="module")
def resident_tokens():
    cfg = _cfg()
    return _serve(ServingEngine(cfg, b_max=2, max_len=64), _prompts(cfg))


def test_offload_decode_parity_host(resident_tokens):
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="host", pipeline="performance")
    assert eng.warm                    # warm pipeline is the default
    assert _serve(eng, _prompts(cfg)) == resident_tokens


def test_offload_decode_parity_cold(resident_tokens):
    """warm=False reproduces the PR-1 cold-per-step pipeline; tokens are
    identical either way (warm is a scheduling change only)."""
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="host", pipeline="performance",
                                 warm=False)
    assert _serve(eng, _prompts(cfg)) == resident_tokens


@pytest.mark.parametrize("depth", [2, 3])
def test_offload_decode_parity_depth(resident_tokens, depth):
    """Depth-D windows are a scheduling change only: token parity with
    the resident engine holds at every preload depth.  Built through
    the spec path — a StaticDepth(D) plan must match the pre-redesign
    engine bit for bit (acceptance criterion)."""
    from repro.serving import StaticDepth
    cfg = _cfg()
    eng = _offload_spec(cfg, b_max=2, max_len=64, pipeline="performance",
                        depth=depth)
    assert isinstance(eng.preload_policy, StaticDepth)
    assert eng.sched.depth == min(depth, len(eng.units) - 1)
    assert _serve(eng, _prompts(cfg)) == resident_tokens


def test_offload_default_depth_is_budget_sized():
    """depth=None sizes the window from the memory budget
    (autoconfig.serving_preload_depth) instead of pinning the paper's
    two-resident-layer constant."""
    from repro.core.autoconfig import serving_preload_depth
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="host", pipeline="performance")
    want = serving_preload_depth(cfg, b_max=2, max_len=64, spill_cap=32)
    assert eng.sched.depth == min(want, len(eng.units) - 1) >= 1
    eng.shutdown()


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["memory", "sequential"])
def test_offload_decode_parity_modes(resident_tokens, mode):
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="host", pipeline=mode)
    assert _serve(eng, _prompts(cfg)) == resident_tokens


@pytest.mark.slow
def test_offload_decode_parity_disk(resident_tokens, tmp_path):
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="disk", pipeline="performance",
                                 disk_root=str(tmp_path / "weights"))
    assert _serve(eng, _prompts(cfg)) == resident_tokens


# ---------------------------------------------------------------------------
# INT4 weight streaming
# ---------------------------------------------------------------------------


def test_offload_int4_decode_parity():
    """INT4 streaming decodes token-identical to a resident engine holding
    the same quantize->dequantize roundtripped weights (the 'INT4
    resident path'), and the streamed bytes actually shrink."""
    cfg = _cfg()
    ref = ServingEngine(cfg, b_max=2, max_len=64)
    ref.params = quant_roundtrip_params(cfg, ref.params)
    ref_tokens = _serve(ref, _prompts(cfg))

    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="host", pipeline="performance",
                                 quant="int4")
    int4_bytes = sum(eng.weights.nbytes(u.key) for u in eng.units)
    assert _serve(eng, _prompts(cfg)) == ref_tokens

    fp32 = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                  placement="host")
    fp32_bytes = sum(fp32.weights.nbytes(u.key) for u in fp32.units)
    fp32.shutdown()
    assert int4_bytes < 0.5 * fp32_bytes      # packed nibbles + scales


@pytest.mark.parametrize("depth", [2, 3])
def test_offload_int4_depth_parity(depth):
    """Acceptance criterion: parity holds at every depth/quant combo —
    an INT4 StaticDepth(D) plan still matches the roundtripped resident
    reference token for token."""
    cfg = _cfg()
    ref = ServingEngine(cfg, b_max=2, max_len=64)
    ref.params = quant_roundtrip_params(cfg, ref.params)
    ref_tokens = _serve(ref, _prompts(cfg))
    eng = _offload_spec(cfg, b_max=2, max_len=64, pipeline="performance",
                        quant="int4", depth=depth)
    assert _serve(eng, _prompts(cfg)) == ref_tokens


def test_int4_quant_changes_tokens_vs_fp16():
    """Sanity: the INT4 path really quantizes (its reference differs from
    the plain FP32 params for at least one leaf)."""
    cfg = _cfg()
    eng = ServingEngine(cfg, b_max=1, max_len=32)
    q = quant_roundtrip_params(cfg, eng.params)
    diffs = 0
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(q)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            diffs += 1
    assert diffs > 0


# ---------------------------------------------------------------------------
# Tiered KV: live-row slabs + INT4 KV streaming
# ---------------------------------------------------------------------------


def test_kv_load_ships_live_rows_not_the_slab():
    """Live-row slicing on the real engine: with ONE short request in a
    4-slot engine, every decode KV_LOAD's traced bytes sit strictly
    below the allocated (b_max, max_len) slab, the live extent is
    recorded on the event, and fp32 tokens still match the resident
    engine bit for bit (the padding is value-invisible)."""
    cfg = _cfg()
    prompt = _prompts(cfg, 1)[0]
    ref = ServingEngine(cfg, b_max=4, max_len=64)
    ref.submit(Request(rid=0, prompt=prompt.copy(), max_new=5))
    want = ref.run()[0].out

    eng = OffloadedServingEngine(cfg, b_max=4, max_len=64,
                                 placement="host", pipeline="performance")
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=5))
    got = eng.run()[0].out
    assert got == want
    kv_loads = [e for e in eng.trace.events()
                if e.kind == "kv_load" and e.nbytes]
    assert kv_loads
    slab = max(eng.kvstore.slab_nbytes(j) for j in range(len(eng.units)))
    assert all(e.nbytes < slab for e in kv_loads)
    # one active slot, short positions: extents are (1, pos)-shaped
    assert all(e.extent is not None and e.extent[0] == 1
               for e in kv_loads)
    assert max(e.extent[1] for e in kv_loads) < 64
    # and the whole traced KV volume sits far below slab * loads
    rep = eng.pipeline_report()
    assert rep["per_kind"]["kv_load"]["bytes"] < \
        0.5 * slab * rep["per_kind"]["kv_load"]["count"]
    assert rep["per_kind"]["kv_save"]["bytes"] > 0     # saves accounted
    eng.shutdown()


@pytest.fixture(scope="module")
def kv_roundtrip_tokens():
    """Resident reference whose newly-written cache rows roundtrip
    through the store's exact quantize->dequantize (fp32 weights)."""
    from repro.serving import KVRoundtripServingEngine
    cfg = _cfg()
    return _serve(KVRoundtripServingEngine(cfg, b_max=2, max_len=64),
                  _prompts(cfg))


@pytest.fixture(scope="module")
def kv_int4_roundtrip_tokens():
    """Same reference with INT4-roundtripped weights on top — the
    weights-int4 x kv-int4 corner."""
    from repro.serving import KVRoundtripServingEngine
    cfg = _cfg()
    ref = KVRoundtripServingEngine(cfg, b_max=2, max_len=64)
    ref.params = quant_roundtrip_params(cfg, ref.params)
    return _serve(ref, _prompts(cfg))


@pytest.mark.parametrize("depth", [1, 2])
def test_kv_int4_decode_parity(kv_roundtrip_tokens, depth):
    """Acceptance criterion: kv_mode='int4' decodes token-identical to
    the KV-roundtripped resident reference at every preload depth
    (fp32 weights)."""
    cfg = _cfg()
    eng = _offload_spec(cfg, b_max=2, max_len=64, pipeline="performance",
                        kv_mode="int4", depth=depth)
    assert eng.kvstore.kv_mode == "int4"
    assert _serve(eng, _prompts(cfg)) == kv_roundtrip_tokens


@pytest.mark.parametrize("depth", [1, 2])
def test_kv_int4_weights_int4_decode_parity(kv_int4_roundtrip_tokens,
                                            depth):
    """Acceptance criterion: the full INT4 corner — packed weights AND
    packed KV — still matches its roundtripped resident reference at
    depth {1, 2}."""
    cfg = _cfg()
    eng = _offload_spec(cfg, b_max=2, max_len=64, pipeline="performance",
                        quant="int4", kv_mode="int4", depth=depth)
    assert _serve(eng, _prompts(cfg)) == kv_int4_roundtrip_tokens


def test_kv_int4_actually_quantizes(resident_tokens, kv_roundtrip_tokens):
    """Sanity: INT4 KV is a real precision change (the reference differs
    from the plain resident tokens), so the parity above is not
    vacuous; and the traced KV bytes shrink accordingly."""
    assert kv_roundtrip_tokens != resident_tokens
    cfg = _cfg()
    eng4 = _offload_spec(cfg, b_max=2, max_len=64, kv_mode="int4")
    fp = _offload_spec(cfg, b_max=2, max_len=64)
    assert eng4.kvstore.slab_nbytes(0) < 0.5 * fp.kvstore.slab_nbytes(0)
    fp.shutdown()
    eng4.shutdown()


def test_kv_int4_spill_restore_resume_parity():
    """Preempt/resume under INT4 KV: packed rows spill and restore
    losslessly, so the interrupted stream equals the uninterrupted
    one."""
    from repro.serving import KVRoundtripServingEngine
    cfg = _cfg()
    prompt = _prompts(cfg, 1)[0]
    ref = KVRoundtripServingEngine(cfg, b_max=2, max_len=64)
    ref.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    uninterrupted = ref.run()[0].out

    eng = _offload_spec(cfg, b_max=2, max_len=64, kv_mode="int4")
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    eng._admit()
    done = []
    for _ in range(3):
        eng._decode_step(done)
    assert not done
    eng.preempt_slot(0)
    done = eng.run()
    eng.shutdown()
    assert done[0].out == uninterrupted
    assert eng.stats["slot_restores"] == 1


def test_kv_mode_moe_decode_parity():
    """INT4 KV composes with MoE routed-union serving (every mixer kind
    the offloaded engine carries streams through the same store)."""
    from repro.serving import KVRoundtripServingEngine
    cfg = _moe_cfg()
    prompts = _prompts(cfg, 3)
    ref = _serve(KVRoundtripServingEngine(cfg, b_max=2, max_len=48),
                 prompts, max_new=4)
    eng = _offload_spec(cfg, b_max=2, max_len=48, pipeline="performance",
                        kv_mode="int4")
    assert _serve(eng, prompts, max_new=4) == ref


def test_moe_quant_resident_parity():
    """moe_quant='int4' — the resident engine's routed expert stacks
    packed once at load, unpacked per step through the fused-int4 path —
    decodes token-identical to a resident engine holding the SAME
    roundtripped stacks, and the resident expert bytes shrink >6x."""
    import jax.numpy as jnp
    from repro.quant.int4 import dequantize_int4_stack
    cfg = _moe_cfg()
    prompts = _prompts(cfg, 3)
    eng = create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=False,
                                   b_max=2, max_len=48, moe_quant="int4"))
    assert eng.plan.moe_quant == "int4"
    assert "moe_quant" in eng.plan.provenance
    stacks = ("w_gate", "w_up", "w_down")
    packed_tables = [
        (part, i, t) for part in ("pat", "rem")
        for i, t in enumerate(eng.params.get(part, ()))
        if isinstance(t, dict) and "w_gate#q" in t]
    assert packed_tables                      # every MoE table packed
    for _, _, t in packed_tables:
        assert not any(n in t for n in stacks)     # fp leaves replaced
        assert "wg" in t                           # router stays fp

    # reference: plain resident engine holding the dequantized stacks
    ref = ServingEngine(cfg, b_max=2, max_len=48)
    packed_b = fp_b = 0
    ref_parts = dict(ref.params)
    for part, i, t in packed_tables:
        rt = dict(ref_parts[part][i])
        for n in stacks:
            fp_b += rt[n].nbytes
            packed_b += t[n + "#q"].nbytes + t[n + "#s"].nbytes
            rt[n] = dequantize_int4_stack(t[n + "#q"], t[n + "#s"],
                                          jnp.float32)
        ref_parts[part] = (ref_parts[part][:i] + (rt,)
                           + ref_parts[part][i + 1:])
    ref.params = ref_parts
    assert packed_b * 6 < fp_b                # real resident-memory win
    assert _serve(eng, prompts, max_new=4) == _serve(ref, prompts,
                                                     max_new=4)


def test_moe_quant_dropped_on_offloaded_plan():
    """moe_quant is a resident-engine feature: an offloaded plan drops
    it with provenance (experts stream through the unit quant path)."""
    cfg = _moe_cfg()
    plan = EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                      moe_quant="int4").resolve()
    assert plan.moe_quant is None
    assert "dropped" in plan.provenance["moe_quant"]


# ---------------------------------------------------------------------------
# MoE routed-union serving
# ---------------------------------------------------------------------------


def test_offload_moe_decode_parity():
    """MoE serving (router resident, per-expert streaming) matches the
    resident engine token for token."""
    cfg = _moe_cfg()
    prompts = _prompts(cfg, 3)
    ref = _serve(ServingEngine(cfg, b_max=2, max_len=48), prompts,
                 max_new=4)
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=48,
                                 placement="host", pipeline="performance")
    assert _serve(eng, prompts, max_new=4) == ref


def test_offload_moe_loads_routed_union_only():
    """Decode loads only the routed-expert union per MoE layer — asserted
    on trace bytes: expert WEIGHT_LOAD volume over the decode steps is
    exactly union-size * per-expert bytes, strictly below the whole
    bank."""
    cfg = _moe_cfg()              # scaled llama4: 4 experts, top_k=1
    m = cfg.moe
    eng = OffloadedServingEngine(cfg, b_max=1, max_len=48,
                                 placement="host", pipeline="performance")
    eng.submit(Request(rid=0, prompt=_prompts(cfg, 1)[0], max_new=4))
    eng._admit()                               # prefill (routes per-token)
    expert_keys = [k for u in eng.units if u.moe for k in u.expert_keys]
    snap = dict(eng.weights.load_counts)
    done = []
    while eng.slots[0] is not None:
        eng._decode_step(done)
    assert len(done) == 1

    n_moe_units = sum(1 for u in eng.units if u.moe)
    steps = eng.stats["decode_steps"]
    decode_loads = sum(eng.weights.load_counts.get(k, 0) - snap.get(k, 0)
                       for k in expert_keys)
    # b=1, top_k=1: the routed union is exactly ONE expert per MoE unit
    # per decode step — 4x below the whole bank
    assert decode_loads == steps * n_moe_units
    assert decode_loads < steps * n_moe_units * m.num_experts
    # and the trace carries the byte accounting: expert WEIGHT_LOAD bytes
    # equal loads * per-expert buffer size (scheduler-named unit loads use
    # 'w[0]'-style names; expert tasks are named by their store key)
    per_expert = {k: eng.weights.nbytes(k) for k in expert_keys}
    traced = eng.trace.bytes_moved("weight_load", "w[u")
    assert traced == sum(eng.weights.load_counts.get(k, 0) * b
                         for k, b in per_expert.items())
    eng.shutdown()


def test_offload_moe_compact_combine_stacks_union_bytes():
    """The combine boundary is |union|-proportional too (the PR-2 gap):
    the compact combine stacks exactly the loaded experts — one fp32
    slot per expert WEIGHT_LOAD — never a zero-padded full bank, so
    total stacked bytes sit strictly below the bank-sized staging the
    padded combine used to do every MoE step."""
    cfg = _moe_cfg()              # scaled llama4: 4 experts, top_k=1
    m = cfg.moe
    eng = OffloadedServingEngine(cfg, b_max=1, max_len=48,
                                 placement="host", pipeline="performance")
    eng.submit(Request(rid=0, prompt=_prompts(cfg, 1)[0], max_new=4))
    done = eng.run()
    assert len(done) == 1
    expert_keys = [k for u in eng.units if u.moe for k in u.expert_keys]
    total_loads = sum(eng.weights.load_counts.get(k, 0)
                      for k in expert_keys)
    d, f = cfg.d_model, m.expert_d_ff
    per_expert_fp32 = 4 * (2 * d * f + f * d)    # w_gate + w_up + w_down
    assert eng.stats["moe_stack_bytes"] == total_loads * per_expert_fp32
    n_moe_units = sum(1 for u in eng.units if u.moe)
    n_combines = (eng.stats["prefills"]
                  + eng.stats["decode_steps"]) * n_moe_units
    assert eng.stats["moe_stack_bytes"] \
        < n_combines * m.num_experts * per_expert_fp32
    eng.shutdown()


# ---------------------------------------------------------------------------
# Warm pipeline on the live engine
# ---------------------------------------------------------------------------


def test_warm_engine_preloads_across_decode_steps():
    """On the live engine the warm scheduler leaves at most one pending
    weight preload between steps, and steady-state decode produces more
    w[0] loads than decode steps would cold-start (the preloads ARE the
    per-step loads)."""
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                 placement="host", pipeline="performance")
    _serve(eng, _prompts(cfg, 2), max_new=4)
    # every generate() call left a w[0] preload pending for the next one;
    # totals: one w[0] per call + one dangling => calls + 1
    calls = eng.stats["prefills"] + eng.stats["decode_steps"]
    w0 = [e for e in eng.trace.events()
          if e.kind == "weight_load" and e.name == "w[0]"]
    assert len(w0) == calls + 1


# ---------------------------------------------------------------------------
# Slot spill: epoch namespacing + LRU retention
# ---------------------------------------------------------------------------


def test_slot_offload_restore_resume_parity():
    """Preempt a mid-flight request (KV spilled to host), resume it via
    restore_slot, and the full token stream must equal an uninterrupted
    run — the slot-granularity PIPO KV round-trip."""
    cfg = _cfg()
    prompt = _prompts(cfg, 1)[0]

    ref = ServingEngine(cfg, b_max=2, max_len=64)
    ref.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    uninterrupted = ref.run()[0].out

    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64, placement="host")
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    eng._admit()
    done = []
    for _ in range(3):
        eng._decode_step(done)
    assert not done
    eng.preempt_slot(0)
    assert eng.slots[0] is None and eng.queue     # parked, back in queue
    assert eng.queue[0].spill_ns                  # namespace recorded
    done = eng.run()
    eng.shutdown()
    assert done[0].out == uninterrupted
    assert eng.stats["slot_restores"] == 1


def test_resident_async_slot_offload_roundtrip():
    """ServingEngine with a transfer pool spills finished slots as KV_SAVE
    tasks (overlapped), and the spilled rows still restore exactly.

    The two requests finish on different steps, so the first spill is
    followed by further decode steps whose jitted _decode donates the old
    cache buffers — the snapshot must not alias them (read-after-free on
    the pool thread otherwise)."""
    cfg = _cfg()
    pool = ThreadPool(2)
    eng = ServingEngine(cfg, b_max=2, max_len=48, kv_pool=pool)
    rng = np.random.default_rng(0)
    eng.submit(Request(rid=7, prompt=rng.integers(
        0, cfg.vocab_size, (8,)).astype(np.int32), max_new=3))
    eng.submit(Request(rid=8, prompt=rng.integers(
        0, cfg.vocab_size, (9,)).astype(np.int32), max_new=12))
    done = eng.run()
    eng.shutdown()                 # drain in-flight slot saves
    pool.shutdown()
    assert len(done) == 2
    ns7, ns8 = eng._spill_ns(7), eng._spill_ns(8)   # epoch 1 namespaces
    assert any(k.startswith(ns7 + "/") for k in eng.host.keys())
    assert any(k.startswith(ns8 + "/") for k in eng.host.keys())
    eng.restore_slot(0, ns7)
    # restored rows equal the rows present when the request finished
    flat, _ = jax.tree_util.tree_flatten_with_path(eng.caches)
    for i, (path, leaf) in enumerate(flat):
        ax = eng._batch_axis(path)
        idx = [slice(None)] * leaf.ndim
        idx[ax] = 0
        np.testing.assert_array_equal(
            np.asarray(leaf[tuple(idx)]), eng.host.get(f"{ns7}/{i}"))


def test_spill_epoch_namespacing_across_runs():
    """Reused rids across run() calls land in distinct namespaces, so a
    later run can never alias (or clobber) an earlier run's spill."""
    cfg = _cfg()
    eng = ServingEngine(cfg, b_max=1, max_len=48)
    p = _prompts(cfg, 1)[0]
    eng.submit(Request(rid=0, prompt=p.copy(), max_new=2))
    eng.run()
    eng.submit(Request(rid=0, prompt=p.copy(), max_new=2))
    eng.run()
    eng.shutdown()
    keys = eng.host.keys()
    assert any(k.startswith("e1/slot0/") for k in keys)
    assert any(k.startswith("e2/slot0/") for k in keys)


def test_spill_lru_eviction_prefers_finished_over_parked():
    """With spill_cap=1: a finished request's spill is evicted when the
    cap is exceeded, but a parked (preempted) request's spill is pinned —
    it must survive to resume losslessly."""
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=1, max_len=64,
                                 placement="host", spill_cap=1)
    prompts = _prompts(cfg, 2)
    # park rid=0 mid-flight: its spill namespace becomes pinned
    eng.submit(Request(rid=0, prompt=prompts[0].copy(), max_new=8))
    eng._admit()
    done = []
    eng._decode_step(done)
    eng.preempt_slot(0)
    parked_ns = eng.queue[0].spill_ns
    assert parked_ns
    # slip rid=1 in FRONT of the parked request so it occupies the single
    # slot; the parked one stays queued (and therefore pinned) meanwhile
    eng.submit(Request(rid=1, prompt=prompts[1].copy(), max_new=2))
    eng.queue.reverse()                # [rid1, parked rid0]
    eng._admit()
    assert eng.slots[0] is not None and eng.slots[0].rid == 1
    while eng.slots[0] is not None:    # finish rid1 -> its slot spills
        eng._decode_step(done)
    # cap=1 with two spills (parked + rid1's): rid1's was evicted, the
    # parked one survived the LRU pass despite being older
    assert eng.stats["spill_evictions"] == 1
    assert any(k.startswith(parked_ns + "/") for k in eng.host.keys()), \
        "parked request's spill was evicted"
    assert not any(k.startswith(eng._spill_ns(1) + "/")
                   for k in eng.host.keys())
    # the parked request still resumes losslessly after the eviction
    resumed = eng.run()
    eng.shutdown()
    ref = OffloadedServingEngine(cfg, b_max=1, max_len=64,
                                 placement="host")
    ref.submit(Request(rid=0, prompt=prompts[0].copy(), max_new=8))
    expect = ref.run()[0].out
    ref.shutdown()
    assert [r.out for r in resumed if r.rid == 0] == [expect]


def test_spill_cap_never_evicts_a_just_preempted_request():
    """Regression: the request being preempted must already count as
    parked when its own spill is recorded — with spill_cap=1 and another
    parked request pinning the LRU, the second preemption's spill used
    to be evicted immediately, and its resume raised KeyError."""
    cfg = _cfg()
    eng = ServingEngine(cfg, b_max=2, max_len=64, spill_cap=1)
    prompts = _prompts(cfg, 2)
    for rid in (0, 1):
        eng.submit(Request(rid=rid, prompt=prompts[rid].copy(), max_new=8))
    eng._admit()
    done = []
    eng._decode_step(done)
    eng.preempt_slot(0)               # parks A (pins its spill)
    eng.preempt_slot(1)               # parks B — must be pinned too
    for r in eng.queue:
        assert any(k.startswith(r.spill_ns + "/")
                   for k in eng.host.keys()), f"rid {r.rid} spill evicted"
    resumed = {r.rid: r.out for r in eng.run()}
    # both resumed losslessly: same tokens as an uninterrupted run
    ref = ServingEngine(cfg, b_max=2, max_len=64)
    for rid in (0, 1):
        ref.submit(Request(rid=rid, prompt=prompts[rid].copy(), max_new=8))
    expect = {r.rid: r.out for r in ref.run()}
    assert resumed == expect


def test_offload_pipeline_report_populated():
    cfg = _cfg()
    eng = OffloadedServingEngine(cfg, b_max=2, max_len=64, placement="host")
    _serve(eng, _prompts(cfg, 2), max_new=3)
    rep = eng.pipeline_report()
    assert rep["span_s"] > 0
    assert rep["per_kind"]["compute"]["count"] > 0
    assert rep["per_kind"]["weight_load"]["count"] > 0
    assert rep["per_kind"]["weight_load"]["bytes"] > 0
    assert rep["per_kind"]["kv_load"]["count"] > 0
    assert rep["per_kind"]["kv_save"]["count"] > 0
    assert 0 < rep["compute_util"] <= 1
    assert abs(rep["compute_util"] + rep["bubble_frac"] - 1.0) < 1e-9


# ---------------------------------------------------------------------------
# Pipeline-parallel staging (--stages): per-stage tiered stores + pools
# ---------------------------------------------------------------------------


def _pp_engine(cfg, **kw):
    kw.setdefault("b_max", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("pipeline", "performance")
    kw.setdefault("stages", 2)
    return _offload_spec(cfg, **kw)


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("quant,kv_mode",
                         [(None, "fp32"), ("int4", "fp32"),
                          (None, "int4"), ("int4", "int4")])
def test_pp_two_stage_decode_parity(request, quant, kv_mode, depth):
    """Acceptance criterion: a 2-stage engine (each stage its own tiered
    weight/KV store, transfer pool and preload window, activations
    microbatched between them) decodes token-identical to the resident
    reference across the full quant x kv_mode x depth matrix — staging
    is a scheduling change only."""
    cfg = _cfg()
    if kv_mode == "int4":
        from repro.serving import KVRoundtripServingEngine
        ref = KVRoundtripServingEngine(cfg, b_max=2, max_len=64)
    else:
        ref = ServingEngine(cfg, b_max=2, max_len=64)
    if quant == "int4":
        ref.params = quant_roundtrip_params(cfg, ref.params)
    want = _serve(ref, _prompts(cfg))

    kw = dict(depth=depth)
    if quant:
        kw["quant"] = quant
    if kv_mode != "fp32":
        kw["kv_mode"] = kv_mode
    eng = _pp_engine(cfg, **kw)
    assert eng.n_stages == 2
    assert eng.stage_bounds == [(0, 1), (1, 2)]
    assert _serve(eng, _prompts(cfg)) == want


def test_pp_trace_carries_stage_structure():
    """The staged engine's trace is stage-tagged end to end: meta records
    the tiling, events carry both stage ids, the report grows the
    stage_bubbles bucket — and each stage streams over its OWN link
    (aggregate bandwidth is the whole point)."""
    cfg = _cfg()
    eng = _pp_engine(cfg)
    _serve(eng, _prompts(cfg, 2), max_new=3)
    assert eng.trace.meta["stages"] == 2
    assert eng.trace.meta["stage_units"] == [[0, 1], [1, 2]]
    assert {e.stage for e in eng.trace.events()} == {0, 1}
    assert set(eng.pipeline_report()["stage_bubbles"]) == {0, 1}
    s0, s1 = eng.weights.stores
    assert s0.link is not s1.link
    assert eng.kvstore.stores[0].link is s0.link
    assert eng.kvstore.stores[1].link is s1.link


def test_pp_both_stages_preload_weights():
    """Every stage primes its own window: decode steps show stage-tagged
    weight loads from BOTH stages, and the downstream stage's loads are
    issued by its own pool (no cross-stage load serialization)."""
    cfg = _cfg()
    eng = _pp_engine(cfg)
    _serve(eng, _prompts(cfg, 2), max_new=4)
    by_stage = {}
    for e in eng.trace.events():
        if e.kind == "weight_load":
            by_stage.setdefault(e.stage, []).append(e)
    assert set(by_stage) == {0, 1}
    # the fake-free engine names units globally: stage 1 loads w[1]
    assert {e.name for e in by_stage[1]} == {"w[1]"}
    assert len(by_stage[1]) > 1


def test_pp_spill_restore_resume_parity():
    """Preempt/resume under staging: each stage's KV store spills into
    its own namespace (ns/s<stage>), and the interrupted stream still
    equals the uninterrupted one."""
    cfg = _cfg()
    prompt = _prompts(cfg, 1)[0]
    ref = ServingEngine(cfg, b_max=2, max_len=64)
    ref.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    uninterrupted = ref.run()[0].out

    eng = _pp_engine(cfg)
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    eng._admit()
    done = []
    for _ in range(3):
        eng._decode_step(done)
    assert not done
    eng.preempt_slot(0)
    done = eng.run()
    eng.shutdown()
    assert done[0].out == uninterrupted
    assert eng.stats["slot_restores"] == 1


def test_pp_stage_count_clamps_to_units():
    """stages > n_units resolves to one unit per stage, not an error —
    the scaled test config has two schedulable units."""
    cfg = _cfg()
    eng = _pp_engine(cfg, stages=8)
    assert eng.n_stages == 2
    assert eng.plan.stages == 2
    assert "clamped" in eng.plan.provenance["stages"]
    _serve(eng, _prompts(cfg, 1), max_new=2)


_PP_DEVICES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro.configs import get_config, scaled_down
from repro.serving import EngineSpec, Request, create_engine

devs = jax.devices()
assert len(devs) == 4, devs
cfg = scaled_down(get_config("tinyllama-1.1b"), num_layers=8, num_periods=8)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
           for i in range(3)]

def serve(stages):
    eng = create_engine(EngineSpec(
        arch="tinyllama-1.1b", cfg=cfg, offload=True, placement="host",
        pipeline="performance", b_max=2, max_len=64,
        stages=stages).resolve())
    if stages > 1:
        held = lambda tree: {d for a in jax.tree.leaves(tree)
                             for d in a.devices()}
        assert held(eng.resident) == {devs[0]}, held(eng.resident)
        for s, (lo, hi) in enumerate(eng.stage_bounds):
            assert held(eng.weights.stores[s].load(eng.units[lo].key)) \
                == {devs[s]}, s
            assert held(eng.kvstore.stores[s].load(0)) == {devs[s]}, s
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=4))
    out = {r.rid: r.out for r in eng.run()}
    eng.shutdown()
    return out

assert serve(4) == serve(1)
print("STAGED_DEVICES_OK")
"""


def test_pp_stages_live_on_their_own_devices():
    """With one device per stage (4 virtual CPU devices, in a child
    process: the device count is fixed at JAX start-up), stage s's
    weight and KV loads land on device s, the resident embedding and
    final norm on device 0 where the head runs, and the tokens equal the
    single-stage engine's."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _PP_DEVICES_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "STAGED_DEVICES_OK" in r.stdout, \
        r.stderr[-3000:]
