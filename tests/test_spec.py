"""EngineSpec/ResolvedPlan API: resolution, provenance, JSON round-trip,
CLI parity, deprecation shims, unsupported-model fallback, and the
preload/quant policy seams."""
import dataclasses
import json
import warnings

import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.core.offload import MemoryBudget
from repro.serving import (AdaptiveDepth, EngineSpec, OffloadedServingEngine,
                           Pressure, Request, ResolvedPlan, ServingEngine,
                           SpecError, StaticDepth, UnsupportedModelError,
                           build_lm, create_engine)
from repro.serving.spec import (CLI_FLAGS, NO_FLAG_FIELDS, WORKLOAD_FLAGS,
                                preload_policy_for, quant_policy_for)


def _cfg():
    return scaled_down(get_config("tinyllama-1.1b"))


def _spec(**kw):
    kw.setdefault("arch", "tinyllama-1.1b")
    kw.setdefault("scaled", True)
    return EngineSpec(**kw)


# ---------------------------------------------------------------------------
# resolution + provenance + JSON round-trip
# ---------------------------------------------------------------------------


def test_resolved_plan_json_roundtrip():
    plan = _spec(offload=True, b_max=2, max_len=64, quant="int4").resolve()
    js = json.dumps(plan.to_json())
    plan2 = ResolvedPlan.from_json(js)
    assert plan2 == plan
    assert plan2.to_json() == plan.to_json()
    # and a reconstructed plan still resolves to a real config
    assert plan2.model_config() == plan.model_config()


def test_spec_json_roundtrip():
    spec = _spec(offload=True, depth=2, sim_bw=0.5e9)
    assert EngineSpec.from_json(json.dumps(spec.to_json())) == spec


def test_plan_json_rejects_unknown_and_missing_fields():
    plan = _spec().resolve()
    d = plan.to_json()
    d["bogus"] = 1
    with pytest.raises(SpecError):
        ResolvedPlan.from_json(d)
    d = plan.to_json()
    d.pop("depth")
    with pytest.raises(SpecError):
        ResolvedPlan.from_json(d)


def test_provenance_present_for_every_auto_field():
    """Every field left on auto records a non-empty why string."""
    plan = _spec(offload=True, b_max=2, max_len=64).resolve()
    for fld in ("engine", "placement", "warm", "depth", "fused_int4",
                "block_bytes", "disk_root"):
        assert plan.provenance.get(fld), f"no provenance for {fld}"
    # explicit fields say so
    plan2 = _spec(offload=True, placement="disk", depth=2,
                  warm=False).resolve()
    assert plan2.provenance["placement"].startswith("explicit")
    assert plan2.provenance["depth"].startswith("explicit")
    assert plan2.provenance["warm"].startswith("explicit")


def test_resolution_matches_memory_model():
    """The auto depth is the serving_preload_depth the engines used to
    compute inline, and the budget the plan resolved under is recorded."""
    from repro.core.autoconfig import serving_preload_depth
    spec = _spec(offload=True, b_max=2, max_len=64)
    plan = spec.resolve()
    want = serving_preload_depth(_cfg(), b_max=2, max_len=64, spill_cap=32)
    assert plan.depth == want
    assert plan.device_budget == MemoryBudget.device
    tight = MemoryBudget(device=1 << 12, host=1 << 40)
    plan_tight = spec.resolve(tight)
    assert plan_tight.depth == 1
    assert plan_tight.device_budget == 1 << 12


def test_validation_typed_errors():
    with pytest.raises(SpecError):
        _spec(pipeline="warp").resolve()
    with pytest.raises(SpecError):
        _spec(quant="int8").resolve()
    with pytest.raises(SpecError):
        _spec(depth=0).resolve()
    with pytest.raises(SpecError):
        _spec(offload=False, quant="int4").resolve()      # old CLI error
    with pytest.raises(SpecError):
        _spec(depth_policy="adaptive", pipeline="memory").resolve()
    with pytest.raises(SpecError):
        EngineSpec(arch="no-such-arch").resolve()


# ---------------------------------------------------------------------------
# CLI parity: flag table <-> argparse <-> dataclass (the check_docs
# invariant, asserted in-tree so a plain pytest run catches drift)
# ---------------------------------------------------------------------------


def test_cli_flag_table_three_way_parity():
    from repro.launch.serve import build_parser
    parser_flags = {s for a in build_parser()._actions
                    for s in a.option_strings if s.startswith("--")}
    table_flags = [f.flag for f in CLI_FLAGS]
    table_fields = [f.field for f in CLI_FLAGS]
    spec_fields = {f.name for f in dataclasses.fields(EngineSpec)}
    # every serve flag maps to exactly one spec field, or is workload
    assert set(table_flags) <= parser_flags
    assert parser_flags - set(table_flags) - WORKLOAD_FLAGS == set()
    assert len(set(table_flags)) == len(table_flags)
    # and vice versa: every spec field has exactly one flag, or is
    # declared flag-less
    assert set(table_fields) <= spec_fields
    assert spec_fields - set(table_fields) - NO_FLAG_FIELDS == set()
    assert len(set(table_fields)) == len(table_fields)


def test_cli_flags_build_the_spec():
    from repro.launch.serve import build_parser
    args = build_parser().parse_args(
        ["--arch", "tinyllama-1.1b", "--scaled", "--offload",
         "--quant", "int4", "--preload-depth", "2", "--no-warm",
         "--b-max", "2"])
    from repro.serving.spec import spec_from_args
    spec = spec_from_args(args)
    assert spec.quant == "int4" and spec.depth == 2 and spec.warm is False
    assert spec.b_max == 2 and spec.offload is True
    assert spec.max_len == 128           # the CLI's historical default


# ---------------------------------------------------------------------------
# deprecation shims: old kwargs -> identical plans
# ---------------------------------------------------------------------------


def test_legacy_offload_kwargs_shim_identical_plan():
    from repro.serving.spec import reset_deprecation_warnings
    cfg = _cfg()
    spec = EngineSpec(arch=cfg.name, cfg=cfg, offload=True, b_max=2,
                      max_len=64, placement="host", quant="int4", depth=2,
                      fused_int4=True)
    eng = create_engine(spec)
    reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning):
        leg = OffloadedServingEngine(cfg, b_max=2, max_len=64,
                                     placement="host", quant="int4",
                                     depth=2)
    assert leg.plan == eng.plan
    assert leg.plan.to_json() == eng.plan.to_json()
    eng.shutdown()
    leg.shutdown()


def test_legacy_shim_warns_once_per_process():
    """The legacy-kwarg DeprecationWarning is deduped: a serving loop
    constructing shimmed engines warns on the FIRST construction only
    (reset_deprecation_warnings reopens it, for tests)."""
    import warnings as w
    from repro.serving.spec import reset_deprecation_warnings
    cfg = _cfg()
    reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning):
        OffloadedServingEngine(cfg, b_max=1, max_len=32,
                               placement="host").shutdown()
    with w.catch_warnings():
        w.simplefilter("error", DeprecationWarning)
        OffloadedServingEngine(cfg, b_max=1, max_len=32,
                               placement="host").shutdown()


def test_legacy_pipelined_lm_shim_identical_plan():
    from repro.core.engine import PipelinedLM
    from repro.serving.spec import reset_deprecation_warnings
    cfg = _cfg()
    reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning):
        leg = PipelinedLM(cfg, batch=2, max_len=32, placement="host")
    spec = EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                      placement="host", b_max=2, max_len=32, depth=1,
                      disk_root="/tmp/pipo_disk")
    lm = build_lm(spec)
    assert leg.plan == lm.plan
    assert leg.plan.to_json() == lm.plan.to_json()


def test_plan_construction_rejects_stray_kwargs():
    plan = _spec(offload=True, b_max=1, max_len=32).resolve()
    with pytest.raises(TypeError):
        OffloadedServingEngine(plan, b_max=4)


# ---------------------------------------------------------------------------
# unsupported models: typed error + resident fallback
# ---------------------------------------------------------------------------


def test_unsupported_model_typed_error():
    from repro.serving.spec import reset_deprecation_warnings
    whisper = scaled_down(get_config("whisper-base"))
    reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning):
        with pytest.raises(UnsupportedModelError) as ei:
            OffloadedServingEngine(whisper, b_max=1, max_len=32)
    assert ei.value.capability == "enc_dec"


@pytest.mark.parametrize("arch,cap", [("whisper-base", "enc_dec"),
                                      ("qwen2-vl-72b", "embeds_frontend")])
def test_unsupported_falls_back_to_resident_and_serves(arch, cap):
    """The satellite: enc-dec/embeds configs get a serving path again —
    resolve downgrades to the resident engine (recording the failing
    capability) and create_engine serves requests through it."""
    plan = EngineSpec(arch=arch, scaled=True, offload=True, b_max=2,
                      max_len=48).resolve()
    assert plan.engine == "resident"
    assert cap in plan.provenance["engine"]
    eng = create_engine(plan)
    assert isinstance(eng, ServingEngine)
    rng = np.random.default_rng(0)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, eng.cfg.vocab_size, (5 + i,)).astype(np.int32), max_new=4))
    done = eng.run()
    eng.shutdown()
    assert len(done) == 2 and all(len(r.out) == 4 for r in done)


def test_enc_dec_serving_is_deterministic_per_enc_embeds():
    """Whisper serving: same request -> same tokens; different encoder
    frames -> (almost surely) different continuation, i.e. the encoder
    actually participates."""
    cfg = scaled_down(get_config("whisper-base"))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    enc = rng.standard_normal(
        (cfg.encoder_seq_len, cfg.d_model)).astype(np.float32)

    def serve_one(enc_embeds):
        eng = ServingEngine(cfg, b_max=1, max_len=48)
        eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=6,
                           enc_embeds=enc_embeds))
        out = eng.run()[0].out
        eng.shutdown()
        return out

    base = serve_one(None)
    assert serve_one(None) == base            # zero-frame stub is stable
    assert serve_one(enc) != base             # frames reach the decoder


# ---------------------------------------------------------------------------
# policy seams
# ---------------------------------------------------------------------------


def test_static_policy_reproduces_prespec_engine():
    """StaticDepth(D) via the spec path matches the resident engine
    token for token (depth x quant parity matrix rides in
    tests/test_serving_offload.py; this is the spec-path spot check)."""
    cfg = _cfg()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
               for i in range(3)]

    def serve(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new=5))
        done = eng.run()
        eng.shutdown()
        return {r.rid: r.out for r in done}

    ref = serve(ServingEngine(cfg, b_max=2, max_len=64))
    eng = create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                   b_max=2, max_len=64, placement="host",
                                   depth=2))
    assert isinstance(eng.preload_policy, StaticDepth)
    assert serve(eng) == ref


def test_adaptive_policy_token_parity():
    """AdaptiveDepth is a scheduling change only: tokens still match the
    resident engine exactly while the window re-sizes."""
    cfg = _cfg()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
               for i in range(3)]

    def serve(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new=5))
        done = eng.run()
        eng.shutdown()
        return {r.rid: r.out for r in done}

    ref = serve(ServingEngine(cfg, b_max=2, max_len=64))
    eng = create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                   b_max=2, max_len=64, placement="host",
                                   depth_policy="adaptive"))
    assert isinstance(eng.preload_policy, AdaptiveDepth)
    assert serve(eng) == ref
    assert eng.stats["preload_depth"] >= 1


def test_adaptive_policy_responds_to_pressure():
    """More requests in flight / longer contexts / more retained spills
    => a monotonically non-deeper window, bottoming at 1."""
    cfg = get_config("tinyllama-1.1b")            # full size: model binds
    from repro.core.memory_model import estimate
    est0 = estimate(cfg, batch=8, seq=2048, p=4, preload=0)
    budget = MemoryBudget(
        device=max(est0.peak_prefill, est0.peak_decode) + (1 << 30))
    pol = AdaptiveDepth(cfg, b_max=8, max_len=2048, budget=budget)
    d_light = pol.depth(Pressure(active=1, max_pos=16))
    d_mid = pol.depth(Pressure(active=4, max_pos=1024))
    d_heavy = pol.depth(Pressure(active=8, max_pos=2040))
    assert d_light >= d_mid >= d_heavy >= 1
    assert d_light > d_heavy, (d_light, d_mid, d_heavy)
    # host spill saturation forces depth 1 regardless of device headroom
    small_host = MemoryBudget(device=budget.device, host=1 << 28)
    pol2 = AdaptiveDepth(cfg, b_max=8, max_len=2048, budget=small_host)
    assert pol2.depth(Pressure(active=1, max_pos=16, spills=64)) == 1


def test_preload_policy_for_uses_plan_budget():
    plan = _spec(offload=True, depth_policy="adaptive").resolve(
        MemoryBudget(device=123 << 20, host=7 << 30))
    pol = preload_policy_for(plan)
    assert isinstance(pol, AdaptiveDepth)
    assert pol.budget.device == 123 << 20 and pol.budget.host == 7 << 30


def test_build_lm_int4_kv():
    """PipelinedLM streams quantized KV through the tiered store (the
    PR-5 gap, now closed): a kv_mode='int4' host-cache plan builds, and
    the nonsensical combination — int4 KV with a device-resident cache,
    where nothing ever crosses the link — is rejected, not silently
    downgraded (plans are obeyed or refused)."""
    lm = build_lm(_spec(offload=True, b_max=1, max_len=32, kv_mode="int4"))
    assert lm.kv_mode == "int4" and lm.kvstore is not None
    with pytest.raises(SpecError, match="kv_mode"):
        build_lm(_spec(offload=True, b_max=1, max_len=32, kv_mode="int4",
                       cache_on="device"))
    # the default (auto -> fp32) builds fine
    build_lm(_spec(offload=True, b_max=1, max_len=32))


def test_quant_policy_seam():
    import numpy as np
    none = quant_policy_for(None)
    int4 = quant_policy_for("int4")
    assert none.weight_mode is None and none.kv_mode == "fp32"
    assert int4.weight_mode == "int4" and int4.kv_mode == "fp32"
    t = {"w": np.zeros((128, 64), np.float32)}
    assert none.prepare_unit(t) is t
    packed = int4.prepare_unit(t)
    assert "w#q" in packed and "w#s" in packed
    # the kv_mode seam is live: every weight mode composes with INT4 KV
    assert quant_policy_for(None, "int4").kv_mode == "int4"
    assert quant_policy_for("int4", "int4").weight_mode == "int4"
    assert quant_policy_for("int4", None).kv_mode == "fp32"   # auto


# ---------------------------------------------------------------------------
# entry points speak the plan
# ---------------------------------------------------------------------------


def test_serve_plan_json_dry_run(tmp_path, capsys):
    """launch.serve --plan-json resolves and dumps the plan without
    building an engine (the docs-CI dry-run path)."""
    from repro.launch import serve
    out = tmp_path / "plan.json"
    serve.main(["--arch", "tinyllama-1.1b", "--scaled", "--offload",
                "--quant", "int4", "--plan-json", str(out)])
    plan = ResolvedPlan.from_json(out.read_text())
    assert plan.engine == "offloaded" and plan.quant == "int4"
    assert plan.provenance["depth"]


def test_serve_spec_json_base_with_flag_override(tmp_path):
    from repro.launch.serve import build_parser
    from repro.serving.spec import spec_from_args
    f = tmp_path / "spec.json"
    f.write_text(json.dumps(_spec(offload=True, b_max=2,
                                  quant="int4").to_json()))
    args = build_parser().parse_args(["--spec-json", str(f),
                                      "--b-max", "3"])
    spec = spec_from_args(args, base=EngineSpec.from_json(f.read_text()))
    assert spec.quant == "int4"          # from the file
    assert spec.b_max == 3               # flag overrides


# ---------------------------------------------------------------------------
# pipeline-parallel staging: resolution, carve-outs, JSON round-trip
# ---------------------------------------------------------------------------


def test_stages_resolve_to_stage_plan():
    plan = _spec(offload=True, b_max=2, max_len=64, stages=2).resolve()
    assert plan.stages == 2 and plan.stage_axis == "layer"
    assert len(plan.stage_plan) == 2
    lo = 0
    for s, sp in enumerate(plan.stage_plan):
        assert sp.stage == s and sp.layer_lo == lo
        lo = sp.layer_hi
        assert sp.depth >= 1 and sp.device_budget > 0
        assert "1/2 budget split" in sp.why
    assert "stage_plan" in plan.provenance


def test_stage_plan_json_roundtrip():
    """StagePlan entries survive to_json/from_json (rehydrated from
    dicts back to the frozen dataclass)."""
    plan = _spec(offload=True, b_max=2, max_len=64, stages=2).resolve()
    plan2 = ResolvedPlan.from_json(json.dumps(plan.to_json()))
    assert plan2 == plan
    assert plan2.stage_plan == plan.stage_plan
    assert plan2.to_json() == plan.to_json()


def test_stages_default_is_single():
    plan = _spec(offload=True, b_max=2, max_len=64).resolve()
    assert plan.stages == 1 and plan.stage_plan == ()


@pytest.mark.parametrize("arch", ["whisper-base", "qwen2-vl-72b"])
def test_stages_dropped_on_resident_fallback(arch):
    """The satellite carve-out: enc-dec/embeds configs asked to stage
    still fall back to the resident engine, with a typed drop recording
    what happened to the stages request — and still serve."""
    plan = EngineSpec(arch=arch, scaled=True, offload=True, b_max=2,
                      max_len=48, stages=2).resolve()
    assert plan.engine == "resident"
    assert plan.stages == 1 and plan.stage_plan == ()
    assert "dropped (2)" in plan.provenance["stages"]
    eng = create_engine(plan)
    assert isinstance(eng, ServingEngine)
    rng = np.random.default_rng(0)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, eng.cfg.vocab_size, (5,)).astype(np.int32), max_new=3))
    done = eng.run()
    eng.shutdown()
    assert len(done) == 1 and len(done[0].out) == 3


def test_stages_dropped_under_sparse_attention():
    """Staging needs a dense global-attention stack (sliding-window
    layers read cross-stage history) — a mixtral-style config drops the
    request with provenance instead of mis-serving."""
    plan = _spec(arch="mixtral-8x7b", offload=True, b_max=2, max_len=64,
                 stages=2).resolve()
    assert plan.stages == 1
    assert "dense global-attention" in plan.provenance["stages"]


def test_stages_validation():
    with pytest.raises(ValueError, match="stages"):
        _spec(offload=True, stages=0).validate()
    with pytest.raises(ValueError, match="stage_axis"):
        _spec(offload=True, stages=2, stage_axis="tensor").validate()


class _ReportingDevice:
    """A device stand-in whose backend reports ``stats``."""

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_budget_device_capacity_from_backend():
    """The device budget is the capacity the backend reports; a backend
    that reports none (the CPU) keeps the RTX3060 default, so CPU plans
    stay as they were."""
    v5e = MemoryBudget.for_device(_ReportingDevice({"bytes_limit": 16 << 30}))
    assert v5e.device == 16 << 30
    assert v5e.host == MemoryBudget.host
    assert MemoryBudget.for_device(_ReportingDevice(None)) == MemoryBudget()
    assert MemoryBudget.for_device(_ReportingDevice({})) == MemoryBudget()
    plan = _spec(offload=True).resolve()
    assert plan.device_budget == MemoryBudget.for_device().device
    bigger = _spec(offload=True).resolve(budget=v5e)
    assert bigger.device_budget == 16 << 30


def test_compile_cache_directory(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set (JAX reads it; nothing is
    set in code); unset, the cache is the checkout's fixed .jax_cache."""
    import jax
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "untouched")
        monkeypatch.setenv(cc.ENV, "/some/dir")
        assert cc.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "untouched"
        monkeypatch.delenv(cc.ENV)
        path = cc.enable_compile_cache()
        assert path == str(cc.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert (cc.CHECKOUT_CACHE_DIR.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
