"""Benchmark harness: one function per PIPO table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  All benches run on CPU with
reduced model sizes; the *comparisons* (pipelined vs sequential, suite vs
naive, INT4 fused vs dequant-first) mirror the paper's figures and are
validated directionally against its claims in EXPERIMENTS.md.

  fig5_throughput    — tokens/s by weight placement x batch (Fig. 5)
  fig6_blocksize     — transfer bandwidth vs block size (Fig. 6 / Appx A)
  fig7_transfer      — suite vs naive disk->device bandwidth (Fig. 7)
  fig8_utilization   — compute-busy fraction, PIPO vs sequential (Fig. 8)
  fig9_ablation      — +pipeline, +suite, +int4-kernel cumulative (Fig. 9)
  table3_latency     — TTFT + decode latency vs context (Table 3)
  table6_memory      — memory footprint by placement (Table 6)
  fig12_moe          — MoE offloading with expert-load overlap (Fig. 12)
  serving_offload    — continuous-batching decode: seq/cold/warm/warm+INT4
  serving_offload_depth — warm preload-depth sweep {1,2,3} x {fp32,int4}
  serving_kv_quant   — KV streaming sweep: kv_mode {fp32,int4} x depth {1,2}
  pipelined_kv_quant — batch-generation KV streaming: kv_mode on PipelinedLM
  serving_spec_decode — k-token draft-then-verify vs plain decode (ours)
  replay_validate    — trace-replay predicted vs measured step time (ours)
  kernel_int4        — fused INT4 kernel vs dequant-then-matmul (§3.4)
  roofline           — aggregate dry-run roofline table (ours)
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

ROWS: list[str] = []

# --steps N overrides the KV-streaming scenarios' decode length (CI
# smoke runs `serving_kv_quant --steps 2` and `pipelined_kv_quant
# --steps 2` so they can't rot without paying the full sweep); None =
# the scenario's default
STEPS: "int | None" = None

# --seed plumbs into workload generation (arrival traces, prompts) and is
# stamped into every serving_traffic row so a figure names the workload
# that produced it
SEED: int = 0


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _bench_cfg(layers=4, d=256, ff=1024, vocab=2048):
    from repro.configs.base import ATTN, DENSE, LayerSpec, ModelConfig
    return ModelConfig(name="bench", num_layers=layers, d_model=d,
                       num_heads=8, num_kv_heads=4, head_dim=d // 8, d_ff=ff,
                       vocab_size=vocab, pattern=(LayerSpec(ATTN, DENSE),))


def _run_engine(placement, pipeline, batch=4, gen=8, prompt_len=32,
                quant=None, **kw):
    from repro.serving.spec import EngineSpec, build_lm
    cfg = _bench_cfg()
    # disk placement: evict page cache per load — the paper's NVMe regime
    # (page-cached "disk" reads are memcpys and hide the pipeline's win)
    kw.setdefault("cold_reads", placement == "disk")
    spec = EngineSpec(
        arch=cfg.name, cfg=cfg, offload=True, placement=placement,
        pipeline=pipeline, quant=quant, b_max=batch,
        max_len=prompt_len + gen + 2, depth=1,
        disk_root=f"/tmp/pipo_bench_{placement}_{pipeline}_{quant}", **kw)
    lm = build_lm(spec)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (batch, prompt_len)).astype(
        np.int32)
    toks, stats = lm.generate(prompt, gen_len=gen)
    return stats


def fig5_throughput():
    """Paper Fig. 5: throughput by weight placement and batch size."""
    for placement, tag in (("device", "G"), ("host", "C"), ("disk", "D")):
        for batch in (4, 8):
            seq = _run_engine(placement, "sequential", batch=batch)
            pipo = _run_engine(placement, "performance", batch=batch)
            speedup = pipo["throughput_tok_s"] / max(1e-9,
                                                     seq["throughput_tok_s"])
            emit(f"fig5_{tag}-{batch}_seq",
                 1e6 / max(1e-9, seq["throughput_tok_s"]),
                 f"tok_s={seq['throughput_tok_s']:.2f}")
            emit(f"fig5_{tag}-{batch}_pipo",
                 1e6 / max(1e-9, pipo["throughput_tok_s"]),
                 f"tok_s={pipo['throughput_tok_s']:.2f};speedup={speedup:.2f}x")


def fig6_blocksize():
    """Appendix A: transfer bandwidth vs block size."""
    from repro.core.offload import DiskStore
    from repro.core.transfer import sweep_block_size
    disk = DiskStore("/tmp/pipo_bench_blk")
    arr = np.zeros((64 << 20,), np.uint8)  # 64MB
    disk.put("w", arr)
    for bs, bw in sweep_block_size(disk, "w",
                                   sizes=[1 << 20, 4 << 20, 8 << 20,
                                          32 << 20, 64 << 20]):
        emit(f"fig6_block_{bs >> 20}MB", 64 * 2**20 / bw * 1e6,
             f"GBps={bw / 1e9:.2f}")


def fig7_transfer():
    """Fig. 7: suite vs naive disk->device transfer speed."""
    from repro.core.offload import DiskStore
    from repro.core.transfer import (blockwise_disk_to_host, host_to_device,
                                     naive_disk_to_host,
                                     pipelined_disk_to_device)
    disk = DiskStore("/tmp/pipo_bench_tx")
    for mb in (4, 16, 64):
        arr = np.random.default_rng(0).integers(
            0, 255, (mb << 20,)).astype(np.uint8)
        disk.put(f"w{mb}", arr)
        reps = 3

        def t_naive():
            disk.drop_cache(f"w{mb}")   # cold reads = the paper's regime
            t0 = time.perf_counter()
            host_to_device(naive_disk_to_host(disk, f"w{mb}"))
            return time.perf_counter() - t0

        def t_suite():
            disk.drop_cache(f"w{mb}")
            t0 = time.perf_counter()
            pipelined_disk_to_device(disk, f"w{mb}", block_bytes=8 << 20)
            return time.perf_counter() - t0

        tn = min(t_naive() for _ in range(reps))
        ts = min(t_suite() for _ in range(reps))
        emit(f"fig7_naive_{mb}MB", tn * 1e6,
             f"GBps={mb / 1024 / tn:.2f}")
        emit(f"fig7_suite_{mb}MB", ts * 1e6,
             f"GBps={mb / 1024 / ts:.2f};gain={tn / ts:.2f}x")


def fig8_utilization():
    """Fig. 8: compute-busy fraction (the GPU-utilization analogue)."""
    seq = _run_engine("disk", "sequential", gen=6)
    pipo = _run_engine("disk", "performance", gen=6)
    emit("fig8_util_sequential", seq["total_s"] * 1e6,
         f"busy={seq['compute_busy']:.2f}")
    emit("fig8_util_pipo", pipo["total_s"] * 1e6,
         f"busy={pipo['compute_busy']:.2f}")


def fig9_ablation():
    """Fig. 9: cumulative component gains over the sequential baseline."""
    base = _run_engine("disk", "sequential", quant="int4", fused_int4=False)
    t0 = base["throughput_tok_s"]
    pipe = _run_engine("disk", "performance", quant="int4", fused_int4=False,
                       block_bytes=1 << 30, n_io_threads=1)
    suite = _run_engine("disk", "performance", quant="int4",
                        fused_int4=False)
    kernel = _run_engine("disk", "performance", quant="int4",
                         fused_int4=True)
    emit("fig9_flexgen_like", 1e6 / max(1e-9, t0), "rel=1.00")
    for name, s in (("pipo_base", pipe), ("plus_suite", suite),
                    ("plus_kernel", kernel)):
        emit(f"fig9_{name}", 1e6 / max(1e-9, s["throughput_tok_s"]),
             f"rel={s['throughput_tok_s'] / max(1e-9, t0):.2f}")


def table3_latency():
    """Table 3: TTFT and per-token decode latency vs context length."""
    for ctx in (64, 128, 256):
        seq = _run_engine("disk", "sequential", batch=1, prompt_len=ctx,
                          gen=4)
        pipo = _run_engine("disk", "performance", batch=1, prompt_len=ctx,
                           gen=4)
        dec_seq = (seq["total_s"] - seq["ttft_s"]) / 3
        dec_pipo = (pipo["total_s"] - pipo["ttft_s"]) / 3
        emit(f"table3_ctx{ctx}_seq", seq["ttft_s"] * 1e6,
             f"ttft_s={seq['ttft_s']:.3f};decode_s={dec_seq:.3f}")
        emit(f"table3_ctx{ctx}_pipo", pipo["ttft_s"] * 1e6,
             f"ttft_s={pipo['ttft_s']:.3f};decode_s={dec_pipo:.3f}")


def table6_memory():
    """Table 6: device/host peak memory by placement."""
    for placement in ("device", "host", "disk"):
        s = _run_engine(placement, "performance", gen=4)
        emit(f"table6_{placement}", s["total_s"] * 1e6,
             f"dev_gb={s['device_peak_gb']:.3f};host_gb={s['host_peak_gb']:.3f};"
             f"tok_s={s['throughput_tok_s']:.2f}")


def fig12_moe():
    """Fig. 12 / Appx C.4: MoE offloading with expert-load overlap."""
    from repro.configs.base import ATTN, MOE, LayerSpec, ModelConfig, MoEConfig
    from repro.serving.spec import EngineSpec, build_lm
    cfg = ModelConfig(name="bench-moe", num_layers=3, d_model=256,
                      num_heads=8, num_kv_heads=4, head_dim=32, d_ff=512,
                      vocab_size=2048, pattern=(LayerSpec(ATTN, MOE),),
                      moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=512,
                                    num_shared=1, shared_d_ff=512))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    for mode in ("sequential", "performance"):
        lm = build_lm(EngineSpec(
            arch=cfg.name, cfg=cfg, offload=True, placement="disk",
            pipeline=mode, b_max=2, max_len=32, depth=1,
            disk_root=f"/tmp/pipo_bench_moe_{mode}"))
        toks, s = lm.generate(prompt, gen_len=6)
        emit(f"fig12_moe_{mode}", 1e6 / max(1e-9, s["throughput_tok_s"]),
             f"tok_s={s['throughput_tok_s']:.2f};busy={s['compute_busy']:.2f}")


def serving_offload():
    """Serving through the PIPO pipeline (tentpole scenario): continuous-
    batching decode under the deterministic ``sim_bw`` link floor,
    comparing four configurations on the same model:

      sequential  — FlexGen-like full serialization (baseline)
      cold        — performance pipeline, scheduler drained per decode
                    step (the PR-1 behavior: every step pays a cold w[0])
      warm        — performance + cross-step preload (step t+1's first
                    weight/KV loads submitted during step t's tail)
      warm_int4   — warm + INT4 weight streaming (~1/4 the bytes over
                    the same link; dequant overlapped on a pool thread)

    sim_bw rationale: on this CPU-only container transfers are memcpys
    whose speed swings with CPU contention and page-cache state, which
    would make the overlap gap pure noise.  The floor sleeps out the
    remainder like a DMA engine (GIL released), so sequential pays
    (weights + KV + compute) per layer while the pipeline hides the link
    time — the paper's transfer-bound serving regime, deterministic run
    to run.  The shape (d=512, ff=2048, b=16) keeps the link
    weight-dominated — the PIPO weight-offload regime, and the one where
    INT4's byte reduction shows (KV streams FP32 either way, so a
    KV-dominated link would mask it)."""
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    # depth pinned to 1 (the paper's two-resident-layer invariant) so rows
    # stay comparable across PRs; serving_offload_depth sweeps depth.
    variants = (
        ("sequential", dict(pipeline="sequential")),
        ("cold", dict(pipeline="performance", warm=False, depth=1)),
        ("warm", dict(pipeline="performance", warm=True, depth=1)),
        # fused_int4 pinned True for row continuity: the §3.5 auto rule
        # would disable the fused kernel at this b_max=16 shape
        ("warm_int4", dict(pipeline="performance", warm=True, depth=1,
                           quant="int4", fused_int4=True)),
    )
    results = {}
    for name, kw in variants:
        eng = _serving_engine(cfg, b_max=16, max_len=96, placement="host",
                              sim_bw=0.3e9, **kw)
        tok_s, step_s, rep, _ = _serve_steady_state(eng)
        results[name] = (tok_s, step_s, rep)
        emit(f"serving_offload_{name}", step_s * 1e6,
             f"decode_tok_s={tok_s:.2f};"
             f"step_ms={step_s * 1e3:.1f};"
             f"util={rep['compute_util']:.2f};"
             f"bubble={rep['bubble_frac']:.2f}")
    emit("serving_offload_speedup", 0.0,
         f"perf_vs_seq={results['warm'][0] / max(1e-9, results['sequential'][0]):.2f}x;"
         f"warm_vs_cold={results['warm'][0] / max(1e-9, results['cold'][0]):.2f}x;"
         f"int4_vs_fp32={results['warm_int4'][0] / max(1e-9, results['warm'][0]):.2f}x;"
         f"warm_step_ms={results['warm'][1] * 1e3:.1f};"
         f"cold_step_ms={results['cold'][1] * 1e3:.1f}")


def _serving_engine(cfg, **kw):
    """Serving engines are built through the one construction path:
    EngineSpec -> resolve -> create_engine (the spec carries the ad-hoc
    bench config as its cfg override)."""
    from repro.serving.spec import EngineSpec, create_engine
    return create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                    **kw))


def _serve_steady_state(eng, prompt_len=32, max_new=12):
    """Shared serving-offload measurement: fill all of the engine's slots,
    one untimed jit-warm decode step, then time steady-state decode to
    drain.  Returns (decode tok/s, s/step, pipeline report — empty for
    the resident engine, which has no pipeline, and (i0, i1): the global
    scheduler-iteration window the timing covered, so the timed steps
    can be sliced out of the engine's trace for ``core.replay``
    predicted-vs-measured validation; (None, None) when the engine has
    no scheduler)."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    for i in range(eng.b_max):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, eng.cfg.vocab_size, (prompt_len,)).astype(np.int32),
            max_new=max_new))
    eng._admit()                      # prefill all slots
    done = []
    eng._decode_step(done)           # warm the jit caches untimed
    i0 = eng.sched._iter0 if hasattr(eng, "sched") else None
    t0 = time.perf_counter()
    n0 = eng.stats["tokens_out"]
    s0 = eng.stats["decode_steps"]
    while any(s is not None for s in eng.slots):
        eng._decode_step(done)
    dt = time.perf_counter() - t0
    i1 = eng.sched._iter0 if hasattr(eng, "sched") else None
    ntok = eng.stats["tokens_out"] - n0
    nstep = eng.stats["decode_steps"] - s0
    rep = eng.pipeline_report() if hasattr(eng, "pipeline_report") else {}
    eng.shutdown()
    return ntok / dt, dt / max(1, nstep), rep, (i0, i1)


def _serve_ramping(eng, prompt_len=24, max_new=24, wave=2,
                   steps_per_wave=4):
    """Ramping-load measurement for the adaptive-depth sweep: start with
    ``wave`` requests and admit ``wave`` more every ``steps_per_wave``
    decode steps until all slots have been offered work, then drain.
    Returns (tok/s, s/step, depth_min, depth_max, resizes) — the depth
    fields track ``stats['preload_depth']`` across the ramp."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    rid = 0

    def submit(n):
        nonlocal rid
        for _ in range(n):
            eng.submit(Request(rid=rid, prompt=rng.integers(
                0, eng.cfg.vocab_size, (prompt_len,)).astype(np.int32),
                max_new=max_new))
            rid += 1

    submit(wave)
    eng._admit()
    done = []
    eng._decode_step(done)            # warm the jit caches untimed
    depths = [eng.stats["preload_depth"]]
    t0 = time.perf_counter()
    n0, s0 = eng.stats["tokens_out"], eng.stats["decode_steps"]
    steps = 0
    while eng.queue or any(s is not None for s in eng.slots) \
            or rid < eng.b_max:
        if rid < eng.b_max and steps and steps % steps_per_wave == 0:
            submit(min(wave, eng.b_max - rid))
        eng._admit()
        eng._decode_step(done)
        depths.append(eng.stats["preload_depth"])
        steps += 1
    dt = time.perf_counter() - t0
    ntok = eng.stats["tokens_out"] - n0
    nstep = eng.stats["decode_steps"] - s0
    eng.shutdown()
    return (ntok / dt, dt / max(1, nstep), min(depths), max(depths),
            eng.stats["depth_resizes"])


def serving_offload_depth():
    """Preload-depth sweep on the warm serving pipeline: depth D in
    {1, 2, 3} x {fp32, int4} on the serving_offload model/link.  Depth 1
    is the paper's two-resident-layer invariant (weight loads serialized
    one ahead); deeper windows keep up to D loads in flight across the
    depth+2 transfer workers.  b=8 (vs serving_offload's 16) keeps the
    shape firmly weight-dominated so the depth signal is transfer
    scheduling, not 2-core compute contention; max_new=24 lengthens the
    steady-state window.  Expected shape of the results: fp32 (17MB/layer
    over the link) gains through d2-d3; INT4's packed bytes make the link
    cheap, so its depth curve is flat-to-negative on this container — the
    overlapped dequants contend with main-thread compute on 2 cores (on a
    real GPU the fused dequant is on-device).  The summary row carries
    the headline ratios for docs/BENCHMARKS.md."""
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    results = {}
    for quant in (None, "int4"):
        tag = "int4" if quant else "fp32"
        for depth in (1, 2, 3):
            eng = _serving_engine(
                cfg, b_max=8, max_len=96, placement="host", sim_bw=0.3e9,
                pipeline="performance", warm=True, depth=depth, quant=quant)
            tok_s, step_s, rep, _ = _serve_steady_state(eng, max_new=24)
            results[(tag, depth)] = step_s
            emit(f"serving_offload_depth_{tag}_d{depth}", step_s * 1e6,
                 f"decode_tok_s={tok_s:.2f};"
                 f"step_ms={step_s * 1e3:.1f};"
                 f"util={rep['compute_util']:.2f};"
                 f"bubble={rep['bubble_frac']:.2f}")
    emit("serving_offload_depth_summary", 0.0,
         f"fp32_d2_vs_d1={results[('fp32', 1)] / results[('fp32', 2)]:.2f}x;"
         f"fp32_d3_vs_d1={results[('fp32', 1)] / results[('fp32', 3)]:.2f}x;"
         f"int4_d2_vs_d1={results[('int4', 1)] / results[('int4', 2)]:.2f}x;"
         f"int4_d3_vs_d1={results[('int4', 1)] / results[('int4', 3)]:.2f}x")


def serving_kv_quant():
    """KV-cache streaming sweep (tiered KV store): kv_mode {fp32, int4}
    x depth {1, 2} on the sim link, weights pinned INT4 so the step is
    KV-dominated — the regime the PR-3 depth sweep exposed ("INT4 is
    KV-dominated on the sim link: quantized cache is the next byte
    win").  All arms serve the same warm continuous-batching workload
    with prompt_len=64 of the 96-position extent live, so the KV rows
    (not the packed weights) carry most of the link bytes and the
    kv_mode delta is the dominant term at depth 1.  Live-row slicing is
    on everywhere (it is the store's only load path), so the fp32 rows
    already ship live rows, and the int4 rows additionally pack them
    ~3.2x (bf16 -> nibbles + group scales).  The
    derived fields carry the mean traced DECODE KV_LOAD payload —
    prefill loads carry 0 bytes and are excluded, so the figure is the
    real per-load link cost.  Record the table in docs/BENCHMARKS.md."""
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    max_new = (STEPS + 1) if STEPS else 16
    results = {}
    for kv_mode in ("fp32", "int4"):
        for depth in (1, 2):
            eng = _serving_engine(
                cfg, b_max=8, max_len=96, placement="host", sim_bw=0.3e9,
                pipeline="performance", warm=True, depth=depth,
                quant="int4", fused_int4=True, kv_mode=kv_mode)
            slab_kb = eng.kvstore.slab_nbytes(0) / 2**10
            trace = eng.trace              # survives engine shutdown
            tok_s, step_s, rep, _ = _serve_steady_state(eng, prompt_len=64,
                                                        max_new=max_new)
            loads = [e.nbytes for e in trace.events()
                     if e.kind == "kv_load" and e.nbytes]
            kv_kb_load = sum(loads) / max(1, len(loads)) / 2**10
            results[(kv_mode, depth)] = step_s
            emit(f"serving_kv_quant_{kv_mode}_d{depth}", step_s * 1e6,
                 f"decode_tok_s={tok_s:.2f};"
                 f"step_ms={step_s * 1e3:.1f};"
                 f"kv_KB_per_load={kv_kb_load:.0f};"
                 f"slab_KB={slab_kb:.0f};"
                 f"util={rep['compute_util']:.2f};"
                 f"bubble={rep['bubble_frac']:.2f}")
    emit("serving_kv_quant_summary", 0.0,
         f"int4_vs_fp32_d1="
         f"{results[('fp32', 1)] / results[('int4', 1)]:.2f}x;"
         f"int4_vs_fp32_d2="
         f"{results[('fp32', 2)] / results[('int4', 2)]:.2f}x;"
         f"fp32_d2_vs_d1={results[('fp32', 1)] / results[('fp32', 2)]:.2f}x;"
         f"int4_d2_vs_d1={results[('int4', 1)] / results[('int4', 2)]:.2f}x")


def pipelined_kv_quant():
    """Batch-generation twin of serving_kv_quant: ``PipelinedLM``'s host
    KV cache now lives in the SAME tiered KV store serving uses, so
    kv_mode {fp32, int4} applies to batch generation too (the PR-6
    unification; before it the engine kept a bespoke fp32 host dict and
    silently ignored --kv-mode).  Depth 1 on the sim link, weights
    pinned INT4 so the decode step is KV-dominated; both arms ship only
    the live (slots, positions) extent, int4 additionally packs it ~6x
    (f32 -> nibbles + group scales) with the dequant on the transfer
    thread.  The derived fields carry the mean traced decode KV_LOAD
    payload vs the full-slab bytes the pre-PR-6 engine would have moved.
    CI smoke runs `pipelined_kv_quant --steps 2`."""
    from repro.serving.spec import EngineSpec, build_lm
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    batch, prompt_len = 8, 32
    gen = (STEPS + 1) if STEPS else 12
    results = {}
    for kv_mode in ("fp32", "int4"):
        spec = EngineSpec(
            arch=cfg.name, cfg=cfg, offload=True, placement="host",
            pipeline="performance", quant="int4", kv_mode=kv_mode,
            b_max=batch, max_len=prompt_len + gen + 2, depth=1,
            sim_bw=0.3e9, disk_root=f"/tmp/pipo_bench_pkv_{kv_mode}")
        lm = build_lm(spec)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size,
                              (batch, prompt_len)).astype(np.int32)
        toks, stats = lm.generate(prompt, gen_len=gen)
        loads = [e.nbytes for e in lm.trace.events()
                 if e.kind == "kv_load" and e.nbytes]
        kv_kb_load = sum(loads) / max(1, len(loads)) / 2**10
        slab_kb = lm.kvstore.slab_nbytes(0) / 2**10
        step_s = batch / max(1e-9, stats["decode_tok_s"])
        results[kv_mode] = step_s
        emit(f"pipelined_kv_quant_{kv_mode}_d1", step_s * 1e6,
             f"decode_tok_s={stats['decode_tok_s']:.2f};"
             f"step_ms={step_s * 1e3:.1f};"
             f"kv_KB_per_load={kv_kb_load:.0f};"
             f"slab_KB={slab_kb:.0f};"
             f"compute_busy={stats['compute_busy']:.2f}")
    emit("pipelined_kv_quant_summary", 0.0,
         f"int4_vs_fp32_d1={results['fp32'] / results['int4']:.2f}x")


def serving_spec_decode():
    """Speculative decoding through the offload pipeline: k-token
    draft-then-verify vs plain decode on the sim link, weights {fp32,
    int4}.  The verify scores all k+1 positions in ONE ragged pass, so
    a speculative step moves the same weight bytes over the link as a
    plain step but can emit up to k+1 tokens per slot — on a
    weight-dominated link decode tok/s scales with the mean acceptance
    length.  Two proposal sources bound the range: an oracle draft
    replaying the baseline's own emitted stream (acceptance = k, the
    best case) and a seeded random draft (acceptance ~ 0, the overhead
    floor).  Greedy accept/reject keeps the emitted tokens
    bit-identical to the baseline either way — draft quality moves the
    speed, never the text — and the summary row carries a live
    ``bit_exact`` check of exactly that.  CI smoke:
    `serving_spec_decode --steps 2`."""
    from repro.serving import Request
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    b, prompt_len, k = 8, 32, 3
    max_new = STEPS * (k + 1) if STEPS else 16

    class _OracleDraft:
        """Proposes the recorded baseline stream — full acceptance."""

        def __init__(self, streams):
            self.streams = streams

        def prefill_slot(self, slot, prompt):
            pass

        def propose(self, tokens, pos, kk):
            pos = np.asarray(pos).reshape(-1)
            out = np.zeros((len(pos), kk), np.int32)
            for r, st in enumerate(self.streams):
                # prefill emitted stream[0] while pos still sat at
                # prompt_len, so the next unemitted stream index is
                # pos - prompt_len + 1
                i0 = int(pos[r]) - prompt_len + 1
                for t in range(kk):
                    out[r, t] = st[i0 + t] if 0 <= i0 + t < len(st) else 0
            return out

    class _NoisyDraft:
        """Seeded random proposals — the ~zero-acceptance floor."""

        def __init__(self):
            self.rng = np.random.default_rng(7)

        def prefill_slot(self, slot, prompt):
            pass

        def propose(self, tokens, pos, kk):
            rows = len(np.asarray(pos).reshape(-1))
            return self.rng.integers(0, cfg.vocab_size,
                                     (rows, kk)).astype(np.int32)

    def run(quant, make_draft):
        eng = _serving_engine(cfg, b_max=b, max_len=96, placement="host",
                              sim_bw=0.3e9, pipeline="performance",
                              warm=True, depth=1, quant=quant,
                              fused_int4=bool(quant))
        if make_draft is not None:
            eng.attach_draft(make_draft(), k)
        rng = np.random.default_rng(0)
        for i in range(b):
            eng.submit(Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, (prompt_len,)).astype(np.int32),
                max_new=max_new))
        eng._admit()
        done = []
        eng._decode_step(done)        # untimed jit warm
        t0 = time.perf_counter()
        n0, s0 = eng.stats["tokens_out"], eng.stats["decode_steps"]
        while any(s is not None for s in eng.slots):
            eng._decode_step(done)
        dt = time.perf_counter() - t0
        ntok = eng.stats["tokens_out"] - n0
        nstep = eng.stats["decode_steps"] - s0
        accept = (eng.stats.get("spec_accepted", 0)
                  / max(1, eng.stats.get("spec_steps", 0) * b))
        out = {r.rid: [int(t) for t in r.out] for r in done}
        eng.shutdown()
        return dict(tok_s=ntok / max(1e-9, dt), step_s=dt / max(1, nstep),
                    steps=nstep, accept=accept, out=out)

    results = {}
    for quant in (None, "int4"):
        tag = "int4" if quant else "fp32"
        base = run(quant, None)
        streams = [base["out"][i] for i in range(b)]
        oracle = run(quant, lambda: _OracleDraft(streams))
        noisy = run(quant, _NoisyDraft)
        results[tag] = (base, oracle, noisy)
        for name, r in (("base", base), ("oracle", oracle),
                        ("random", noisy)):
            emit(f"serving_spec_decode_{tag}_{name}", r["step_s"] * 1e6,
                 f"decode_tok_s={r['tok_s']:.2f};"
                 f"step_ms={r['step_s'] * 1e3:.1f};"
                 f"steps={r['steps']};accept={r['accept']:.2f}")
    bit_exact = all(results[t][1]["out"] == results[t][0]["out"]
                    and results[t][2]["out"] == results[t][0]["out"]
                    for t in results)
    emit("serving_spec_decode_summary", 0.0,
         f"k={k};bit_exact={int(bit_exact)};"
         f"oracle_vs_base_fp32="
         f"{results['fp32'][1]['tok_s'] / max(1e-9, results['fp32'][0]['tok_s']):.2f}x;"
         f"oracle_vs_base_int4="
         f"{results['int4'][1]['tok_s'] / max(1e-9, results['int4'][0]['tok_s']):.2f}x;"
         f"random_vs_base_fp32="
         f"{results['fp32'][2]['tok_s'] / max(1e-9, results['fp32'][0]['tok_s']):.2f}x")


def serving_traffic():
    """Traffic subsystem: arrival traces x scheduling policies with
    TTFT/p99 accounting, in two parts.

    Part 1 — policy latency on the deterministic traffic simulator
    (``serving.workload.TrafficSim``, virtual clock, identical numbers
    on any machine): a ramp arrival trace (load building from 0.3 to
    3 req/s) through monolithic prefill vs OnlineSLO (chunk cap 16) vs
    OfflineThroughput.  Monolithic pays a dedicated weight sweep per
    admission; chunked prefill rides the decode batch's sweeps, so
    under queue buildup the chunked policies drain faster: OnlineSLO's
    p99 TTFT lands strictly below monolithic while its chunk cap keeps
    p99 TBT bounded at ~one sweep; OfflineThroughput (whole prompt
    rides one sweep) posts the best tok/s at the worst TBT tail.

    Part 2 — token parity on the REAL engines: the same seeded ramp
    trace served through the offloaded engine under each policy x
    kv_mode {fp32, int4}; chunked prefill must be BIT-IDENTICAL to
    monolithic (any chunk size — the chunk-attention + per-chunk KV
    append path is exact, asserted live in the bit_exact field), with
    wall-clock p99 TTFT reported for scale.  ``--seed`` regenerates
    both parts' workloads; the seed is stamped into every row.  CI
    smoke: `serving_traffic --steps 2`."""
    from repro.core.replay import replay_traffic
    from repro.serving.workload import (SimCosts, TrafficSim, latency_series,
                                        ramp_trace, run_trace)
    from repro.core.tasks import percentile

    # -- part 1: deterministic policy comparison ----------------------------
    sim_trace = ramp_trace(16, 0.3, 3.0, seed=SEED, prompt_len=(24, 48),
                           max_new=8)
    costs = SimCosts(sweep_s=1.0, tok_s=0.02, prefill_tok_s=0.05)
    sims = {}
    for name, sched, chunk in (("monolithic", "monolithic", 0),
                               ("online", "online", 16),
                               ("offline", "offline", 0)):
        r = TrafficSim(sim_trace, b_max=2, sched=sched, chunk=chunk,
                       costs=costs).run()
        lat = r.trace.report()["latency"]
        sims[name] = (r, lat)
        emit(f"serving_traffic_sim_{name}", lat["ttft"]["p99_s"] * 1e6,
             f"ttft_p50_s={lat['ttft']['p50_s']:.2f};"
             f"ttft_p99_s={lat['ttft']['p99_s']:.2f};"
             f"tbt_p99_s={lat['tbt']['p99_s']:.2f};"
             f"tok_s={r.tok_per_s:.2f};sweeps={r.sweeps};seed={SEED}")
    # what-if replay closes the loop: the recorded monolithic traffic
    # re-run under OnlineSLO knobs must equal the live online simulation
    what_if = replay_traffic(sims["monolithic"][0].trace,
                             sched="online", chunk=16)
    replay_ok = (what_if.trace.meta["latency"]
                 == sims["online"][0].trace.meta["latency"])
    p99 = lambda n: sims[n][1]["ttft"]["p99_s"]
    emit("serving_traffic_sim_summary", 0.0,
         f"online_vs_mono_p99="
         f"{p99('online') / max(1e-9, p99('monolithic')):.2f}x;"
         f"online_p99_below_mono={int(p99('online') < p99('monolithic'))};"
         f"offline_tok_s_best="
         f"{int(sims['offline'][0].tok_per_s >= max(sims['monolithic'][0].tok_per_s, sims['online'][0].tok_per_s))};"
         f"replay_matches_live={int(replay_ok)};seed={SEED}")

    # -- part 2: real-engine token parity under traffic ---------------------
    cfg = _bench_cfg()
    n_req = 4
    max_new = (STEPS + 1) if STEPS else 6
    eng_trace = ramp_trace(n_req, 5.0, 50.0, seed=SEED, prompt_len=(6, 12),
                           max_new=max_new, vocab=cfg.vocab_size)
    outs = {}
    for kv_mode in ("fp32", "int4"):
        for name, kw in (("monolithic", dict(sched="monolithic")),
                         ("online", dict(sched="online", prefill_chunk=3)),
                         ("offline", dict(sched="offline"))):
            eng = _serving_engine(cfg, b_max=2, max_len=64,
                                  placement="host", pipeline="performance",
                                  warm=True, depth=1, kv_mode=kv_mode, **kw)
            done = run_trace(eng, eng_trace, time_scale=1e-3)
            lat = latency_series(done)
            outs[(kv_mode, name)] = {r.rid: [int(t) for t in r.out]
                                     for r in done}
            chunks = eng.stats["prefill_chunks"]
            eng.shutdown()
            emit(f"serving_traffic_{kv_mode}_{name}",
                 percentile(lat["ttft"], 99) * 1e6,
                 f"ttft_p99_ms={percentile(lat['ttft'], 99) * 1e3:.1f};"
                 f"tbt_p99_ms={percentile(lat['tbt'], 99) * 1e3:.1f};"
                 f"reqs={len(done)};chunks={chunks};seed={SEED}")
    bit_exact = all(outs[(kv, n)] == outs[(kv, "monolithic")]
                    for kv in ("fp32", "int4")
                    for n in ("online", "offline"))
    emit("serving_traffic_summary", 0.0,
         f"bit_exact={int(bit_exact)};reqs={n_req};seed={SEED}")


def serving_adaptive_depth():
    """AdaptiveDepth vs static windows under RAMPING request load: the
    engine starts near-empty (2 requests) and admits 2 more every 4
    decode steps until all 8 slots have been offered work.  Static
    windows (d in {1,2,3}) pay the same depth throughout; the adaptive
    policy re-sizes between steps from live KV/spill pressure — deep
    while load is light, shrinking as slots fill (the ROADMAP "depth is
    static per engine" gap, measured).

    The device budget is pinned tight (depth-0 peak at the worst case +
    5 MiB of headroom) so the memory model actually binds at this bench
    scale, and quant is INT4 so the per-layer in-flight cost is
    KV-sensitive (packed weights ~1.6 MiB/layer vs a live KV slab
    growing past that) — the regime where a consumer device wants the
    window to breathe: live_depth resolves 8 -> 7 -> 5 -> 2 as the ramp
    fills.  The summary row carries the headline ratios for
    docs/BENCHMARKS.md."""
    from repro.core.memory_model import estimate
    from repro.core.offload import MemoryBudget
    from repro.serving.spec import EngineSpec, create_engine
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    est0 = estimate(cfg, batch=8, seq=56, p=4, preload=0)
    budget = MemoryBudget(
        device=max(est0.peak_prefill, est0.peak_decode) + (5 << 20))
    results = {}
    for name, kw in (("static_d1", dict(depth=1)),
                     ("static_d2", dict(depth=2)),
                     ("static_d3", dict(depth=3)),
                     ("adaptive", dict(depth_policy="adaptive"))):
        spec = EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                          placement="host", pipeline="performance",
                          warm=True, quant="int4", b_max=8, max_len=56,
                          sim_bw=0.3e9, **kw)
        eng = create_engine(spec.resolve(budget))
        tok_s, step_s, d_min, d_max, resizes = _serve_ramping(eng)
        results[name] = step_s
        emit(f"serving_adaptive_{name}", step_s * 1e6,
             f"decode_tok_s={tok_s:.2f};step_ms={step_s * 1e3:.1f};"
             f"depth={d_min}..{d_max};resizes={resizes}")
    emit("serving_adaptive_summary", 0.0,
         f"adaptive_vs_d1={results['static_d1'] / results['adaptive']:.2f}x;"
         f"adaptive_vs_d2={results['static_d2'] / results['adaptive']:.2f}x;"
         f"adaptive_vs_d3={results['static_d3'] / results['adaptive']:.2f}x")


def serving_pp():
    """Pipeline-parallel offload (--stages): the layer stack split into
    contiguous stages, each with its own tiered weight/KV store and
    transfer pool over its own sim link — so aggregate host->device
    bandwidth scales with the stage count while activations microbatch
    stage to stage.  Sweeps stages {1, 2, 4} x weights {fp32, int4} on
    the weight-dominated serving_offload shape; each row carries the
    tok/s ratio vs its single-stage arm and a bit_exact column checking
    the staged tokens against the single-stage tokens (staging must be
    a scheduling change only).  CI smoke: `serving_pp --steps 2`."""
    from repro.serving import Request
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    max_new = (STEPS + 1) if STEPS else 12

    def serve(eng):
        """_serve_steady_state, plus the emitted tokens (for bit_exact)."""
        rng = np.random.default_rng(0)
        for i in range(eng.b_max):
            eng.submit(Request(rid=i, prompt=rng.integers(
                0, eng.cfg.vocab_size, (32,)).astype(np.int32),
                max_new=max_new))
        eng._admit()
        done = []
        eng._decode_step(done)        # warm the jit caches untimed
        t0 = time.perf_counter()
        n0, s0 = eng.stats["tokens_out"], eng.stats["decode_steps"]
        while any(s is not None for s in eng.slots):
            eng._decode_step(done)
        dt = time.perf_counter() - t0
        ntok = eng.stats["tokens_out"] - n0
        nstep = eng.stats["decode_steps"] - s0
        rep = eng.pipeline_report()
        eng.shutdown()
        tokens = {r.rid: tuple(r.out) for r in done}
        return ntok / dt, dt / max(1, nstep), rep, tokens

    base = {}
    for wq in (None, "int4"):
        tag = wq or "fp32"
        for stages in (1, 2, 4):
            kw = dict(pipeline="performance", warm=True, depth=1,
                      stages=stages)
            if wq:
                kw.update(quant=wq, fused_int4=True)
            eng = _serving_engine(cfg, b_max=16, max_len=96,
                                  placement="host", sim_bw=0.3e9, **kw)
            tok_s, step_s, rep, tokens = serve(eng)
            if stages == 1:
                base[tag] = (tok_s, tokens)
            ratio = tok_s / max(1e-9, base[tag][0])
            emit(f"serving_pp_s{stages}_{tag}", step_s * 1e6,
                 f"decode_tok_s={tok_s:.2f};step_ms={step_s * 1e3:.1f};"
                 f"util={rep['compute_util']:.2f};"
                 f"vs_s1={ratio:.2f}x;"
                 f"bit_exact={int(tokens == base[tag][1])}")
            assert tokens == base[tag][1], \
                f"staged tokens diverged at stages={stages} quant={tag}"


def replay_validate():
    """Predicted-vs-measured validation of the trace-replay cost model
    (``core.replay``): each arm serves a warm continuous-batching decode
    workload on the sim link (the serving_offload / serving_kv_quant
    regimes), slices the timed steady-state iteration window out of the
    engine's trace, replays it with UNCHANGED knobs, and reports the
    replay's steady step time against the wall-clock measurement.  The
    residual error is real unmodeled time — per-step engine bookkeeping
    (sampling, numpy round-trips) outside the traced tasks, plus real
    thread-pool queueing the virtual pool idealizes — so the err_pct
    column is the honest accuracy figure for trace-driven resolve
    (strict <10%% bounds are asserted on the deterministic virtual-clock
    workloads in tests/test_replay.py, where wall-clock noise can't
    flake CI).  The depth_pick rows close the loop: the simulated-argmin
    depth from the d=1 recording vs the measured-best static depth
    across the d1/d2 arms.  CI smoke: `replay_validate --steps 2`."""
    from repro.core.replay import best_depth, replay
    cfg = _bench_cfg(layers=6, d=512, ff=2048)
    max_new = (STEPS + 1) if STEPS else 12
    arms = (
        ("offload_warm_fp32_d1", 32,
         dict(pipeline="performance", warm=True, depth=1, b_max=16)),
        ("kv_fp32_d1", 64,
         dict(pipeline="performance", warm=True, depth=1, b_max=8,
              quant="int4", fused_int4=True, kv_mode="fp32")),
        ("kv_fp32_d2", 64,
         dict(pipeline="performance", warm=True, depth=2, b_max=8,
              quant="int4", fused_int4=True, kv_mode="fp32")),
        ("kv_int4_d1", 64,
         dict(pipeline="performance", warm=True, depth=1, b_max=8,
              quant="int4", fused_int4=True, kv_mode="int4")),
        ("kv_int4_d2", 64,
         dict(pipeline="performance", warm=True, depth=2, b_max=8,
              quant="int4", fused_int4=True, kv_mode="int4")),
    )
    measured = {}
    traces = {}
    for name, prompt_len, kw in arms:
        eng = _serving_engine(cfg, max_len=96, placement="host",
                              sim_bw=0.3e9, **kw)
        trace = eng.trace              # survives engine shutdown
        tok_s, step_s, rep, (i0, i1) = _serve_steady_state(
            eng, prompt_len=prompt_len, max_new=max_new)
        res = replay(trace, start_iter=i0, stop_iter=i1)
        err = abs(res.steady_step_s - step_s) / max(1e-9, step_s)
        measured[name] = step_s
        traces[name] = (trace, i0, i1)
        emit(f"replay_validate_{name}", step_s * 1e6,
             f"measured_ms={step_s * 1e3:.1f};"
             f"predicted_ms={res.steady_step_s * 1e3:.1f};"
             f"err_pct={err * 100:.1f};"
             f"steps={i1 - i0}")
    for kv in ("fp32", "int4"):
        trace, i0, i1 = traces[f"kv_{kv}_d1"]
        picked, preds = best_depth(trace, depth_cap=2,
                                   start_iter=i0, stop_iter=i1)
        best_measured = min((1, 2), key=lambda d: measured[f"kv_{kv}_d{d}"])
        emit(f"replay_validate_depth_pick_{kv}", 0.0,
             f"picked_d={picked};measured_best_d={best_measured};"
             f"pred_d1_ms={preds[1] * 1e3:.1f};"
             f"pred_d2_ms={preds[2] * 1e3:.1f};"
             f"agree={int(picked == best_measured)}")


def kernel_int4():
    """§3.4: fused INT4 matmul vs dequantize-then-matmul."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import int4_matmul_ref
    from repro.quant.int4 import dequantize_int4, quantize_int4
    M, K, N = 8, 2048, 2048
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32) * 0.1
    packed, scale = quantize_int4(w)

    fused = jax.jit(int4_matmul_ref)              # dequant fused by XLA

    def unfused(x, packed, scale):
        wd = jax.device_put(np.asarray(dequantize_int4(packed, scale,
                                                       jnp.float32)))
        return x @ wd
    fused(x, packed, scale).block_until_ready()

    reps = 10
    t0 = time.perf_counter()
    for _ in range(reps):
        fused(x, packed, scale).block_until_ready()
    tf = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        unfused(x, packed, scale).block_until_ready()
    tu = (time.perf_counter() - t0) / reps
    emit("kernel_int4_fused", tf * 1e6, f"GFLOPs={2 * M * K * N / tf / 1e9:.1f}")
    emit("kernel_int4_unfused", tu * 1e6, f"gain={tu / tf:.2f}x")


def roofline():
    """Aggregate the dry-run roofline table (reads experiments/dryrun)."""
    d = Path("experiments/dryrun")
    if not d.exists():
        emit("roofline_missing", 0.0, "run repro.launch.dryrun first")
        return
    n = 0
    for f in sorted(d.glob("*.json")):
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            continue
        n += 1
        emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}_{r['variant']}",
             r["t_bound_s"] * 1e6,
             f"bound={r['bottleneck']};mem_gb={r['tpu_bytes_per_device']/2**30:.2f};"
             f"useful={r['flops_useful_ratio']:.2f}")
    emit("roofline_cells_ok", float(n), "")


BENCHES = [fig5_throughput, fig6_blocksize, fig7_transfer, fig8_utilization,
           fig9_ablation, table3_latency, table6_memory, fig12_moe,
           serving_offload, serving_offload_depth, serving_kv_quant,
           pipelined_kv_quant, serving_spec_decode, serving_traffic,
           serving_adaptive_depth, serving_pp, replay_validate,
           kernel_int4, roofline]


def run_spec_scenario(path: str):
    """Ad-hoc serving scenario from an EngineSpec JSON: resolve, build
    through create_engine, and measure steady-state decode — the same
    harness the named serving scenarios use."""
    from repro.serving.spec import EngineSpec, create_engine
    spec = EngineSpec.from_json(Path(path).read_text())
    plan = spec.resolve()
    eng = create_engine(plan)
    tok_s, step_s, rep, _ = _serve_steady_state(eng)
    derived = (f"decode_tok_s={tok_s:.2f};step_ms={step_s * 1e3:.1f};"
               f"engine={plan.engine};placement={plan.placement};"
               f"depth={plan.depth}")
    if rep:
        derived += (f";util={rep['compute_util']:.2f};"
                    f"bubble={rep['bubble_frac']:.2f}")
    emit(f"spec_{plan.arch}{'_scaled' if plan.scaled else ''}",
         step_s * 1e6, derived)


def main(argv=None) -> "int | None":
    import argparse
    by_name = {b.__name__: b for b in BENCHES}
    ap = argparse.ArgumentParser(
        description="PIPO benchmark harness: one function per paper "
                    "table/figure (see docs/BENCHMARKS.md for methodology "
                    "and how to read the output)")
    ap.add_argument("scenarios", nargs="*", metavar="scenario",
                    help="scenario names to run (default: all; see --list)")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    ap.add_argument("--spec-json", metavar="FILE",
                    help="run an ad-hoc serving scenario from an "
                         "EngineSpec JSON (resolve -> create_engine -> "
                         "steady-state decode), then exit")
    ap.add_argument("--steps", type=int, metavar="N",
                    help="decode steps for the KV-streaming, speculative "
                         "and replay scenarios (smoke runs: CI uses "
                         "'serving_kv_quant --steps 2', 'pipelined_kv_quant "
                         "--steps 2', 'serving_spec_decode --steps 2' and "
                         "'replay_validate --steps 2', "
                         "'serving_traffic --steps 2' and "
                         "'serving_pp --steps 2'); other scenarios "
                         "run their documented full length")
    ap.add_argument("--seed", type=int, default=0, metavar="N",
                    help="workload-generation seed (arrival traces, "
                         "prompts); stamped into every serving_traffic "
                         "row so figures name their workload")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.steps is not None and args.steps < 1:
        ap.error(f"--steps must be >= 1, got {args.steps}")
    global STEPS, SEED
    STEPS = args.steps
    SEED = args.seed
    if args.list:
        for b in BENCHES:
            doc = (b.__doc__ or "").strip().splitlines()[0]
            print(f"{b.__name__:20s} {doc}")
        return
    if args.spec_json:
        import json
        from repro.serving.spec import SpecError
        print("name,us_per_call,derived")
        try:
            run_spec_scenario(args.spec_json)
        except (SpecError, OSError, json.JSONDecodeError) as e:
            ap.error(str(e))
        return
    unknown = [n for n in args.scenarios if n not in by_name]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}; see --list")
    benches = [by_name[n] for n in args.scenarios] if args.scenarios \
        else BENCHES
    print("name,us_per_call,derived")
    failed = []
    for b in benches:
        t0 = time.perf_counter()
        try:
            b()
        except Exception as e:  # keep the harness alive per-table
            emit(f"{b.__name__}_ERROR", 0.0, repr(e)[:120])
            failed.append(b.__name__)
        print(f"# {b.__name__} done in {time.perf_counter()-t0:.1f}s",
              flush=True)
    if failed:
        # a scenario that raised fails the run, whether it was requested
        # by name or ran as part of the full sweep
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
