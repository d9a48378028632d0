#!/usr/bin/env python3
"""Smoke run of the served path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the staged path, one stage per chip

One chip: tinyllama-1.1b at its published widths, with random weights made
from ``--seed``, is resolved through ``EngineSpec.resolve()`` and built with
``create_engine(plan)`` twice in this one process: as the offloaded engine
(weights on the host tier, performance pipeline, warm window and depth on
auto) and as the resident ``ServingEngine``.  Both serve the same eight
requests (prompts of 8-64 tokens, 8-16 new tokens each) to completion, the
offloaded engine twice: its two runs must give identical tokens.  Between
the engines, each request's first decode step must give the same logits
within ``LOGIT_RTOL`` at the chip's default matmul precision, and with
f32 matmuls every greedy token must be identical.

Four chips (``--chips 4``): only the pipeline-parallel offloaded engine with
one stage per chip (``stages=4``) and the single-stage engine it is compared
with run.  Tokens must be identical, and each stage's weights, KV and the
first stage's resident tensors must live on that stage's chip.

Every timing printed is a smoke timing of one run on a cold process, not a
benchmark number.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits non-zero without it.  Without a TPU the script refuses to
run.  It starts no child process.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "tinyllama-1.1b"
PROMPT_LENS = (8, 16, 32, 64)
N_REQUESTS = 8


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


class CompileLog:
    """Counts XLA backend compiles and their seconds (JAX monitoring
    events), so each phase can report what it compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.secs = 0.0

    def __call__(self, name, secs, **_):
        if name == self.EVENT:
            self.count += 1
            self.secs += secs

    def mark(self):
        return (self.count, self.secs)

    def since(self, mark) -> str:
        n, s = mark
        return f"compiles={self.count - n} compile_s={self.secs - s:.1f}"


def log(msg: str):
    print(msg, flush=True)


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def request_specs(vocab: int, seed: int):
    """(prompt, max_new) for each request, made from ``seed``."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, PROMPT_LENS[i % len(PROMPT_LENS)])
             .astype(np.int32), 8 if i < N_REQUESTS // 2 else 16)
            for i in range(N_REQUESTS)]


def serve(eng, specs):
    """Serve ``specs`` to completion; returns ({rid: tokens}, seconds)."""
    from repro.serving import Request
    t0 = time.perf_counter()
    for rid, (prompt, max_new) in enumerate(specs):
        eng.submit(Request(rid=rid, prompt=prompt, max_new=max_new))
    done = eng.run()
    secs = time.perf_counter() - t0
    outs = {r.rid: [int(t) for t in r.out] for r in done}
    check(sorted(outs) == list(range(len(specs))),
          f"completed {sorted(outs)} of {len(specs)} requests")
    for rid, (_, max_new) in enumerate(specs):
        check(len(outs[rid]) == max_new,
              f"request {rid}: {len(outs[rid])} of {max_new} tokens")
    return outs, secs


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    limit = stats.get("bytes_limit")
    if peak is None:
        return "peak_hbm=not reported"
    return f"peak_hbm_bytes={peak} bytes_limit={limit}"


def describe_plan(tag: str, plan):
    log(f"[{tag}] plan: {plan.summary()}")
    for key in ("engine", "placement", "warm", "depth", "stages",
                "stage_plan"):
        if key in plan.provenance:
            log(f"[{tag}]   {key}: {plan.provenance[key]}")
    log(f"[{tag}]   device_budget_bytes={plan.device_budget}")


def build(tag: str, spec, comp: CompileLog):
    from repro.serving.spec import create_engine
    plan = spec.resolve()
    describe_plan(tag, plan)
    mark = comp.mark()
    t0 = time.perf_counter()
    eng = create_engine(plan)
    log(f"[{tag}] built in {time.perf_counter() - t0:.1f}s "
        f"({comp.since(mark)}; smoke timing)")
    return eng


def run_phase(tag: str, eng, specs, comp: CompileLog):
    mark = comp.mark()
    outs, secs = serve(eng, specs)
    n_tok = sum(len(t) for t in outs.values())
    log(f"[{tag}] completed={len(outs)} tokens={n_tok} seconds={secs:.1f} "
        f"({comp.since(mark)}; smoke timing, not a benchmark)")
    return outs


def mismatches(a, b):
    """[(rid, first differing index)] between two {rid: tokens} maps."""
    out = []
    for rid in sorted(a):
        diff = [i for i, (x, y) in enumerate(zip(a[rid], b[rid])) if x != y]
        if diff or len(a[rid]) != len(b[rid]):
            out.append((rid, diff[0] if diff else min(len(a[rid]),
                                                      len(b[rid]))))
    return out


def report_pipeline(tag: str, eng):
    rep = eng.pipeline_report()
    busy = {k: round(v["busy_s"], 3) for k, v in rep["per_kind"].items()}
    log(f"[{tag}] pipeline depth={eng.sched.depth} "
        f"compute_util={rep['compute_util']:.3f} "
        f"bubble_frac={rep['bubble_frac']:.3f} busy_s={busy} "
        f"(host-clock trace spans; smoke timing)")


def engine_spec(seed: int, **fields):
    from repro.serving.spec import EngineSpec
    return EngineSpec(arch=ARCH, seed=seed, **fields)


def log_requests(specs):
    log(f"requests: {len(specs)} prompts of "
        f"{[len(p) for p, _ in specs]} tokens, max_new "
        f"{[m for _, m in specs]}")


def one_chip(args, dev, comp: CompileLog):
    """Offloaded vs resident engine on one chip.  At the chip's default
    matmul precision (f32 operands in bf16 passes) the two engines' programs
    round differently, so their tokens may part at a near-tie: there the
    first decode step's logits must agree within ``LOGIT_RTOL``.  With f32
    matmuls every token must be identical."""
    import jax
    off_spec = engine_spec(args.seed, offload=True, placement="host",
                           pipeline="performance")
    specs = request_specs(off_spec.model_config().vocab_size, args.seed)
    log_requests(specs)

    off = build("offloaded", off_spec, comp)
    res = None
    try:
        first = run_phase("offloaded run 1", off, specs, comp)
        again = run_phase("offloaded run 2", off, specs, comp)
        report_pipeline("offloaded", off)
        log(f"[offloaded] {peak_hbm(dev)} (process peak so far)")
        bad = mismatches(first, again)
        log(f"offloaded run 1 vs run 2 tokens match: {not bad}")
        check(not bad, f"offloaded runs disagree at (rid, index) {bad}")

        res = build("resident", engine_spec(args.seed, offload=False), comp)
        ref = run_phase("resident", res, specs, comp)
        log(f"[resident] {peak_hbm(dev)} (process peak so far)")
        bad = mismatches(first, ref)
        log(f"offloaded vs resident tokens bit-identical at the default "
            f"matmul precision: {not bad}"
            + (f" (first differing (rid, index): {bad})" if bad else ""))
        check_first_step_logits(off, res, specs)

        jax.config.update("jax_default_matmul_precision", "float32")
        a = run_phase("offloaded, f32 matmuls", off, specs, comp)
        b = run_phase("resident, f32 matmuls", res, specs, comp)
        bad = mismatches(a, b)
        log(f"offloaded vs resident tokens bit-identical with f32 matmuls: "
            f"{not bad}"
            + (f" (first differing (rid, index): {bad})" if bad else ""))
        check(not bad, f"with f32 matmuls the engines' tokens differ at "
                       f"(rid, index) {bad}")
    finally:
        jax.config.update("jax_default_matmul_precision", None)
        off.shutdown()
        if res is not None:
            res.shutdown()


# first-decode-step logits of the two engines agree within this share of
# the largest |logit|: five bf16 roundings (2**-8 each)
LOGIT_RTOL = 0.02


def check_first_step_logits(off, res, specs):
    """Each request's first decode step through each engine's own
    programs (the resident whole-stack prefill and decode, the offloaded
    per-layer prefill and decode over its streamed weights), both fed the
    resident engine's first token and ending in one logits head."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import layers as L
    from repro.models import transformer as T
    cfg = off.cfg

    @jax.jit
    def head(emb, fn, x):
        x = L.rms_norm(x[:, -1], fn["scale"], cfg.norm_eps)
        return (x @ L._w_out(emb, cfg))[:, :cfg.vocab_size]

    @jax.jit
    def resident_decode(params, tok, pos, caches):
        # transformer.decode_step with the logits in place of the argmax
        ctx = L.Ctx(cfg=cfg, dist=res.dist, mode="decode",
                    angles=T._angles(cfg, pos[:, None]), pos=pos,
                    batch_size=1)
        x = T._inputs_to_x(params, cfg, ctx, {"token": tok})
        x, _, _ = T._run_stack(params, x, ctx, caches, cfg, cfg.pattern,
                               cfg.remainder, remat=False)
        return head(params["embed"], params["final_norm"], x)

    worst = 0.0
    for rid, (prompt, _) in enumerate(specs):
        tok = jnp.asarray(prompt)[None]
        pos = jnp.asarray([len(prompt)], jnp.int32)
        nt, caches = res._prefill(res.params, {"tokens": tok}, res.max_len)
        a = np.asarray(resident_decode(res.params, nt[:, None], pos,
                                       caches))[0]
        x = off._embed(off.resident["embed"], tok, "prefill")
        angles = T._angles(cfg, jnp.arange(len(prompt)))
        unit_caches = []
        for u in off.units:
            x, c = off._prefill_fns[(u.group, u.q)](
                off.weights.load(u.key), x, angles)
            unit_caches.append(c)
        x = off._embed(off.resident["embed"], nt[:, None], "decode")
        angles = T._angles(cfg, pos[:, None])
        for u, c in zip(off.units, unit_caches):
            x, _ = off._decode_fns[(u.group, u.q)](
                off.weights.load(u.key), x, c, pos, angles)
        b = np.asarray(head(off.resident["embed"],
                            off.resident["final_norm"], x))[0]
        diff, scale = float(np.abs(a - b).max()), float(np.abs(a).max())
        top2 = np.sort(a)[-2:]
        log(f"[first decode step] rid {rid}: max|logit diff|={diff:.3g} "
            f"of logit scale {scale:.3g}; argmax resident={int(a.argmax())} "
            f"offloaded={int(b.argmax())}; resident top-2 margin="
            f"{top2[1] - top2[0]:.3g}")
        worst = max(worst, diff / scale)
        check(diff <= LOGIT_RTOL * scale,
              f"rid {rid}: first-decode-step logits differ by {diff:.3g}, "
              f"over {LOGIT_RTOL} of the logit scale {scale:.3g}")
    log(f"first-decode-step logits agree within {LOGIT_RTOL} of the logit "
        f"scale: True (worst {worst:.3g})")


def _devices_of(tree):
    import jax
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def four_chips(args, devs, comp: CompileLog):
    outs = {}
    for stages in (1, 4):
        tag = f"stages={stages}"
        spec = engine_spec(args.seed, offload=True, placement="host",
                           pipeline="performance", stages=stages)
        specs = request_specs(spec.model_config().vocab_size, args.seed)
        if stages == 1:
            log_requests(specs)
        eng = build(tag, spec, comp)
        try:
            check(eng.n_stages == stages,
                  f"plan resolved to {eng.n_stages} stages, not {stages}")
            if stages > 1:
                check_stage_placement(eng, devs)
            outs[stages] = run_phase(tag, eng, specs, comp)
            report_pipeline(tag, eng)
        finally:
            eng.shutdown()
        del eng
        gc.collect()
    bad = mismatches(outs[1], outs[4])
    log(f"stages=4 vs stages=1 tokens match: {not bad}"
        + (f" (first differing (rid, index): {bad})" if bad else ""))
    check(not bad, f"staged tokens differ at {bad}")
    log(f"[stages=4] {peak_hbm(devs[0])} on chip 0 (process peak)")


def check_stage_placement(eng, devs):
    """Stage s's weight and KV loads land on chip s; the resident
    embedding and final norm live on chip 0, where the head runs."""
    check(_devices_of(eng.resident) == {devs[0]},
          f"resident tensors on {_devices_of(eng.resident)}")
    for s, (lo, hi) in enumerate(eng.stage_bounds):
        w = eng.weights.stores[s].load(eng.units[lo].key)
        kv = eng.kvstore.stores[s].load(0)
        got_w, got_kv = _devices_of(w), _devices_of(kv)
        log(f"[stages=4] stage {s} units [{lo}, {hi}): weights on "
            f"{sorted(d.id for d in got_w)}, kv on "
            f"{sorted(d.id for d in got_kv)}")
        check(got_w == {devs[s]} and got_kv == {devs[s]},
              f"stage {s} buffers on {got_w} / {got_kv}, not {devs[s]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: offloaded vs resident engine on one chip; "
                         "4: the staged engine, one stage per chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are missing ({e})",
              file=sys.stderr)
        return 2
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache: "
        f"{enable_compile_cache() or 'off'}")
    comp = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(comp)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args, devs, comp)
        else:
            one_chip(args, devs[0], comp)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f}s, {comp.count} compiles, "
        f"{comp.secs:.1f}s compiling (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
