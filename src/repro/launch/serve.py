"""Serving launcher: continuous-batching engine over a registry arch.

The CLI is generated from the one flag<->field table in
``serving.spec.CLI_FLAGS`` — every engine flag maps to exactly one
``EngineSpec`` field (cross-checked three ways by tools/check_docs.py).
Flags build a spec, ``resolve()`` materializes the plan against the
memory budget, and ``create_engine(plan)`` dispatches to the resident or
offloaded engine — the same path tests and benchmarks construct through.

Resident weights (default):
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --scaled --requests 10

Offloaded weights through the PIPO pipeline (models larger than device
memory; see serving/offload_engine.py).  The pipeline stays warm across
decode steps by default (--no-warm for the cold per-step baseline),
keeps a budget-sized window of layers in flight (--preload-depth to
override, --depth-policy adaptive to re-size it from live KV/spill
pressure AND the measured link-bandwidth EWMA; docs/TUNING.md walks the
sizing), --quant int4 streams packed INT4 weights over the offload
link, and --kv-mode int4 packs the KV-cache rows the same way (the
tiered KV store ships live rows either way; see docs/ARCHITECTURE.md
"The KV tier"):
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --scaled --offload --placement disk --pipeline performance
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --scaled --offload --quant int4 --kv-mode int4

Plans are first-class: --plan-json resolves the spec and dumps the
fully-materialized plan (every auto field + why it got its value)
WITHOUT building an engine; --spec-json loads an EngineSpec JSON as the
base (explicit flags still override its fields):
  PYTHONPATH=src python -m repro.launch.serve --scaled --offload \
      --quant int4 --plan-json -
  PYTHONPATH=src python -m repro.launch.serve --spec-json my_spec.json
"""
import argparse
import json
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.serving.spec import (EngineSpec, SpecError, add_spec_args,
                                spec_from_args)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="PIPO serving launcher (spec-driven: flags -> "
                    "EngineSpec -> ResolvedPlan -> create_engine)")
    add_spec_args(ap)                       # generated from CLI_FLAGS
    ap.add_argument("--requests", type=int, default=8,
                    help="synthetic request count for the demo workload")
    ap.add_argument("--spec-json", metavar="FILE",
                    help="load an EngineSpec JSON as the base "
                         "(explicitly-given flags override its fields)")
    ap.add_argument("--plan-json", nargs="?", const="-", metavar="FILE",
                    help="resolve and dump the plan JSON (stdout when no "
                         "FILE), then exit without serving — the plan "
                         "dry-run")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()
    base = None
    try:
        if args.spec_json:
            with open(args.spec_json) as f:
                base = EngineSpec.from_json(f.read())
        spec = spec_from_args(args, base=base)
        plan = spec.resolve()
    except (SpecError, OSError, json.JSONDecodeError) as e:
        ap.error(str(e))
    if args.plan_json:
        payload = json.dumps(plan.to_json(), indent=2)
        if args.plan_json == "-":
            print(payload)
        else:
            with open(args.plan_json, "w") as f:
                f.write(payload + "\n")
            print(f"plan written to {args.plan_json}")
        return

    from repro.core.tasks import TaskType
    from repro.serving import Request
    from repro.serving.spec import create_engine

    print(f"plan: {plan.summary()}")
    eng = create_engine(plan)
    cfg = eng.cfg
    offloaded = plan.engine == "offloaded"
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, (8 + i % 8,)).astype(np.int32),
            max_new=8))
    done = eng.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.out) for r in done)
    print(f"completed={len(done)} tokens={total} tok_s={total / dt:.1f} "
          f"stats={eng.stats}")
    if offloaded:
        rep = eng.pipeline_report()
        busy = {t.value: f"{rep['per_kind'][t.value]['busy_s']:.2f}s"
                for t in TaskType}
        # host-clock spans: where the main thread's time went, not the
        # device's utilization (that needs a profiler trace)
        split = " ".join(f"{k}={v:.2f}"
                         for k, v in rep["main"]["share"].items())
        print(f"pipeline[{plan.pipeline}] depth={eng.sched.depth} "
              f"main thread: {split} busy={busy}")
    eng.shutdown()


if __name__ == "__main__":
    main()
