#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``).  One run:

1. builds the engine through the one normal path, ``EngineSpec(cfg=...)``
   -> ``resolve(budget)`` -> ``create_engine(plan)``, with the weights on
   the host tier and the full-depth deployment's device memory per layer;
2. warms up the cell's own shapes (every prompt length, decode at
   ``b_max``, the KV shapes the window will cross) and fills the slots as
   the traffic asks;
3. drives the traffic through ``engine.step()`` for ``--seconds``;
4. frees the engine, runs the plain float32 reference over a sample of
   the served requests drawn from the seed, and prints the numbers
   compared, each beside its limit, then one JSON result line.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the engine's own spans and counters.  Each metric is a reader
``bench/metrics/<name>.py`` with ``read(run) -> float | None``, found by
the names in ``BENCHMARK.json`` (``reader_path``).

A configuration file is the ``ModelConfig`` it describes (``model_config``):
every key that names a ``ModelConfig`` field is passed as it stands; a
dict under ``moe``, ``mla`` or ``ssm`` becomes that sub-config, field by
field; ``pattern`` and ``remainder`` are lists of ``[mixer, ffn]``, and a
one-kind stack may give ``mixer`` and ``ffn`` instead.  The harness's own
keys are ``HARNESS_KEYS``; ``reference`` names the file of the plain
reference (``bench/reference.py`` states what such a file provides).  Any
other key is an error.  A limits file (``bench/limits/<cell>.json``) bounds
numbers over the served tokens' gaps (``gap_number``): ``served_gap``,
``share_over_<g>``, or both.

Without a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits with code 2.  The JAX compilation cache lives
in ``bench/.jax_cache`` inside the checkout, so only a checkout's first
run compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
KV_BUCKET = 32            # the KV tier pads live rows to multiples of this


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class BenchError(RuntimeError):
    """The cell cannot run as its files describe."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# files
# ---------------------------------------------------------------------------

def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots); a
    file already imported from that path is not run again."""
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} is missing")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(name)
    if mod is not None and Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    mix: dict               # the traffic file
    end_to_end: List[dict]  # metric entries this cell reports, trace 0
    per_layer: List[dict]   # ... trace 1
    limits: dict            # {number: limit} of the correctness check


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def find_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(configs[w["config"]]["file"])
    mix = load_json(f"bench/traffic/{w['traffic']}.json")
    limits = load_json(f"bench/limits/{name}.json")
    for k in limits:
        gap_number(k, [0.0])             # a name no run can compute fails here
    return Cell(name, int(w["chips"]), config, mix,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)],
                limits)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def setup_jax():
    """Compilation cache inside the checkout, every program cached (the
    per-layer programs compile in well under a second)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_accelerator(chips: int):
    """The chips this run may use; raises ``NoAccelerator`` unless JAX
    finds at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX found "
                            f"{len(devs)}")
    return devs[:chips]


class CompileLog:
    """Counts XLA backend compiles (JAX monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.secs = 0.0

    def __call__(self, name, secs, **_):
        if name == self.EVENT:
            self.count += 1
            self.secs += secs


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def bytes_in_use(devs) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devs)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

# Keys of a configuration file that the harness reads itself; every other
# key names a ``ModelConfig`` field.
HARNESS_KEYS = ("source", "reference", "precision", "placement",
                "device_budget", "published", "reduced", "assumed",
                "deployment", "mixer", "ffn")


def _fields(cls, d: dict, where: str) -> dict:
    """``d`` as keyword arguments of dataclass ``cls``: a key that names no
    field is an error; a value is cast to its field's default's float or
    bool, and a list becomes a tuple, as a frozen config holds them."""
    import dataclasses
    fields = {f.name: f for f in dataclasses.fields(cls)}
    out = {}
    for k, v in d.items():
        if k not in fields:
            raise BenchError(f"configuration key {where}{k!r} is neither a "
                             f"{cls.__name__} field nor one the harness "
                             f"reads ({', '.join(HARNESS_KEYS)})")
        default = fields[k].default
        if isinstance(default, bool) and v is not None:
            v = bool(v)
        elif isinstance(default, float) and v is not None:
            v = float(v)
        elif isinstance(v, list):
            v = tuple(v)
        out[k] = v
    return out


def model_config(c: dict):
    """The served ``ModelConfig`` that a configuration file describes (the
    module docstring gives the format)."""
    from repro.configs.base import (LayerSpec, MLAConfig, ModelConfig,
                                    MoEConfig, SSMConfig)
    kw = _fields(ModelConfig, {k: v for k, v in c.items()
                               if k not in HARNESS_KEYS}, "")
    for k, cls in (("moe", MoEConfig), ("mla", MLAConfig),
                   ("ssm", SSMConfig)):
        if kw.get(k) is not None:
            kw[k] = cls(**_fields(cls, kw[k], f"{k}."))
    for k in ("pattern", "remainder"):
        if k in kw:
            kw[k] = tuple(LayerSpec(*p) for p in kw[k])
    one_kind = [k for k in ("mixer", "ffn") if k in c]
    if len(one_kind) != (0 if "pattern" in kw else 2):
        raise BenchError(f"configuration {c.get('name')!r} must give either "
                         f"pattern or mixer and ffn")
    if "pattern" not in kw:
        kw["pattern"] = (LayerSpec(c["mixer"], c["ffn"]),)
    kw.setdefault("family", "moe" if kw.get("moe") else "dense")
    return ModelConfig(**kw)


def reference_of(c: dict):
    """The plain reference module that the configuration's ``reference``
    key names, a path from the checkout's root."""
    if "reference" not in c:
        raise BenchError(f"configuration {c.get('name')!r} names no "
                         f"reference")
    return load_module(ROOT / c["reference"])


def resident_bytes(c: dict) -> int:
    """Device-resident tensors at the stated bf16: the embedding, an
    untied head, the final norm and the router of each MoE layer."""
    costs = load_module(BENCH / "costs.py")
    d = c["d_model"]
    n = reference_of(c).padded_vocab(c) * d * (1 if c["tie_embeddings"] else 2) + d
    if c.get("moe"):
        n += (sum(1 for _, ffn in costs.layers(c) if ffn == "moe")
              * d * c["moe"]["num_experts"])
    return 2 * n


def device_budget(c: dict, bytes_limit: int) -> int:
    """The full-depth deployment's device memory per layer, for the kept
    layers: resident + (limit - resident) * kept / published."""
    res = resident_bytes(c)
    kept, full = c["num_layers"], c["published"]["num_layers"]
    return int(res + (bytes_limit - res) * kept / full)


def host_bytes() -> int:
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def build_engine(cell: Cell, seed: int, devs):
    from repro.core.offload import MemoryBudget
    from repro.serving.spec import EngineSpec, create_engine
    c, mix = cell.config, cell.mix
    limit = int((devs[0].memory_stats() or {}).get("bytes_limit", 16 << 30))
    budget = MemoryBudget(device=device_budget(c, limit), host=host_bytes())
    spec = EngineSpec(arch=c["name"], cfg=model_config(c), offload=True,
                      placement=c["placement"], pipeline="performance",
                      b_max=int(mix["b_max"]), max_len=int(mix["max_len"]),
                      seed=int(seed))
    plan = spec.resolve(budget)
    if plan.engine != "offloaded" or plan.placement != c["placement"]:
        raise BenchError(f"plan resolved to the {plan.engine} engine with "
                         f"placement {plan.placement}: {plan.provenance}")
    log(f"plan: {plan.summary()}")
    log(f"plan: device_budget={budget.device} (bytes_limit {limit}) "
        f"host_budget={budget.host} depth: {plan.provenance.get('depth')}")
    return create_engine(plan)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclass
class HostEvent:
    """One of the engine's own spans, on the perf_counter clock."""
    kind: str               # weight_load | kv_load | kv_save | compute
    name: str
    t_start: float
    t_end: float
    nbytes: int


@dataclass
class Step:
    t0: float
    t1: float
    prefills: int           # admissions this step
    decode_steps: int
    tokens: int


@dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    seed: int
    chips: int
    setup_s: float
    t0: float                              # window, perf_counter seconds
    t1: float
    steps: List[Step]
    requests: List[Any]                    # every traffic request submitted
    stats0: Dict[str, int]
    stats1: Dict[str, int]
    host_events: List[Any] = field(default_factory=list)   # in the window
    device: Any = None                     # trace_reduce.DeviceTrace
    peaks: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def delta(self, key: str) -> int:
        return int(self.stats1.get(key, 0)) - int(self.stats0.get(key, 0))

    def tokens_in_window(self):
        """(request, index) of every token emitted inside the window."""
        return [(r, i) for r in self.requests
                for i, t in enumerate(r.t_tokens) if self.t0 <= t <= self.t1]


def _submit(eng, item):
    from repro.serving import Request
    req = Request(rid=item.rid, prompt=item.prompt, max_new=item.max_new)
    eng.submit(req)
    return req


def _step(eng, done, steps: List[Step]):
    import jax
    s0 = dict(eng.stats)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.step"):
        eng.step(done)
    t1 = time.perf_counter()
    steps.append(Step(t0, t1, eng.stats["prefills"] - s0["prefills"],
                      eng.stats["decode_steps"] - s0["decode_steps"],
                      eng.stats["tokens_out"] - s0["tokens_out"]))


def warm_up(eng, cell: Cell, seed: int, vocab: int):
    """Compile every prefill length and the decode step at ``b_max``."""
    import generator
    for it in generator.warm_items(cell.mix, vocab, seed):
        _submit(eng, it)
    eng.run()


def warm_kv(eng, cell: Cell):
    """Load one layer's cache at every live shape the window can present
    (all ``b_max`` rows, as a deep queue keeps them, x positions up to the
    longest prompt and output, rounded up to the KV tier's bucket), so the
    window finds the padding programs compiled."""
    kv = getattr(eng, "kvstore", None)
    if kv is None or not hasattr(kv, "load"):
        log("warm_kv: the engine has no KV store to warm")
        return
    b_max, max_len = int(cell.mix["b_max"]), int(cell.mix["max_len"])
    reach = min(max(cell.mix["prompt_lens"]) + int(cell.mix["out_max"]),
                max_len)
    n = 0
    try:
        for ll in range(KV_BUCKET, reach + KV_BUCKET, KV_BUCKET):
            kv.load(0, b_max, min(ll, max_len))
            n += 1
    except TypeError as e:          # a KV store with another signature
        log(f"warm_kv: skipped ({e})")
    log(f"warm_kv: {n} live shapes loaded")


def drive(eng, cell: Cell, items, seconds: float, profile_dir: Optional[str],
          comp: CompileLog, devs):
    """Fill the slots and the queue (set-up), then run the window, keeping
    at least ``queue`` requests waiting.  Returns (t0, t1, steps,
    requests, stats before, stats after, compiles in the window, the most
    device memory in use at the end of a step in the window)."""
    import jax
    mix = cell.mix
    depth = int(mix["queue"])
    reqs, done = [], []
    pending = list(items)
    n0 = int(mix["b_max"]) + depth
    for it in pending[:n0]:
        reqs.append(_submit(eng, it))
    pending = pending[n0:]
    _step(eng, done, [])                  # admit the first b_max, one decode
    warm_kv(eng, cell)
    if profile_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # Python frames: off
        opts.host_tracer_level = 1         # the benchmark's own spans
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    stats0 = dict(eng.stats)
    c0 = comp.count
    steps: List[Step] = []
    in_use = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        until = t0 + seconds
        while time.perf_counter() < until:
            while pending and len(eng.queue) < depth:
                reqs.append(_submit(eng, pending.pop(0)))
            if eng.idle():
                break
            _step(eng, done, steps)
            in_use = max(in_use, bytes_in_use(devs))
        t1 = time.perf_counter()
    compiles = comp.count - c0
    stats1 = dict(eng.stats)
    if profile_dir:
        jax.profiler.stop_trace()
    return t0, t1, steps, reqs, stats0, stats1, compiles, in_use


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(requests, mix: dict, seed: int):
    """Requests to check, drawn from the seed: the longest finished one,
    then others at random until ``check_tokens`` served tokens; where too
    few finished, requests still decoding add the tokens served so far."""
    import numpy as np
    rng = np.random.default_rng(int(seed) + 2)
    fin = [r for r in requests if r.t_done and r.out]
    live = [r for r in requests if not r.t_done and r.out]
    want = int(mix["check_tokens"])
    picked = []
    for pool in (fin, live):
        if not pool or sum(len(r.out) for r in picked) >= want:
            continue
        pool = sorted(pool, key=lambda r: (-len(r.out), r.rid))
        rest = [pool[0]] + [pool[1:][i] for i in rng.permutation(len(pool) - 1)]
        for r in rest:
            if sum(len(r.out) for r in picked) >= want:
                break
            picked.append(r)
    return picked


def served_gaps(config: dict, seed: int, picked, length: int,
                fp8: bool = False):
    """For each picked request, the gap by which each served token's
    reference logit lies below the reference's best at its position;
    with ``fp8`` also the gap of the token that the float8 control puts
    first.  Each request runs alone, padded to ``length`` positions, so
    the reference compiles once per cell.  Returns (program gaps, control
    gaps), lists of arrays."""
    import numpy as np
    reference = reference_of(config)
    log(f"check: reference {config['reference']}")
    w = reference.init_weights(config, seed)
    prog, ctrl = [], []
    for r in picked:
        p, out = len(r.prompt), np.asarray(r.out, np.int32)
        span = slice(p - 1, p - 1 + len(out))
        toks = np.zeros((1, length), np.int32)
        toks[0, :p] = r.prompt
        toks[0, p:p + len(out) - 1] = out[:-1]
        served = np.zeros((1, length), np.int32)
        served[0, span] = out
        x = reference.hidden(config, w, toks)
        best, at, _ = reference.judge(config, w, x, served)
        prog.append((best - at)[0, span])
        if fp8:
            xq = reference.hidden(config, w, toks, fp8=True)
            _, _, top = reference.judge(config, w, xq, served, fp8=True)
            _, at_c, _ = reference.judge(config, w, x, top)
            ctrl.append((best - at_c)[0, span])
    del w
    return prog, ctrl


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def reader_path(name: str) -> Path:
    """``bench/metrics/<name>.py``; a name ``<base>.<tag>`` without a file
    of its own is read by ``<base>.py`` (one quantity split by the
    end-to-end metric it moves in each cell)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return path


def read_metrics(entries: List[dict], run: Run) -> Dict[str, dict]:
    out = {}
    for m in entries:
        v = load_module(reader_path(m["name"])).read(run)
        if v is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def host_events_in(eng, t0: float, t1: float):
    """The engine's own trace events (weight/KV transfers, layer
    computes) that ended inside the window, on the perf_counter clock."""
    tr = getattr(eng, "trace", None)
    if tr is None:
        return []
    base = tr.t0
    return [HostEvent(e.kind, e.name, e.t_start + base, e.t_end + base,
                      e.nbytes) for e in tr.events()
            if t0 <= e.t_end + base <= t1]


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def prepare(workload: str):
    """(cell, jax, devices, compile log) for a run of ``workload``."""
    cell = find_cell(workload)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    jax = setup_jax()
    devs = require_accelerator(cell.chips)
    import repro  # noqa: F401  (the system under test; fails without src/)
    comp = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(comp)
    return cell, jax, devs, comp


def serve(cell: Cell, seed: int, seconds: float, trace: bool, devs,
          comp: CompileLog, t_start: float) -> dict:
    """Build, warm and drive the engine for one window, read the metrics,
    and free the engine.  Returns the result without ``correct`` and the
    check, plus the requests (``"requests"``) for the check."""
    import generator
    c = cell.config
    d0 = devs[0]
    eng = build_engine(cell, seed, devs)
    log(f"set-up: engine built at {time.perf_counter() - t_start:.1f}s "
        f"({comp.count} compiles)")
    warm_up(eng, cell, seed, c["vocab_size"])
    log(f"set-up: warm at {time.perf_counter() - t_start:.1f}s "
        f"({comp.count} compiles)")
    items = generator.make(cell.mix, c["vocab_size"], seed)
    prof = tempfile.mkdtemp(prefix="bench_prof_") if trace else None
    out = {}
    try:
        t0, t1, steps, reqs, s0, s1, compiles, in_use = drive(
            eng, cell, items, float(seconds), prof, comp, devs)
        setup_s = t0 - t_start
        log(f"window: {t1 - t0:.3f}s, {len(steps)} steps, "
            f"{s1['tokens_out'] - s0['tokens_out']} tokens, "
            f"{s1['prefills'] - s0['prefills']} admissions, {compiles} "
            f"compiles inside the window; set-up {setup_s:.3f}s")
        log(f"device memory: {in_use} bytes in use at most at a step's end "
            f"in the window; {memory_peak(devs)} peak since the start")
        run = Run(cell, seed, len(devs), setup_s, t0, t1, steps, reqs,
                  s0, s1, host_events_in(eng, t0, t1))
        out["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": memory_peak(devs),
                         "window_bytes_in_use": in_use}
        if trace:
            import peaks
            import trace_reduce
            run.peaks = peaks.lookup(d0.device_kind)
            run.device = trace_reduce.load(prof, n_chips=len(devs))
            out["device"]["busy_s"] = run.device.busy_s()
            out["device"]["window_s"] = run.device.window_s
            out["breakdown"] = trace_reduce.breakdown(run.device,
                                                      run.host_events, t0)
            out["metrics"] = read_metrics(cell.per_layer, run)
        else:
            out["metrics"] = read_metrics(cell.end_to_end, run)
        ttft = sorted(r.t_first_token - r.t_arrive for r in reqs
                      if r.t_first_token and t0 <= r.t_first_token <= t1)
        if ttft:
            log(f"ttft (not a metric): median "
                f"{ttft[len(ttft) // 2] * 1e3:.1f} ms over {len(ttft)} "
                f"requests")
    finally:
        if prof:
            shutil.rmtree(prof, ignore_errors=True)
    out["attempted"] = sum(1 for r in reqs if r.out)
    out["failed"] = sum(
        1 for r in reqs if r.t_done and len(r.out) < r.max_new
        and len(r.prompt) + len(r.out) < int(cell.mix["max_len"]) - 1)
    out["requests"] = reqs
    eng.shutdown()
    del eng
    gc.collect()
    log(f"engine freed: {bytes_in_use(devs)} bytes still in use on the "
        f"device")
    return out


# The numbers a cell's limits file may hold, each over the gaps of the
# served tokens compared (``gap_number``): the widest gap, and
# ``share_over_<g>``, the percentage of served tokens whose gap exceeds
# ``<g>``.  Every run reads the widest gap and the shares at CONTROL_SHARES
# besides the numbers its limits file bounds.
GAP_NUMBERS = {
    "served_gap": lambda g: float(g.max()),
}
SHARE_OVER = "share_over_"
CONTROL_SHARES = tuple(f"{SHARE_OVER}{g}" for g in ("0.01", "0.05", "0.1"))


def gap_number(name: str, gaps) -> float:
    """Check number ``name`` over the served tokens' gaps."""
    import numpy as np
    g = np.asarray(gaps, np.float64)
    if name in GAP_NUMBERS:
        return GAP_NUMBERS[name](g)
    if name.startswith(SHARE_OVER):
        try:
            over = float(name[len(SHARE_OVER):])
        except ValueError:
            over = float("nan")
        if np.isfinite(over) and over >= 0:
            return 100.0 * float(np.mean(g > over))
    raise BenchError(f"no check number {name!r}: a limits file may bound "
                     f"{sorted(GAP_NUMBERS)} and {SHARE_OVER}<gap>")


def check(cell: Cell, seed: int, reqs, fp8: bool = False):
    """(check, correct, program readings, control readings or None): each
    number of the cell's limits file over a sample of the served
    requests, beside its limit; ``GAP_NUMBERS``, ``CONTROL_SHARES`` and the
    limits file's numbers for the program and, with ``fp8``, for the
    float8 control."""
    import numpy as np
    t = time.perf_counter()
    picked = sample(reqs, cell.mix, seed)
    prog, ctrl = served_gaps(cell.config, seed, picked,
                             int(cell.mix["max_len"]), fp8=fp8)
    g = np.concatenate(prog) if prog else np.zeros(0)
    log(f"check: {len(picked)} requests, {g.size} served tokens compared; "
        f"reference {time.perf_counter() - t:.1f}s")
    if not g.size:
        return ({k: {"value": None, "limit": v}
                 for k, v in cell.limits.items()}, False, None, None)
    names = dict.fromkeys([*GAP_NUMBERS, *CONTROL_SHARES, *cell.limits])
    prog = {k: gap_number(k, g) for k in names}
    chk = {k: {"value": prog[k], "limit": v} for k, v in cell.limits.items()}
    for k in prog:
        if k not in chk:
            log(f"check: {k}={prog[k]} (not compared in this cell)")
    correct = all(c["value"] <= c["limit"] for c in chk.values())
    ctl = ({k: gap_number(k, np.concatenate(ctrl)) for k in names}
           if ctrl else None)
    return chk, correct, prog, ctl


def run_cell(args) -> dict:
    cell, jax, devs, comp = prepare(args.workload)
    d0 = devs[0]
    log(f"device: {d0.platform} {d0.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; cell {cell.name} seed {args.seed} seconds "
        f"{args.seconds} trace {args.trace}")
    out = serve(cell, args.seed, args.seconds, bool(args.trace), devs, comp,
                T_START)
    chk, correct, _, _ = check(cell, args.seed, out.pop("requests"))
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": out["device"]}
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["check"] = chk
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args)
    except NoAccelerator as e:
        log(f"cell: {e}")
        return 2
    except (BenchError, FileNotFoundError, ImportError) as e:
        log(f"cell: cannot run: {type(e).__name__}: {e}")
        return 3
    line = json.dumps(result)
    chk = result["check"]
    for k, v in chk.items():
        log(f"check {k}={v['value']} limit={v['limit']}")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
