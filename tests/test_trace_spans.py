"""Spans inside and between the pipeline's tasks (``core/tasks.py``):
transfer phases, main-thread waits by producer, pool queueing, engine
steps, and their profiler annotations on the device trace's clock.  Also: the readers of the four task kinds (``report()``,
``replay``) see exactly what they saw before, and a virtual-clock trace
holds tasks only."""
import glob
import json
import re
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from fake_model import (FakeModel, run_virtual, run_virtual_moe,
                        run_virtual_pp, run_virtual_spec, run_virtual_traffic)
from repro.core.pipeline import PipelineScheduler, ThreadPool
from repro.core.replay import ReplayKnobs, TraceProfile, replay, step_times
from repro.core.tasks import (TASK_KINDS, TRACE_CAPACITY, WAIT_KINDS, Task,
                              TaskType, Trace, TraceEvent, VirtualClock,
                              phase)

FIXTURES = Path(__file__).resolve().parent / "fixtures"
TRANSFERS = ("weight_load", "kv_load", "kv_save")
PHASES = {"weight_load": ("stage", "put", "ready", "dequant"),
          "kv_load": ("stage", "put", "pad", "ready"),
          "kv_save": ("sync", "fetch", "scatter")}
# every span kind the program records
SPAN_KINDS = ({f"{k}.{p}" for k, ps in PHASES.items() for p in ps}
              | set(WAIT_KINDS) | {f"queue.{k}" for k in TRANSFERS}
              | {"engine.prefill", "engine.decode"})


# ---------------------------------------------------------------------------
# a tiny offloaded engine on the CPU, its trace recorded on the wall clock
# ---------------------------------------------------------------------------


def _serve(sim_bw=None, n=4, max_new=4, shutdown=True):
    from repro.configs import get_config, scaled_down
    from repro.serving import EngineSpec, Request, create_engine
    cfg = scaled_down(get_config("tinyllama-1.1b"))
    eng = create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                   placement="host", b_max=2, max_len=64,
                                   sim_bw=sim_bw))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=100 + i, prompt=rng.integers(
        0, cfg.vocab_size, (20 + 8 * i,)).astype(np.int32), max_new=max_new)
        for i in range(n)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    if shutdown:
        eng.shutdown()
    return eng, reqs


@pytest.fixture(scope="module")
def served():
    # a slow simulated link keeps each load milliseconds long, as on a
    # chip, so the fixed cost of recording a phase stays a small share
    return _serve(sim_bw=2e6)


def _phases_of(evs, task):
    return [e for e in evs if e.kind.startswith(task.kind + ".")
            and e.name == task.name and e.thread == task.thread
            and task.t_start <= e.t_start and e.t_end <= task.t_end]


def test_every_span_kind_is_declared_and_none_reuses_a_task_kind(served):
    eng, _ = served
    evs = eng.trace.events()
    kinds = {e.kind for e in evs if e.span}
    # every span is marked as one, and none takes a task kind
    assert {e.kind for e in evs if not e.span} <= TASK_KINDS
    assert kinds <= SPAN_KINDS
    assert not TASK_KINDS & SPAN_KINDS
    # besides the int4 dequant, which an fp32 engine never runs, only a
    # wait may be missing: a producer that finished in time costs none
    missing = SPAN_KINDS - kinds
    assert "weight_load.dequant" in missing
    assert missing <= {"weight_load.dequant", "wait.kv_load",
                       "wait.kv_save"}


@pytest.mark.parametrize("kind", TRANSFERS)
def test_transfer_phases_lie_inside_their_task(served, kind):
    eng, _ = served
    evs = eng.trace.events()
    tasks = [e for e in evs if e.kind == kind]
    assert tasks
    phases = [e for e in evs if e.kind.startswith(kind + ".")]
    assert phases and {e.kind.split(".")[1] for e in phases} <= set(
        PHASES[kind])
    # every phase sits inside exactly one task of its kind, name and thread
    for p in phases:
        parents = [t for t in tasks if t.name == p.name
                   and t.thread == p.thread
                   and t.t_start <= p.t_start and p.t_end <= t.t_end]
        assert len(parents) == 1, p


@pytest.mark.parametrize("kind", ["weight_load", "kv_load"])
def test_load_phases_cover_their_task(served, kind):
    """Each load that moves bytes is accounted for by its phases: at
    least 90% of its span (over the loads' time, and for the median
    load: a pool thread that waits for the interpreter lock between two
    phases leaves a gap no phase covers, and a busy CPU makes a few of
    those long), the bytes carried by its ``put`` phases."""
    eng, _ = served
    evs = eng.trace.events()
    tasks = [e for e in evs if e.kind == kind and e.nbytes]
    assert tasks
    covs, spans = [], []
    for t in tasks:
        ph = _phases_of(evs, t)
        covs.append(sum(e.t_end - e.t_start for e in ph))
        spans.append(t.t_end - t.t_start)
        assert sum(e.nbytes for e in ph) == t.nbytes
        assert all(e.nbytes == 0 for e in ph if not e.kind.endswith(".put"))
        assert ph[-1].kind == f"{kind}.ready"
    assert sum(covs) >= 0.9 * sum(spans)
    shares = sorted(c / s for c, s in zip(covs, spans))
    assert shares[len(shares) // 2] >= 0.9


def test_pipeline_saves_sync_then_fetch_then_scatter(served):
    """A pipeline KV_SAVE waits for its layer's program, copies the rows
    (the fetch carries the bytes copied), then writes the host tier.  The
    rows cross at the program's precision and narrow to the store's on
    the host, so the copy moves at least the bytes the task is priced at
    (the store's)."""
    eng, _ = served
    evs = eng.trace.events()
    saves = [e for e in evs if e.kind == "kv_save"
             and e.name.startswith("sv[")]
    assert saves
    for t in saves:
        ph = sorted(_phases_of(evs, t), key=lambda e: e.t_start)
        assert [e.kind for e in ph] == ["kv_save.sync", "kv_save.fetch",
                                        "kv_save.scatter"], t
        assert ph[1].nbytes in (t.nbytes, 2 * t.nbytes)    # f32 or bf16
        assert ph[0].nbytes == ph[2].nbytes == 0


def test_phase_outside_a_pool_task_records_nothing():
    with phase("put", 10):
        pass
    # a pool thread runs a task's phases under that task, then forgets it
    tr = Trace()
    pool = ThreadPool(1, tr)

    def body():
        with phase("stage"):
            pass

    t = Task(TaskType.WEIGHT_LOAD, "w[0]", body)
    pool.submit(t)
    t.wait()
    pool.shutdown()
    got = [e for e in tr.events() if e.kind == "weight_load.stage"]
    assert [(e.name, e.thread) for e in got] == [("w[0]", "pool-0")]
    assert not [e for e in tr.events() if e.kind == "weight_load.put"]


# ---------------------------------------------------------------------------
# main-thread waits, queueing, engine steps
# ---------------------------------------------------------------------------


def test_main_thread_waits_name_their_producer(served):
    eng, _ = served
    evs = eng.trace.events()
    waits = [e for e in evs if e.kind.startswith("wait.")]
    assert {"wait.weight_load", "wait.head"} <= {e.kind for e in waits}
    assert {e.kind for e in waits} <= set(WAIT_KINDS)
    assert all(e.thread == "main" for e in waits)
    for w in waits:
        if w.kind == "wait.head":
            assert w.name == "head"
            continue
        producer = w.kind.split(".", 1)[1]
        # the producer: a task of that kind and name that ended no later
        # than the wait did
        done = [t for t in evs if t.kind == producer and t.name == w.name
                and t.t_end <= w.t_end]
        assert done, w
    pattern = {"wait.weight_load": r"w\[\d+\]",
               "wait.kv_load": r"kv\[\d+,\d+\]",
               "wait.kv_save": r"(sv\[\d+,\d+\]|slot_save\[.+\])",
               "wait.head": "head"}
    assert all(re.fullmatch(pattern[w.kind], w.name) for w in waits)


class _SlowFake(FakeModel):
    """Transfers that take real time, so the main thread blocks on them."""

    def load_weights(self, j):
        time.sleep(0.004)
        return super().load_weights(j)

    def load_kv(self, i, j):
        time.sleep(0.002)
        return super().load_kv(i, j)

    def save_kv(self, i, j, kv):
        time.sleep(0.006)
        return super().save_kv(i, j, kv)


def test_wait_spans_on_a_thread_pool_over_the_fake_model():
    """The scheduler's waits over real threads: a wait that blocks is
    spanned on the main thread under its producer's name; a producer
    that had already finished costs no span."""
    model = _SlowFake(3)
    tr = Trace()
    pool = ThreadPool(2, tr)
    sched = PipelineScheduler(model.n, "performance", pool=pool, trace=tr,
                              warm=True, depth=1)
    sched.generate(model, lambda i: 0, 3)
    sched.drain_saves()
    sched.drop_kv_preloads()
    sched.shutdown()
    pool.shutdown()
    evs = tr.events()
    waits = [e for e in evs if e.kind.startswith("wait.")]
    assert "wait.weight_load" in {e.kind for e in waits}
    pattern = {"wait.weight_load": r"w\[\d+\]",
               "wait.kv_load": r"kv\[\d+,\d+\]",
               "wait.kv_save": r"sv\[\d+,\d+\]"}
    for w in waits:
        assert w.thread == "main"
        assert re.fullmatch(pattern[w.kind], w.name), w
        # the producer finished before the wait ended
        producer = [t for t in evs if t.kind == w.kind[5:]
                    and t.name == w.name and t.t_end <= w.t_end]
        assert producer, w
    # at most one weight wait per layer and iteration
    ww = [e for e in waits if e.kind == "wait.weight_load"]
    assert len(ww) <= 3 * model.n


class _SlowKVFake(FakeModel):
    """KV loads far slower than a layer's weights and compute, and saves
    slower still: the main thread must block on both."""

    def load_kv(self, i, j):
        time.sleep(0.02)
        return super().load_kv(i, j)

    def save_kv(self, i, j, kv):
        time.sleep(0.08)
        return super().save_kv(i, j, kv)


def test_kv_waits_on_a_forced_slow_kv_tier():
    """A KV load that outlasts everything before it is waited for as
    ``wait.kv_load``, a save that must land before its layer's next load
    as ``wait.kv_save``; each is named after its producer, which ended
    no later than the wait."""
    model = _SlowKVFake(2)
    tr = Trace()
    pool = ThreadPool(2, tr)
    sched = PipelineScheduler(model.n, "performance", pool=pool, trace=tr,
                              warm=True, depth=1)
    sched.generate(model, lambda i: 0, 2)
    sched.drain_saves()
    sched.drop_kv_preloads()
    sched.shutdown()
    pool.shutdown()
    evs = tr.events()
    pattern = {"wait.kv_load": r"kv\[\d+,\d+\]",
               "wait.kv_save": r"sv\[\d+,\d+\]"}
    for kind, pat in pattern.items():
        waits = [e for e in evs if e.kind == kind]
        assert waits, kind
        for w in waits:
            assert w.thread == "main" and re.fullmatch(pat, w.name), w
            assert [t for t in evs if t.kind == kind[5:]
                    and t.name == w.name and t.t_end <= w.t_end], w


def test_queue_spans_run_from_submit_to_start():
    tr = Trace()
    pool = ThreadPool(1, tr)
    gate = threading.Event()
    first = Task(TaskType.WEIGHT_LOAD, "w[0]", gate.wait)
    second = Task(TaskType.KV_SAVE, "sv[0,0]", lambda: None)
    pool.submit(first)
    pool.submit(second, priority=1)
    time.sleep(0.02)                       # the second sits behind the first
    gate.set()
    second.wait()
    pool.shutdown()
    q = {e.name: e for e in tr.events() if e.kind.startswith("queue.")}
    assert set(q) == {"w[0]", "sv[0,0]"}
    assert q["sv[0,0]"].kind == "queue.kv_save"
    for t in (first, second):
        e = q[t.name]
        assert e.t_start == pytest.approx(t.t_submit - tr.t0, abs=1e-12)
        assert e.t_end == pytest.approx(t.t_start - tr.t0, abs=1e-12)
        assert e.thread == "pool-0"
    assert q["sv[0,0]"].t_end - q["sv[0,0]"].t_start >= 0.015


def test_engine_prefill_carries_the_request_id(served):
    eng, reqs = served
    evs = eng.trace.events()
    pre = [e for e in evs if e.kind == "engine.prefill"]
    assert sorted(e.name for e in pre) == sorted(f"r{r.rid}" for r in reqs)
    dec = [e for e in evs if e.kind == "engine.decode"]
    assert dec and all(re.fullmatch(r"rows=[12]", e.name) for e in dec)
    # every compute of the run sits inside one engine step
    steps = pre + dec
    for c in (e for e in evs if e.kind == "compute"):
        assert any(s.t_start <= c.t_start and c.t_end <= s.t_end
                   for s in steps), c
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first_token
        span = next(e for e in pre if e.name == f"r{r.rid}")
        assert span.t_start + eng.trace.t0 >= r.t_admit - 1e-6


def test_report_splits_the_main_thread_window(served):
    eng, _ = served
    main = eng.trace.report()["main"]
    assert set(main["seconds"]) == {"compute", *WAIT_KINDS, "host"}
    assert sum(main["share"].values()) == pytest.approx(1.0)
    assert sum(main["seconds"].values()) == pytest.approx(main["window_s"])
    assert main["seconds"]["wait.weight_load"] > 0
    assert eng.trace.report()["dropped"] == 0
    # compute keeps counting COMPUTE events only
    assert main["seconds"]["compute"] == pytest.approx(
        eng.trace.thread_busy("main"))


def test_nested_wait_counts_as_the_wait():
    tr = Trace(clock=VirtualClock())
    for e in [
        TraceEvent("compute", "c[0,0]", 0.0, 4.0, "main"),
        TraceEvent("wait.weight_load", "w[u0/exp1]", 1.0, 3.0, "main",
                   span=True),
        TraceEvent("wait.head", "head", 5.0, 6.0, "main", span=True)]:
        tr._append(e)
    main = tr.main_split()
    assert main["window_s"] == 6.0
    assert main["seconds"]["compute"] == 2.0
    assert main["seconds"]["wait.weight_load"] == 2.0
    assert main["seconds"]["wait.head"] == 1.0
    assert main["seconds"]["host"] == 1.0


# ---------------------------------------------------------------------------
# what the four task kinds' readers see is unchanged
# ---------------------------------------------------------------------------


def _with_spans(trace: Trace) -> Trace:
    """The same recording with a wait, a queue span and a phase added for
    every task, each under the task's name."""
    out = Trace.from_json(trace.to_json())
    for e in trace.events():
        if e.kind == "compute":
            continue
        out._append(TraceEvent(f"wait.{e.kind}", e.name, e.t_start - 0.5,
                               e.t_end, "main", span=True))
        out._append(TraceEvent(f"queue.{e.kind}", e.name, e.t_start - 1.0,
                               e.t_start, e.thread, span=True))
        out._append(TraceEvent(f"{e.kind}.put", e.name, e.t_start,
                               e.t_end, e.thread, e.nbytes, span=True))
    out._append(TraceEvent("engine.decode", "rows=2", -5.0, 1e6, "main",
                           span=True))
    return out


@pytest.mark.parametrize("fixture", sorted(p.name for p in
                                           FIXTURES.glob("trace_*.json")))
def test_task_kind_readers_ignore_span_kinds(fixture):
    tr = Trace.from_json((FIXTURES / fixture).read_text())
    sp = _with_spans(tr)
    rep, rep2 = tr.report(), sp.report()
    for kind in TASK_KINDS:
        assert rep2["per_kind"][kind] == rep["per_kind"][kind]
    assert rep2["span_s"] == rep["span_s"]
    assert sp.thread_busy("main") == tr.thread_busy("main")
    assert sp.busy_fraction() == tr.busy_fraction()
    assert step_times(sp) == step_times(tr)


@pytest.mark.parametrize("fixture", ["trace_warm_d1.json",
                                     "trace_warm_d2.json",
                                     "trace_traffic_d1.json"])
def test_replay_ignores_span_kinds(fixture):
    tr = Trace.from_json((FIXTURES / fixture).read_text())
    sp = _with_spans(tr)
    assert TraceProfile.from_trace(sp) == TraceProfile.from_trace(tr)
    a, b = replay(tr, ReplayKnobs()), replay(sp, ReplayKnobs())
    assert a.trace.to_json() == b.trace.to_json()


@pytest.mark.parametrize("runner", [
    lambda: run_virtual("performance", warm=True, calls=2, depth=2),
    lambda: run_virtual("sequential"),
    lambda: run_virtual_moe(warm=True),
    lambda: run_virtual_pp(),
    lambda: run_virtual_traffic(),
    lambda: run_virtual_spec(),
], ids=["warm", "sequential", "moe", "pp", "traffic", "spec"])
def test_virtual_traces_hold_tasks_only(runner):
    """A VirtualPool trace records no span: its JSON is byte-stable (the
    golden fixtures are regenerated and compared in test_replay.py)."""
    _, tr, _ = runner()
    assert {e.kind for e in tr.events()} <= TASK_KINDS
    assert not any(e.span for e in tr.events())
    assert tr.dropped == 0
    json.dumps(tr.to_json())


# ---------------------------------------------------------------------------
# the ring of events
# ---------------------------------------------------------------------------


def test_ring_keeps_the_newest_events_and_counts_every_one():
    """A live trace keeps the newest ``TRACE_CAPACITY`` events and says
    how many it let go."""
    tr = Trace()
    n = TRACE_CAPACITY + 6
    for k in range(n):
        tr.record("wait.head", f"h{k}", tr.t0 + k, tr.t0 + k + 1)
    assert tr.seq == n
    assert tr.dropped == 6
    evs = tr.events()
    assert len(evs) == TRACE_CAPACITY
    assert (evs[0].name, evs[-1].name) == ("h6", f"h{n - 1}")
    assert [e.name for e in tr.events_since(n - 3)] == [
        f"h{k}" for k in range(n - 3, n)]
    assert len(tr.events_since(2)) == TRACE_CAPACITY   # the rest left
    assert tr.events_since(n) == []


def test_virtual_and_loaded_traces_keep_every_event():
    """Only a live trace is a ring: a simulation (``TrafficSim`` too) and
    a trace loaded from JSON keep all their events, each counted."""
    tr = Trace(clock=VirtualClock())
    n = TRACE_CAPACITY + 6
    for k in range(n):
        t = Task(TaskType.COMPUTE, f"c[{k},0]", lambda: None)
        t.t_start, t.t_end = k, k + 1
        tr.add(t, "main")
    assert (tr.seq, len(tr.events()), tr.dropped) == (n, n, 0)
    back = Trace.from_json(tr.to_json())
    assert (back.seq, len(back.events()), back.dropped) == (n, n, 0)
    assert back.report()["dropped"] == 0
    assert back.span() == tr.span() == n
    from repro.serving.workload import SimCosts, TrafficSim, ramp_trace
    sim = TrafficSim(ramp_trace(6, 0.3, 1.0, seed=7, prompt_len=(8, 16),
                                max_new=4), b_max=2,
                     costs=SimCosts(sweep_s=1.0, tok_s=0.02,
                                    prefill_tok_s=0.05)).run()
    assert sim.trace.seq == len(sim.trace.events()) > 0


def test_engine_feedback_reads_events_since_its_mark():
    """The adaptive window's per-step feedback takes the events since the
    last step by sequence number, never the whole ring."""
    tr = Trace()
    mark = tr.seq
    tr.record("wait.head", "head", tr.t0, tr.t0 + 1.0)
    assert [e.kind for e in tr.events_since(mark)] == ["wait.head"]
    assert tr.events_since(tr.seq) == []


# ---------------------------------------------------------------------------
# the profiler's host plane, on the device trace's clock
# ---------------------------------------------------------------------------


def test_profiler_host_plane_holds_the_spans_on_the_trace_clock(tmp_path):
    """With JAX's profiler on, each program span and task is also a host
    plane event of the same kind whose start, mapped through the
    ``bench.window`` annotation, lies within 1 ms of the Trace's."""
    import jax
    eng, _ = _serve(sim_bw=2e7, n=2, max_new=3, shutdown=False)   # warm
    from repro.serving import Request
    rng = np.random.default_rng(1)
    for i in range(2):
        eng.submit(Request(rid=200 + i, prompt=rng.integers(
            0, eng.cfg.vocab_size, (20 + 8 * i,)).astype(np.int32),
            max_new=3))
    # the window opens with no transfer thread running (the warm
    # pipeline's preloads landed) and closes once every transfer it
    # started has ended (shutdown drains and joins the pools)
    for t in list(eng.sched._w_tasks.values()):
        t.wait()
    eng.sched.drain_saves()
    eng.sched.drop_kv_preloads()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        t_w = time.perf_counter()
        mark = eng.trace.seq
        eng.run()
        eng.shutdown()
        t_end = time.perf_counter()
    jax.profiler.stop_trace()
    # queue spans are recorded after the fact: they have no annotation;
    # a task started before the window (a preload) opened none either
    evs = [e for e in eng.trace.events_since(mark)
           if not e.kind.startswith("queue.")
           and t_w <= e.t_start + eng.trace.t0
           and e.t_end + eng.trace.t0 <= t_end]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    kinds = {e.kind for e in evs}
    prof, w0 = {}, None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    w0 = e.start_ns
                elif e.name in kinds:
                    prof.setdefault(e.name, []).append(e.start_ns)
    assert w0 is not None
    want = {"weight_load", "kv_load", "kv_save", "compute",
            "weight_load.put", "kv_load.put", "kv_save.fetch",
            "wait.weight_load", "wait.head",
            "engine.prefill", "engine.decode"}
    assert want <= set(prof)
    for kind in kinds:
        mine = sorted(w0 + (e.t_start + eng.trace.t0 - t_w) * 1e9
                      for e in evs if e.kind == kind)
        theirs = sorted(prof.get(kind, []))
        assert len(theirs) == len(mine), kind
        worst = max(abs(a - b) for a, b in zip(mine, theirs))
        assert worst < 1e6, (kind, worst)
