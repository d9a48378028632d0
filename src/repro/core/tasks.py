"""PIPO task model (paper §3.1.2).

Inference work is decomposed into four task types:
  * COMPUTE        — MHA/MLP/embedding layer compute (main thread only)
  * WEIGHT_LOAD    — weights: disk/host tier -> device tier
  * KV_LOAD        — KV-cache: host tier -> device tier
  * KV_SAVE        — new KV-pairs: device tier -> host tier

Each task carries a threading.Event for *task-level* synchronization —
the paper's central deviation from FlexGen's device-level sync ('S' boxes
in Fig. 2): a consumer waits on exactly the producer it needs, nothing
else.

Clock seam: all timestamps flow through a ``Clock`` so the scheduler can
run against a ``VirtualClock`` (deterministic discrete-event timeline, no
sleeps) in tests and the wall clock in production.  See
``core.pipeline.VirtualPool`` for the fake transport built on top.

Spans beyond the four task kinds: on a wall-clock ``Trace`` the pipeline,
the tiers and the engine also record what happens *inside* and *between*
tasks.  Each is a ``TraceEvent`` marked ``span``, under a kind of its own,
so no reader of the four task kinds sees it (``Trace.work_events``):

  * ``<task kind>.<phase>`` — a step inside a transfer (``phase()``),
    named after the task that ran it (``w[3]``, ``kv[7,3]``, ``sv[7,3]``);
  * ``wait.<task kind>`` / ``wait.head`` — the main thread blocked on a
    producer, named after it;
  * ``queue.<task kind>`` — a task sat in the pool's queue, from submit
    to start;
  * ``engine.prefill`` / ``engine.decode`` — one admission's prefill
    (named ``r<rid>``) or one decode step (named ``rows=<n>``).

While JAX's profiler records, every span and every task also opens a
``jax.profiler.TraceAnnotation`` of the same kind (name and bytes as its
arguments), which puts it on the profiler's host plane beside the
device's ops.  Nothing here imports JAX: the annotation is looked up
only once the process has imported it.
"""
from __future__ import annotations

import collections
import contextlib
import json
import sys
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, Optional


class TaskType(Enum):
    COMPUTE = "compute"
    WEIGHT_LOAD = "weight_load"
    KV_LOAD = "kv_load"
    KV_SAVE = "kv_save"


TASK_KINDS = frozenset(t.value for t in TaskType)

# producers a main-thread wait is split by in ``Trace.report()["main"]``
WAIT_KINDS = ("wait.weight_load", "wait.kv_load", "wait.kv_save",
              "wait.head")

# events a wall-clock Trace keeps: a ring, so a long-lived server's trace
# stays bounded.  A traced benchmark window holds ~11k events; this keeps
# several windows.
TRACE_CAPACITY = 1 << 16


# ---------------------------------------------------------------------------
# Clocks
# ---------------------------------------------------------------------------


class Clock:
    """Timestamp source for tasks/traces."""

    def now(self) -> float:
        raise NotImplementedError


class WallClock(Clock):
    def now(self) -> float:
        return time.perf_counter()


class VirtualClock(Clock):
    """Deterministic logical time: advanced explicitly by the virtual
    transport (``VirtualPool``), never by sleeping.  Starts at 0 so traces
    are reproducible run to run."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def advance_to(self, t: float):
        if t > self.t:
            self.t = t


WALL_CLOCK = WallClock()


# ---------------------------------------------------------------------------
# Profiler annotations
# ---------------------------------------------------------------------------

# what ``annotate``/``region``/``phase`` hand out when nothing is recorded
_NULL = contextlib.nullcontext()


def annotate(kind: str, name: str = "", nbytes: int = 0):
    """A ``jax.profiler.TraceAnnotation`` named ``kind`` (with ``name``
    and, when nonzero, ``bytes`` as its arguments) while JAX's profiler
    records; otherwise a no-op context.  JAX is never imported from here:
    a process that has not imported it cannot be profiling."""
    jax = sys.modules.get("jax")
    prof = getattr(jax, "profiler", None) if jax is not None else None
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return _NULL
    if nbytes:
        return prof.TraceAnnotation(kind, name=name, bytes=int(nbytes))
    return prof.TraceAnnotation(kind, name=name)


@dataclass
class Task:
    kind: TaskType
    name: str                      # e.g. "w[3]", "kv_load[i=2,j=5]"
    fn: Callable[[], Any]
    done: threading.Event = field(default_factory=threading.Event)
    result: Any = None
    error: Optional[BaseException] = None
    # timing for the utilization/trace benchmarks
    t_submit: float = 0.0
    t_start: float = 0.0
    t_end: float = 0.0
    # payload size (bytes moved); 0 when unknown.  Set by the submitter
    # BEFORE the task is handed to a pool (a VirtualPool traces the task
    # synchronously inside submit), and copied onto the TraceEvent so
    # per-task-type transfer volumes are assertable on traces (e.g. the
    # MoE routed-union invariant: union bytes < whole-bank bytes).  The
    # scheduler fills it for WEIGHT_LOADs (model.weight_nbytes) and
    # KV_LOADs (model.kv_nbytes) when the model exposes those hooks, so
    # report() splits link volume by task kind.
    nbytes: int = 0
    # live extent of a KV payload, (live_batch, live_len); None when the
    # payload is not extent-sliced (weight loads, whole-slab KV).  Set by
    # the submitter alongside nbytes and copied onto the TraceEvent so
    # live-row slicing is observable on traces (the tiered-KV-store
    # invariant: a half-full slot's KV_LOAD bytes < the allocated slab).
    extent: Optional[tuple] = None
    # pipeline-parallel stage this task belongs to (0 for the single-stage
    # pipeline).  Stamped by the submitting scheduler and copied onto the
    # TraceEvent so per-stage residency/bubble accounting is assertable on
    # traces (``report()['stage_bubbles']``).
    stage: int = 0
    # virtual-transport hook: called by wait() once the task is done, so a
    # VirtualPool can advance its clock to the waiter's sync point.
    on_wait: Optional[Callable[["Task"], None]] = None

    def run(self, clock: Clock = WALL_CLOCK):
        self.t_start = clock.now()
        try:
            self.result = self.fn()
        except BaseException as e:  # propagate to waiter
            self.error = e
        finally:
            self.t_end = clock.now()
            self.done.set()

    def wait(self, trace: Optional["Trace"] = None):
        """Block until the task is done and return its result (or raise
        its error).  With ``trace``, a wait that blocks is spanned there
        as ``wait.<kind>`` under the task's name: the producer it waited
        for (main thread)."""
        if trace is not None and not self.done.is_set():
            with trace.region(f"wait.{self.kind.value}", self.name):
                self.done.wait()
        else:
            self.done.wait()
        if self.on_wait is not None:
            self.on_wait(self)
        if self.error is not None:
            raise self.error
        return self.result


# the task the current pool thread runs, with the trace and thread name it
# records under — set by ``ThreadPool`` around ``Task.run`` so code deep in
# a transfer (the tiered stores) can open phases without holding a trace
_current = threading.local()


@contextlib.contextmanager
def running(task: "Task", trace: "Trace", thread: str):
    """Mark ``task`` as the one this thread runs, inside its profiler
    annotation (pool threads)."""
    _current.task = (task, trace, thread)
    try:
        with annotate(task.kind.value, task.name, task.nbytes):
            yield
    finally:
        _current.task = None


def phase(step: str, nbytes: int = 0):
    """Span one step of the transfer task running on this thread, as kind
    ``<task kind>.<step>`` under the task's name; ``nbytes`` is what the
    step moved.  Outside a pool task (or on a virtual-clock trace) it
    records nothing."""
    cur = getattr(_current, "task", None)
    if cur is None:
        return _NULL
    task, trace, thread = cur
    return trace.region(f"{task.kind.value}.{step}", task.name, nbytes,
                        thread)


@dataclass
class TraceEvent:
    kind: str
    name: str
    t_start: float
    t_end: float
    thread: str
    nbytes: int = 0
    extent: Optional[tuple] = None     # live (batch, len) of a KV payload
    stage: int = 0                     # pipeline-parallel stage (0 = single)
    # a span (``Trace.record``/``region``/``phase``): a wait, a phase, a
    # queue stretch or an engine step, never one of the four task kinds
    span: bool = False


def percentile(xs, q: float) -> float:
    """Linear-interpolation percentile (numpy's default method), stdlib
    only so trace tooling stays importable without the array stack.
    ``q`` in [0, 100]; empty input returns 0.0."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


def latency_summary(samples) -> Dict[str, float]:
    """p50/p95/p99 + mean/count for one latency series (seconds)."""
    xs = [float(x) for x in samples]
    return {
        "count": len(xs),
        "mean_s": sum(xs) / len(xs) if xs else 0.0,
        "p50_s": percentile(xs, 50),
        "p95_s": percentile(xs, 95),
        "p99_s": percentile(xs, 99),
    }


def _merged_busy(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    ivals = sorted(intervals)
    busy, cur_s, cur_e = 0.0, None, None
    for s, t in ivals:
        if cur_s is None:
            cur_s, cur_e = s, t
        elif s <= cur_e:
            cur_e = max(cur_e, t)
        else:
            busy += cur_e - cur_s
            cur_s, cur_e = s, t
    if cur_s is not None:
        busy += cur_e - cur_s
    return busy


def _attribute(labelled, lo: float, hi: float) -> Dict[str, float]:
    """Seconds of [lo, hi] each label held, from (start, end, label)
    intervals: where intervals overlap, the innermost (latest-started)
    one holds the time, so a wait nested in a compute counts as the wait.
    Time no interval covers is not returned."""
    ivals = [(max(s, lo), min(t, hi), lab) for s, t, lab in labelled
             if min(t, hi) > max(s, lo)]
    cuts = sorted({x for s, t, _ in ivals for x in (s, t)})
    out: Dict[str, float] = {}
    ivals.sort()
    k, open_ = 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(ivals) and ivals[k][0] <= a:
            open_.append(ivals[k])
            k += 1
        open_ = [iv for iv in open_ if iv[1] > a]
        if open_:
            lab = max(open_, key=lambda iv: iv[0])[2]
            out[lab] = out.get(lab, 0.0) + (b - a)
    return out


class _Region:
    """One span being timed on a live trace (see ``Trace.region``)."""

    __slots__ = ("trace", "kind", "name", "nbytes", "thread", "t0", "ann")

    def __init__(self, trace, kind, name, nbytes, thread):
        self.trace, self.kind, self.name = trace, kind, name
        self.nbytes, self.thread = nbytes, thread

    def __enter__(self):
        self.ann = annotate(self.kind, self.name, self.nbytes)
        self.ann.__enter__()
        self.t0 = self.trace.clock.now()
        return self

    def __exit__(self, *exc):
        t1 = self.trace.clock.now()
        self.ann.__exit__(*exc)
        self.trace.record(self.kind, self.name, self.t0, t1, self.thread,
                          self.nbytes)
        return False


class Trace:
    """Execution trace for the GPU-utilization analogue (Fig. 8) and the
    pipeline-overlap benchmarks.  Timestamps are relative to the clock's
    value at construction (0 for a fresh VirtualClock).

    A wall-clock (live) trace keeps its events in a ring of
    ``TRACE_CAPACITY`` (the oldest go first, counted by ``dropped``); a
    virtual-clock trace, simulated or loaded by ``from_json``, keeps them
    all.  ``seq`` counts every event ever added, so ``events_since(mark)``
    reads what arrived after an earlier ``seq`` without copying the ring.
    Spans (``region``, ``record``, ``phase``) are kept only on a live
    trace: a virtual-clock trace holds exactly the tasks, so its
    recordings stay byte-stable."""

    def __init__(self, clock: Clock = WALL_CLOCK):
        # spans beyond the task kinds are recorded on the wall clock only
        self.live = not isinstance(clock, VirtualClock)
        self._events = (collections.deque(maxlen=TRACE_CAPACITY)
                        if self.live else [])
        self._lock = threading.Lock()
        self.seq = 0
        self.clock = clock
        self.t0 = clock.now()
        # replayable context: schedulers/pools/engines stamp the knobs the
        # trace was recorded under (mode, warm, depth, pool_size, per-call
        # iteration counts, sim_bw, quant, kv_mode ...) so ``core.replay``
        # can rebuild the run without the model.  Serialized by to_json.
        self.meta: Dict[str, Any] = {}

    def _append(self, ev: TraceEvent):
        with self._lock:
            self._events.append(ev)
            self.seq += 1

    def add(self, task: Task, thread: str):
        self._append(TraceEvent(task.kind.value, task.name,
                                task.t_start - self.t0,
                                task.t_end - self.t0, thread,
                                task.nbytes, task.extent, task.stage))

    def record(self, kind: str, name: str, t_start: float, t_end: float,
               thread: str = "main", nbytes: int = 0):
        """Add a span of ``kind`` (never a task kind) between two
        readings of this trace's clock; a no-op on a virtual clock."""
        if self.live:
            self._append(TraceEvent(kind, name, t_start - self.t0,
                                    t_end - self.t0, thread, nbytes,
                                    span=True))

    def region(self, kind: str, name: str = "", nbytes: int = 0,
               thread: str = "main"):
        """Context manager spanning a block as one ``kind`` event (and a
        profiler annotation while JAX's profiler records).  ``thread`` is
        the executor the block runs on: the scheduler's waits and the
        engine's steps run on ``"main"``.  A no-op on a virtual clock."""
        if not self.live:
            return _NULL
        return _Region(self, kind, name, nbytes, thread)

    def events(self):
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        """Events the ring has let go (0 on a virtual-clock trace)."""
        return self.seq - len(self._events)

    def events_since(self, mark: int):
        """Events added after ``seq`` read ``mark`` (those still in the
        ring), oldest first, copying only them."""
        with self._lock:
            k = min(self.seq - mark, len(self._events))
            if k <= 0:
                return []
            out = [ev for _, ev in zip(range(k), reversed(self._events))]
        out.reverse()
        return out

    def work_events(self):
        """Every event but the spans: the tasks, and any kind a hand-built
        or simulated trace records as its work."""
        return [e for e in self.events() if not e.span]

    # -- (de)serialization --------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        """JSON-serializable snapshot: ``meta`` + every event, timestamps
        already relative to the trace origin.  Committable as a golden
        fixture; ``from_json`` rebuilds an equivalent trace for
        ``core.replay`` (extent tuples survive the list round-trip)."""
        events = []
        for e in self.events():
            ev = {"kind": e.kind, "name": e.name, "t_start": e.t_start,
                  "t_end": e.t_end, "thread": e.thread, "nbytes": e.nbytes,
                  "extent": None if e.extent is None else list(e.extent)}
            # the stage tag is emitted only when set, so single-stage
            # fixtures recorded before pipeline parallelism stay byte-stable
            if e.stage:
                ev["stage"] = e.stage
            if e.span:
                ev["span"] = True
            events.append(ev)
        return {"meta": dict(self.meta), "events": events}

    @classmethod
    def from_json(cls, d: "Dict[str, Any] | str") -> "Trace":
        """Rebuild a trace from ``to_json`` output (dict or JSON string).
        The result reads back identically (events/meta/report); its clock
        is a fresh ``VirtualClock`` so t0 is 0, matching the already-
        relative recorded timestamps."""
        if isinstance(d, str):
            d = json.loads(d)
        unknown = set(d) - {"meta", "events"}
        if unknown:
            raise ValueError(f"unknown Trace JSON key(s) {sorted(unknown)}")
        tr = cls(clock=VirtualClock())
        tr.meta = dict(d.get("meta", {}))
        for ev in d.get("events", []):
            ext = ev.get("extent")
            tr._append(TraceEvent(
                ev["kind"], ev["name"], ev["t_start"], ev["t_end"],
                ev.get("thread", ""), ev.get("nbytes", 0),
                None if ext is None else tuple(ext),
                ev.get("stage", 0), ev.get("span", False)))
        return tr

    def span(self) -> float:
        """First start to last end of the work events (spans excluded: a
        queue span starts before its task, a step span wraps its tasks)."""
        evs = self.work_events()
        if not evs:
            return 0.0
        return max(e.t_end for e in evs) - min(e.t_start for e in evs)

    def busy_time(self, kind: str) -> float:
        """Merged-interval busy seconds for one task kind."""
        return _merged_busy((e.t_start, e.t_end) for e in self.events()
                            if e.kind == kind)

    def thread_busy(self, thread: str = "main") -> float:
        """Merged-interval seconds one executor thread spent running
        tasks (the spans of waits, phases and steps are not tasks)."""
        return _merged_busy((e.t_start, e.t_end)
                            for e in self.work_events() if e.thread == thread)

    def main_split(self) -> Dict[str, Any]:
        """Where the main thread's time went, from its first to its last
        event: layer compute, each wait by the producer it waited for
        (``WAIT_KINDS``), and the rest, host code (the scheduler, embeds,
        sampling, admission bookkeeping).  Seconds and shares; the shares
        sum to 1 over a non-empty window.  A wait nested inside a compute
        (a MoE layer waiting for its experts) counts as the wait."""
        keys = (TaskType.COMPUTE.value,) + WAIT_KINDS
        evs = [e for e in self.events()
               if e.thread == "main" and e.kind in keys]
        lo = min((e.t_start for e in evs), default=0.0)
        hi = max((e.t_end for e in evs), default=0.0)
        secs = dict.fromkeys(keys, 0.0)
        secs.update(_attribute(((e.t_start, e.t_end, e.kind) for e in evs),
                               lo, hi))
        window = hi - lo
        secs["host"] = max(0.0, window - sum(secs.values()))
        return {"window_s": window, "seconds": secs,
                "share": {k: (v / window if window > 0 else 0.0)
                          for k, v in secs.items()}}

    def busy_fraction(self, kind: str = "compute") -> float:
        """Fraction of the makespan the given task kind was executing —
        the paper's 'GPU utilization' proxy."""
        span = self.span()
        if span <= 0:
            return 0.0
        return self.busy_time(kind) / max(1e-9, span)

    def bytes_moved(self, kind: str, name_prefix: str = "") -> int:
        """Sum of per-event payload sizes for one task kind (0-byte events
        are tasks whose submitter didn't know the size).  ``name_prefix``
        filters events, e.g. 'w[u[0][0]/exp' for one MoE layer's expert
        loads — the routed-union invariant is asserted on this."""
        return sum(e.nbytes for e in self.events()
                   if e.kind == kind and e.name.startswith(name_prefix))

    def report(self) -> Dict[str, Any]:
        """Pipeline instrumentation (Fig. 8/9 analogue): per-kind busy
        time, counts and bytes (the four task kinds always; span kinds as
        recorded), the main thread's time split into compute, waits by
        producer and host code (``main_split``), and the events the ring
        has let go (``dropped``: nonzero means the figures cover only the
        newest ``TRACE_CAPACITY`` events).  Host-clock spans: the device's
        own busy share comes from a profiler trace."""
        evs = self.events()
        span = self.span()
        per_kind = {}
        # the four task types always get a bucket (zeroed when absent);
        # kinds the schema doesn't know (hand-built or future traces) get
        # their own bucket instead of silently vanishing from the report
        kinds = [t.value for t in TaskType]
        kinds += sorted({e.kind for e in evs} - set(kinds))
        for kind in kinds:
            sub = [e for e in evs if e.kind == kind]
            ivals = [(e.t_start, e.t_end) for e in sub]
            busy = _merged_busy(ivals)
            nbytes = sum(e.nbytes for e in sub)
            per_kind[kind] = {
                "busy_s": busy,
                "count": len(ivals),
                "busy_frac": busy / span if span > 0 else 0.0,
                "bytes": nbytes,
                # measured link bandwidth for this task kind (0 when no
                # byte-accounted events) — the observable AdaptiveDepth's
                # bandwidth feedback EWMAs per step
                "bw_Bps": nbytes / busy if busy > 0 else 0.0,
            }
        out = {
            "span_s": span,
            "per_kind": per_kind,
            "main": self.main_split(),
            "dropped": self.dropped,
        }
        # pipeline-parallel fill/drain accounting: when any event carries a
        # stage tag, each stage gets a bucket measuring how long it idles
        # before its first compute (fill — upstream stages haven't produced
        # an activation yet) and after its last (drain — downstream stages
        # are still flushing).  Single-stage traces skip the bucket.
        evs = [e for e in evs if not e.span]
        if any(e.stage for e in evs):
            t_lo = min(e.t_start for e in evs)
            t_hi = max(e.t_end for e in evs)
            stage_bubbles = {}
            for s in sorted({e.stage for e in evs}):
                sub = [e for e in evs if e.stage == s]
                comp = [e for e in sub if e.kind == TaskType.COMPUTE.value]
                busy = _merged_busy((e.t_start, e.t_end) for e in comp)
                if comp:
                    fill = min(e.t_start for e in comp) - t_lo
                    drain = t_hi - max(e.t_end for e in comp)
                else:
                    fill, drain = t_hi - t_lo, 0.0
                stage_bubbles[s] = {
                    "fill_s": max(0.0, fill),
                    "drain_s": max(0.0, drain),
                    "busy_s": busy,
                    "idle_s": max(0.0, (t_hi - t_lo) - busy),
                    "span_s": t_hi - t_lo,
                }
            out["stage_bubbles"] = stage_bubbles
        # request-latency percentiles: workload drivers
        # (serving.workload.run_trace / TrafficSim) stamp per-request
        # series into meta["latency"] = {"ttft": [...], "tbt": [...],
        # "e2e": [...]} (seconds); the report summarizes each so p99
        # TTFT is a first-class trace observable next to busy fractions
        lat = self.meta.get("latency")
        if lat:
            out["latency"] = {name: latency_summary(xs)
                              for name, xs in sorted(lat.items())}
        return out
