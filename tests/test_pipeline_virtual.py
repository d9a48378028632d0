"""PipelineScheduler ordering invariants on the virtual clock.

Unlike tests/test_pipeline.py (real threads + sleeps), these drive the
real scheduler through ``VirtualPool``: execution is single-threaded and
deterministic, timestamps are virtual, and every assertion is on Trace
event order — the invariants hold on every run by construction, not
probabilistically.
"""
import pytest

from fake_model import (COSTS, DRAFT_NAME, FakeMoEModel, run_virtual,
                        run_virtual_moe, run_virtual_spec)
from repro.core.tasks import WAIT_KINDS, TaskType


def _by_name(trace):
    """name -> list of events in submission order (w[j]/c[i,j] repeat
    across iterations; kv/sv names are unique per (i, j))."""
    out = {}
    for e in trace.events():
        out.setdefault(e.name, []).append(e)
    return out


def _one(ev_map, name):
    evs = ev_map[name]
    assert len(evs) == 1, f"{name} expected once, got {len(evs)}"
    return evs[0]


@pytest.mark.parametrize("mode", ["performance", "memory", "sequential"])
def test_virtual_run_is_deterministic(mode):
    runs = []
    for _ in range(2):
        model, trace, outs = run_virtual(mode, n_layers=3, iters=3)
        assert outs == [model.n] * 3
        runs.append(([(e.kind, e.name, e.t_start, e.t_end, e.thread)
                      for e in trace.events()], list(model.calls)))
    assert runs[0] == runs[1], "virtual schedule not reproducible"


@pytest.mark.parametrize("mode", ["performance", "memory", "sequential"])
def test_all_tasks_execute_in_every_mode_virtual(mode):
    model, trace, outs = run_virtual(mode, n_layers=3, iters=2)
    ev = _by_name(trace)
    for i in range(2):
        for j in range(model.n):
            assert [e for e in ev[f"c[{i},{j}]"]], (i, j)
            if model.is_mha(j):
                assert f"kv[{i},{j}]" in ev
                assert f"sv[{i},{j}]" in ev


def test_performance_mode_preloads_next_layer_during_compute():
    """Performance invariant (§3.1.2): while layer j computes in iteration
    i, layer j+1's weight load is already in flight — the load's virtual
    interval overlaps the compute's."""
    model, trace, _ = run_virtual("performance", n_layers=4, iters=2)
    ev = _by_name(trace)
    n = model.n
    for i in range(2):
        for j in range(n - 1):
            c = _one(ev, f"c[{i},{j}]")
            loads = ev[f"w[{j + 1}]"]
            assert any(w.t_start < c.t_end and w.t_end > c.t_start
                       for w in loads), \
                f"w[{j+1}] not in flight during c[{i},{j}]"


def test_performance_mode_weight_load_starts_at_compute_start():
    """Stronger form: the preload is submitted *before* the compute task
    runs, so its virtual start is <= the compute's start."""
    model, trace, _ = run_virtual("performance", n_layers=3, iters=1)
    ev = _by_name(trace)
    for j in range(model.n - 1):
        c = _one(ev, f"c[0,{j}]")
        w = ev[f"w[{j + 1}]"][0]
        assert w.t_start <= c.t_start


def test_kv_save_completes_before_next_iteration_load_all_modes():
    """KV-save(i-1, j) must complete before KV-load(i, j) starts — the
    paper's advanced-by-one-layer completion check (§3.2.1)."""
    for mode in ("performance", "memory", "sequential"):
        model, trace, _ = run_virtual(mode, n_layers=3, iters=3)
        ev = _by_name(trace)
        for i in range(1, 3):
            for j in range(model.n):
                if not model.is_mha(j):
                    continue
                save = _one(ev, f"sv[{i - 1},{j}]")
                load = _one(ev, f"kv[{i},{j}]")
                assert save.t_end <= load.t_start, \
                    (mode, i, j, save.t_end, load.t_start)


def test_memory_mode_holds_single_layer_resident():
    """Memory invariant: layer j+1's weight load starts only after layer
    j's compute finished (previous layer's memory released) — never two
    weight buffers in flight."""
    model, trace, _ = run_virtual("memory", n_layers=3, iters=2)
    ev = _by_name(trace)
    for i in range(2):
        for j in range(model.n - 1):
            c = _one(ev, f"c[{i},{j}]")
            w = ev[f"w[{j + 1}]"][i]          # i-th load = iteration i
            assert w.t_start >= c.t_end, \
                f"memory mode preloaded w[{j+1}] during c[{i},{j}]"
    # weight loads never overlap each other either
    loads = sorted([e for e in trace.events() if e.kind == "weight_load"],
                   key=lambda e: e.t_start)
    for a, b in zip(loads, loads[1:]):
        assert b.t_start >= a.t_end


def test_memory_mode_syncs_kv_save():
    """Memory invariant: each KV-save completes before the pipeline moves
    on (next task on the main thread starts after the save ends)."""
    model, trace, _ = run_virtual("memory", n_layers=3, iters=2)
    ev = _by_name(trace)
    for i in range(2):
        for j in range(model.n):
            if not model.is_mha(j):
                continue
            save = _one(ev, f"sv[{i},{j}]")
            nxt = (f"c[{i},{j + 1}]" if j + 1 < model.n
                   else (f"c[{i + 1},0]" if i + 1 < 2 else None))
            if nxt is None:
                continue
            nxt_ev = _one(ev, nxt)
            assert save.t_end <= nxt_ev.t_start, (i, j)


def test_sequential_mode_fully_serializes():
    """Sequential baseline: no two task intervals overlap at all (FlexGen
    device-level sync)."""
    model, trace, _ = run_virtual("sequential", n_layers=3, iters=2)
    evs = sorted(trace.events(), key=lambda e: (e.t_start, e.t_end))
    for a, b in zip(evs, evs[1:]):
        assert b.t_start >= a.t_end, (a.name, b.name)


def test_performance_beats_sequential_on_virtual_makespan():
    """The pipeline's raison d'etre, asserted on virtual time: overlapping
    transfers with compute strictly shrinks the makespan."""
    _, t_perf, _ = run_virtual("performance", n_layers=4, iters=3)
    _, t_seq, _ = run_virtual("sequential", n_layers=4, iters=3)
    assert t_perf.span() < t_seq.span()
    assert (t_perf.busy_fraction("compute")
            > t_seq.busy_fraction("compute"))


# ---------------------------------------------------------------------------
# Warm pipeline: cross-call ("cross decode step") preloading
# ---------------------------------------------------------------------------


def test_warm_pipeline_preloads_next_call_first_weight():
    """Warm invariant (the serving tentpole): with warm=True, two
    single-iteration generate() calls behave like one continuous pipeline
    — call t+1's w[0] load is in flight during call t's tail compute, so
    call t+1 starts with zero cold-start weight bubble."""
    model, trace, _ = run_virtual("performance", n_layers=2, iters=1,
                                  warm=True, calls=2)
    ev = _by_name(trace)
    n = model.n
    tail_c = _one(ev, f"c[0,{n - 1}]")         # call 0's tail compute
    w0_loads = ev["w[0]"]
    # one per call plus the final call's dangling preload for a call that
    # never arrives (steady-state serving amortizes that single load)
    assert len(w0_loads) == 3
    preload = w0_loads[1]                      # call 1's w[0]
    assert preload.t_start <= tail_c.t_start, \
        "cross-step w[0] preload not submitted before the tail compute"
    assert preload.t_start < tail_c.t_end and \
        preload.t_end > tail_c.t_start, \
        "cross-step w[0] preload does not overlap the tail compute"
    # call 1's first compute starts without waiting a full weight load:
    # the preload completed (or mostly completed) during call 0's tail.
    c10 = _one(ev, f"c[1,0]")
    assert c10.t_start >= preload.t_end        # sync honored
    assert c10.t_start - tail_c.t_end < COSTS[TaskType.WEIGHT_LOAD], \
        "warm call still paid a full cold w[0] load after the tail"


def test_warm_pipeline_preloads_next_call_first_kv():
    """The first KV load of call t+1 is likewise pre-submitted during
    call t's tail compute, after call t's save of the same layer."""
    model, trace, _ = run_virtual("performance", n_layers=2, iters=1,
                                  warm=True, calls=2)
    ev = _by_name(trace)
    n = model.n
    tail_c = _one(ev, f"c[0,{n - 1}]")
    kv_pre = _one(ev, "kv[1,0]")               # call 1's first KV load
    sv_prev = _one(ev, "sv[0,0]")
    assert kv_pre.t_start <= tail_c.t_start
    assert sv_prev.t_end <= kv_pre.t_start, \
        "preloaded KV overtook the previous call's save of the same layer"


def test_warm_beats_cold_on_virtual_makespan():
    """The bubble being shaved is real virtual time: N warm single-token
    calls finish strictly earlier than N cold ones."""
    _, t_warm, _ = run_virtual("performance", n_layers=3, iters=1,
                               warm=True, calls=4)
    _, t_cold, _ = run_virtual("performance", n_layers=3, iters=1,
                               warm=False, calls=4)
    assert t_warm.span() < t_cold.span()


def test_warm_pipeline_tokens_match_cold():
    """Warm is a scheduling change only: outputs are identical."""
    m_w, _, outs_w = run_virtual("performance", n_layers=3, iters=2,
                                 warm=True, calls=3)
    m_c, _, outs_c = run_virtual("performance", n_layers=3, iters=2,
                                 warm=False, calls=3)
    assert outs_w == outs_c == [m_w.n] * 2


def test_warm_disabled_for_memory_and_sequential():
    """Memory mode's single-layer-residency (and sequential's full
    serialization) forbid cross-call preloads: warm is a no-op there."""
    from repro.core.pipeline import PipelineScheduler
    for mode in ("memory", "sequential"):
        assert not PipelineScheduler(4, mode, warm=True).warm


# ---------------------------------------------------------------------------
# Depth-D preload window
# ---------------------------------------------------------------------------


def _paired_residency(model, trace):
    """[(position, load_event, release_t)] pairing each weight load with
    the compute that consumes it (the k-th w[j] event belongs to global
    iteration k; release = that compute's end).  Dangling warm preloads
    (no compute ever consumed them) are skipped."""
    ev = _by_name(trace)
    out = []
    for j in range(model.n):
        for k, w in enumerate(ev.get(f"w[{j}]", [])):
            name = f"c[{k},{j}]"
            if name in ev:
                out.append((k * model.n + j, w, _one(ev, name).t_end))
    return sorted(out, key=lambda p: p[0])


def test_depth_window_loads_start_in_stack_order():
    """No preload overtakes an unevicted resident layer: weight loads
    start in schedulable-position order even when ``depth`` of them are
    in flight across the transfer workers."""
    model, trace, _ = run_virtual("performance", n_layers=4, iters=2,
                                  depth=3)
    starts = [w.t_start for _, w, _ in _paired_residency(model, trace)]
    assert starts == sorted(starts)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_depth_window_bounds_weight_residency(depth):
    """At most depth+1 weight buffers are ever resident (interval = load
    start -> consuming compute's end, when the layer is released), and a
    deep window actually reaches that bound — the depth knob is real."""
    model, trace, _ = run_virtual("performance", n_layers=4, iters=3,
                                  depth=depth)
    events = []
    for _, w, release in _paired_residency(model, trace):
        events.append((w.t_start, 1))
        events.append((release, -1))
    cur = peak = 0
    for _, delta in sorted(events):      # (t, -1) sorts before (t, +1)
        cur += delta
        peak = max(peak, cur)
    assert peak <= depth + 1, f"depth {depth} held {peak} layers resident"
    assert peak == depth + 1, f"depth {depth} window never filled ({peak})"


def test_depth_tokens_and_call_order_match_depth1():
    """Depth is a scheduling change only: outputs and the compute call
    sequence are identical at every depth."""
    ref, _, ref_outs = run_virtual("performance", n_layers=3, iters=2,
                                   depth=1)
    ref_computes = [c for c in ref.calls if c[0] == "compute"]
    for depth in (2, 3, 5):
        m, _, outs = run_virtual("performance", n_layers=3, iters=2,
                                 depth=depth)
        assert outs == ref_outs == [m.n] * 2
        assert [c for c in m.calls if c[0] == "compute"] == ref_computes


def test_kv_save_before_load_holds_at_depth():
    """The save(i-1,j)-before-load(i,j) invariant survives deep windows:
    a KV preload is deferred until the save it trails has been issued
    (structural n-1 bound) and completed (non-blocking skip)."""
    model, trace, _ = run_virtual("performance", n_layers=3, iters=3,
                                  depth=4)
    ev = _by_name(trace)
    for i in range(1, 3):
        for j in range(model.n):
            if not model.is_mha(j):
                continue
            save = _one(ev, f"sv[{i - 1},{j}]")
            for load in ev[f"kv[{i},{j}]"]:
                assert save.t_end <= load.t_start, (i, j)


def test_warm_depth2_beats_warm_depth1_beats_cold():
    """The acceptance-criterion shape on the virtual clock: a deeper
    warm window strictly shrinks the makespan of a decode-step sequence
    (weight-dominated costs; 3 virtual transfer slots)."""
    spans = {}
    for depth in (1, 2, 3):
        _, t, _ = run_virtual("performance", n_layers=3, iters=1,
                              warm=True, calls=4, depth=depth)
        spans[depth] = t.span()
    _, t_cold, _ = run_virtual("performance", n_layers=3, iters=1,
                               warm=False, calls=4, depth=1)
    assert spans[2] < spans[1] < t_cold.span()
    assert spans[3] <= spans[2]


def test_warm_depth_window_preloads_next_call_layers():
    """With depth=3 the tail of call t has the next call's first THREE
    weight loads in flight before the tail compute finishes — not just
    w[0]."""
    model, trace, _ = run_virtual("performance", n_layers=3, iters=1,
                                  warm=True, calls=2, depth=3)
    ev = _by_name(trace)
    tail_c = _one(ev, f"c[0,{model.n - 1}]")
    for j in range(3):
        loads = ev[f"w[{j}]"]
        assert len(loads) >= 2, f"w[{j}] not preloaded for call 1"
        assert loads[1].t_start <= tail_c.t_end, \
            f"w[{j}] preload missed call 0's tail window"


def test_drop_kv_preloads_discards_all_depth_preloads():
    """depth > 1 leaves SEVERAL cross-call KV preloads pending at a warm
    call's tail; drop_kv_preloads must discard all of them, and the next
    call must reload fresh while still honoring save-before-load."""
    from repro.core.pipeline import PipelineScheduler, VirtualPool
    from fake_model import FakeModel, cost_fn
    model = FakeModel(3)
    pool = VirtualPool(3, cost_fn=cost_fn)
    sched = PipelineScheduler(model.n, "performance", pool=pool,
                              trace=pool.trace, warm=True, depth=4)
    outs = sched.generate(model, lambda i: 0, 1)
    assert len(sched._kv_tasks) >= 2, \
        "depth-4 warm tail should leave multiple KV preloads in flight"
    sched.drop_kv_preloads()
    assert not sched._kv_tasks
    outs2 = sched.generate(model, lambda i: 0, 1)
    assert outs2 == outs
    sched.shutdown()
    ev = _by_name(pool.trace)
    for j in range(model.n):
        if not model.is_mha(j):
            continue
        save = _one(ev, f"sv[0,{j}]")
        loads = ev[f"kv[1,{j}]"]       # dropped preload + fresh reload
        assert loads and all(save.t_end <= l.t_start for l in loads), j


def _residency_peak(model, trace, positions=None):
    """Peak simultaneously-resident weight buffers over the paired
    load->release intervals (optionally restricted to a set of
    schedulable positions)."""
    events = []
    for pos, w, release in _paired_residency(model, trace):
        if positions is not None and pos not in positions:
            continue
        events.append((w.t_start, 1))
        events.append((release, -1))
    cur = peak = 0
    for _, delta in sorted(events):      # (t, -1) sorts before (t, +1)
        cur += delta
        peak = max(peak, cur)
    return peak


def test_set_depth_resizes_window_between_calls():
    """The AdaptiveDepth hook: ``set_depth`` between warm generate()
    calls re-sizes the window — growth takes effect immediately, and
    after a shrink the steady state honors the NEW depth+1 residency
    bound (in-flight wide-window loads drain through the transition
    call)."""
    from fake_model import FakeModel, cost_fn
    from repro.core.pipeline import PipelineScheduler, VirtualPool
    model = FakeModel(3)                       # 6 schedulable positions
    pool = VirtualPool(6, cost_fn=cost_fn)
    sched = PipelineScheduler(model.n, "performance", pool=pool,
                              trace=pool.trace, warm=True, depth=3)
    outs = [sched.generate(model, lambda i: 0, 1)]
    assert sched.set_depth(1) == 1
    outs.append(sched.generate(model, lambda i: 0, 1))   # transition call
    outs.append(sched.generate(model, lambda i: 0, 1))   # steady at d=1
    sched.shutdown()
    n = model.n
    # whole run never exceeded the WIDE bound...
    assert _residency_peak(model, pool.trace) <= 3 + 1
    # ...and the steady-state call at depth 1 honors the narrow one
    # (its loads: positions 2n..3n-1 plus the next call's dangling
    # preload, which _paired_residency drops as unconsumed)
    steady = set(range(2 * n, 3 * n))
    assert _residency_peak(model, pool.trace, steady) <= 1 + 1
    assert outs[0] == outs[1] == outs[2]       # scheduling change only


def test_adaptive_depth_scheduler_pressure_run():
    """The acceptance-criterion shape on the virtual clock: drive the
    scheduler across warm calls while an AdaptiveDepth-style controller
    shrinks the window under ramping pressure (3 -> 2 -> 1); every
    post-shrink steady call stays within its depth+1 residency bound and
    tokens never change."""
    from fake_model import FakeModel, cost_fn
    from repro.core.pipeline import PipelineScheduler, VirtualPool
    model = FakeModel(3)
    pool = VirtualPool(6, cost_fn=cost_fn)
    sched = PipelineScheduler(model.n, "performance", pool=pool,
                              trace=pool.trace, warm=True, depth=3)
    outs = []
    schedule = [3, 3, 2, 2, 1, 1]              # depth per decode step
    for d in schedule:
        sched.set_depth(d)
        outs.append(sched.generate(model, lambda i: 0, 1))
    sched.shutdown()
    assert all(o == outs[0] for o in outs)
    n = model.n
    assert _residency_peak(model, pool.trace) <= max(schedule) + 1
    for call, d in enumerate(schedule[1:], start=1):
        # calls whose PRELOADS were issued at depth d (the previous
        # call's tail ran after set_depth(d)) must fit d+1
        if schedule[call - 1] == d:
            span = set(range(call * n, (call + 1) * n))
            assert _residency_peak(model, pool.trace, span) <= d + 1, \
                (call, d)


def test_moe_union_invariant_holds_at_depth():
    """Deep weight windows don't disturb routed-union expert streaming:
    per (iteration, MoE unit) exactly the routed union loads, once."""
    model, trace, _ = run_virtual_moe("performance", n_layers=2, iters=2,
                                      depth=3)
    for i in range(2):
        for j in range(model.n):
            if not model.is_moe(j):
                continue
            loaded = [e for (ii, jj, e) in model.expert_loads
                      if (ii, jj) == (i, j)]
            assert loaded == model.routed(i, j), (i, j, loaded)


# ---------------------------------------------------------------------------
# Speculative draft-then-verify schedule
# ---------------------------------------------------------------------------


def test_spec_prime_streams_weights_during_draft():
    """The speculative overlap, on the virtual clock: a cold step's
    ``prime_weights`` pre-submits the verify pass's first ``depth``
    weight loads, and their transfer intervals overlap the draft's
    main-thread compute — the otherwise-idle link streams the target
    while the draft proposes."""
    model, trace, steps = run_virtual_spec(iters=3, depth=2)
    ev = _by_name(trace)
    d0, d1 = steps[0]["draft"]
    assert steps[0]["primed"] == 2
    for j in range(2):
        w = ev[f"w[{j}]"][0]
        assert w.t_start <= d0, f"w[{j}] primed after the draft started"
        assert w.t_start < d1 and w.t_end > d0, \
            f"w[{j}] does not stream during the draft compute"
    # a warm tail already has the next verify's window in flight:
    # priming is a no-op on every later step
    assert [s["primed"] for s in steps[1:]] == [0, 0]
    assert all(s["outs"] == [model.n] for s in steps)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_spec_residency_bound_holds_at_depth(depth):
    """Priming the verify pass never over-fills the window: across a
    run of speculative steps at most depth+1 weight buffers are ever
    resident, same bound as plain decode."""
    model, trace, _ = run_virtual_spec(iters=4, depth=depth)
    peak = _residency_peak(model, trace)
    assert 0 < peak <= depth + 1, \
        f"spec steps at depth {depth} held {peak} layers resident"


def test_spec_reject_drops_stale_kv_preloads():
    """A rejection invalidates rows the warm tail's KV preloads already
    priced: the engines drain saves and drop the preloads, and the next
    step's fresh reload still honors save-before-load.  Outputs are
    untouched — rejection is KV/scheduling bookkeeping only."""
    model, trace, steps = run_virtual_spec(iters=3, depth=2, reject=(1,))
    ev = _by_name(trace)
    for j in range(model.n):
        if not model.is_mha(j):
            continue
        save = _one(ev, f"sv[1,{j}]")
        loads = ev[f"kv[2,{j}]"]
        assert loads, f"kv[2,{j}] never reloaded after the drop"
        assert all(save.t_end <= l.t_start for l in loads), j
    # the warm tail's preload of kv[2,0] ran before the drop; the fresh
    # reload is a second event — both on the trace
    assert len(ev["kv[2,0]"]) == 2
    # and a no-reject run issues it exactly once
    _, t2, _ = run_virtual_spec(iters=3, depth=2)
    assert len(_by_name(t2)["kv[2,0]"]) == 1
    assert [s["outs"] for s in steps] == [[model.n]] * 3


def test_spec_schedule_matches_plain_decode_structure():
    """The verify pass is ONE trip through the layer stack: per step the
    scheduler runs the same w/kv/sv/c task sequence as a plain warm
    decode step, with only the draft COMPUTE events added."""
    _, trace_s, _ = run_virtual_spec(iters=3, depth=1)
    _, trace_p, _ = run_virtual("performance", n_layers=3, iters=1,
                                warm=True, calls=3, depth=1)
    named = lambda t: sorted(e.name for e in t.events()
                             if not e.name.startswith(DRAFT_NAME))
    assert named(trace_s) == named(trace_p)
    drafts = [e for e in trace_s.events() if e.name.startswith(DRAFT_NAME)]
    assert len(drafts) == 3
    assert all(e.kind == "compute" for e in drafts)


# ---------------------------------------------------------------------------
# MoE routed-union expert streaming
# ---------------------------------------------------------------------------


def test_moe_union_loads_only_routed_experts():
    """Only the routed union's experts are loaded per (iteration, MoE
    unit) — never the whole bank — and each exactly once."""
    model, trace, _ = run_virtual_moe("performance", n_layers=2, iters=2)
    for i in range(2):
        for j in range(model.n):
            if not model.is_moe(j):
                continue
            loaded = [e for (ii, jj, e) in model.expert_loads
                      if (ii, jj) == (i, j)]
            assert loaded == model.routed(i, j), (i, j, loaded)
            assert len(loaded) < model.n_experts       # union < bank


def test_moe_union_load_bytes_below_bank_bytes():
    """The acceptance-criterion form: expert WEIGHT_LOAD bytes on the
    trace equal union-size * per-expert bytes — strictly below the
    whole-bank volume a naive loader would move."""
    model, trace, _ = run_virtual_moe("performance", n_layers=2, iters=2)
    n_union = sum(len(model.routed(i, j)) for i in range(2)
                  for j in range(model.n) if model.is_moe(j))
    n_bank = sum(model.n_experts for i in range(2)
                 for j in range(model.n) if model.is_moe(j))
    got = trace.bytes_moved("weight_load", "exp[")
    assert got == n_union * FakeMoEModel.EXPERT_NBYTES
    assert got < n_bank * FakeMoEModel.EXPERT_NBYTES


def test_moe_expert_loads_overlap_unit_compute():
    """Expert loads are submitted from inside the MoE unit's compute
    (after the gate) and stream while it runs — their intervals start
    within the compute window, not after it."""
    model, trace, _ = run_virtual_moe("performance", n_layers=2, iters=1)
    ev = _by_name(trace)
    for j in range(model.n):
        if not model.is_moe(j):
            continue
        c = _one(ev, f"c[0,{j}]")
        for e in model.routed(0, j):
            w = _one(ev, f"exp[{j}][{e}]")
            assert c.t_start <= w.t_start <= c.t_end, (j, e)


def test_trace_report_accounts_per_kind_bytes_and_extents():
    """Per-kind byte totals on the trace are exact: every task kind's
    reported bytes equal count x the model's per-payload constant —
    including KV_SAVE, which used to go unaccounted (the quantized-KV
    accounting satellite) — and KV_LOAD events carry the live extent."""
    from fake_model import KV_EXTENT, NBYTES
    model, trace, _ = run_virtual("performance", n_layers=3, iters=3)
    rep = trace.report()
    for kind in (TaskType.WEIGHT_LOAD, TaskType.KV_LOAD, TaskType.KV_SAVE):
        pk = rep["per_kind"][kind.value]
        assert pk["count"] > 0
        assert pk["bytes"] == pk["count"] * NBYTES[kind], kind
        # measured per-kind bandwidth is derivable from the same trace
        assert pk["bw_Bps"] == pytest.approx(pk["bytes"] / pk["busy_s"])
    kv_loads = [e for e in trace.events() if e.kind == "kv_load"]
    assert kv_loads and all(e.extent == KV_EXTENT for e in kv_loads)
    weight = [e for e in trace.events() if e.kind == "weight_load"]
    assert all(e.extent is None for e in weight)


def test_trace_report_accounts_busy_time():
    model, trace, _ = run_virtual("sequential", n_layers=2, iters=1)
    rep = trace.report()
    # sequential: span is exactly the sum of all task durations
    n_mha = sum(1 for j in range(model.n) if model.is_mha(j))
    expect = (model.n * (COSTS[TaskType.WEIGHT_LOAD]
                         + COSTS[TaskType.COMPUTE])
              + n_mha * (COSTS[TaskType.KV_LOAD] + COSTS[TaskType.KV_SAVE]))
    assert abs(rep["span_s"] - expect) < 1e-9
    assert abs(rep["per_kind"]["compute"]["busy_s"]
               - model.n * COSTS[TaskType.COMPUTE]) < 1e-9
    # a virtual trace holds tasks only: the main thread's window splits
    # into compute and the rest (no wait spans), summing to the whole
    main = rep["main"]
    assert main["seconds"]["host"] > 0
    assert all(main["seconds"][k] == 0.0 for k in WAIT_KINDS)
    assert abs(sum(main["share"].values()) - 1.0) < 1e-9
    assert abs(main["seconds"]["compute"]
               - model.n * COSTS[TaskType.COMPUTE]) < 1e-9


def test_drop_kv_preloads_reraises_a_failed_preload():
    """A discarded preload's RESULT is dropped, its exception is not: a
    failed transfer surfaces at drop time (after every other in-flight
    load has been waited out) instead of vanishing."""
    from repro.core.pipeline import PipelineScheduler, VirtualPool
    from fake_model import FakeModel, cost_fn

    class FailingPreloads(FakeModel):
        def load_kv(self, i, j):
            if i >= 1:                       # the next call's preloads
                raise RuntimeError("kv transfer failed")
            return super().load_kv(i, j)

    model = FailingPreloads(3)
    pool = VirtualPool(3, cost_fn=cost_fn)
    sched = PipelineScheduler(model.n, "performance", pool=pool,
                              trace=pool.trace, warm=True, depth=4)
    sched.generate(model, lambda i: 0, 1)
    assert sched._kv_tasks
    with pytest.raises(RuntimeError, match="kv transfer failed"):
        sched.drop_kv_preloads()
    assert not sched._kv_tasks
    sched.shutdown()
