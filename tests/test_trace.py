"""Trace schema edge cases + JSON round-trip.

``Trace.report()`` / ``bytes_moved()`` / ``busy_fraction()`` feed the
benchmark harness and the AdaptiveDepth feedback loop, so the degenerate
inputs — empty trace, zero-duration events, unknown task kinds, byte
totals with zero busy time — must yield zeros, not ZeroDivisionErrors.
The JSON round-trip half pins the golden-fixture schema ``core.replay``
consumes (meta + events, extents surviving the tuple<->list hop).
"""
import json

import pytest

from repro.core.tasks import (WAIT_KINDS, Task, TaskType, Trace, TraceEvent,
                              VirtualClock)


def _trace(events=()):
    tr = Trace(clock=VirtualClock())
    for e in events:
        tr._append(e)
    return tr


def _ev(kind="compute", name="c[0,0]", t0=0.0, t1=1.0, thread="main",
        nbytes=0, extent=None):
    return TraceEvent(kind, name, t0, t1, thread, nbytes, extent)


# ---------------------------------------------------------------------------
# report() / bytes_moved() edge cases
# ---------------------------------------------------------------------------


def test_empty_trace_report_is_all_zero():
    rep = _trace().report()
    assert rep["span_s"] == 0.0
    main = rep["main"]
    assert main["window_s"] == 0.0
    assert set(main["seconds"]) == {"compute", *WAIT_KINDS, "host"}
    assert all(v == 0.0 for v in main["seconds"].values())
    assert all(v == 0.0 for v in main["share"].values())
    for kind in (t.value for t in TaskType):
        pk = rep["per_kind"][kind]
        assert pk == {"busy_s": 0.0, "count": 0, "busy_frac": 0.0,
                      "bytes": 0, "bw_Bps": 0.0}


def test_empty_trace_span_and_busy():
    tr = _trace()
    assert tr.span() == 0.0
    assert tr.busy_time("compute") == 0.0
    assert tr.busy_fraction() == 0.0
    assert tr.bytes_moved("weight_load") == 0


def test_zero_duration_events_no_division_error():
    # a 0-s transfer that still moved bytes: busy time is 0, so the
    # measured bandwidth must clamp to 0.0 instead of dividing by zero
    tr = _trace([_ev(kind="weight_load", name="w[0]", t0=1.0, t1=1.0,
                     thread="pool-0", nbytes=4096)])
    rep = tr.report()
    pk = rep["per_kind"]["weight_load"]
    assert pk["busy_s"] == 0.0
    assert pk["count"] == 1
    assert pk["bytes"] == 4096
    assert pk["bw_Bps"] == 0.0              # the divide-by-zero guard
    assert rep["span_s"] == 0.0             # single instant: no span
    assert rep["main"]["share"]["compute"] == 0.0
    assert tr.bytes_moved("weight_load") == 4096


def test_unknown_task_kind_gets_its_own_bucket():
    tr = _trace([_ev(kind="compute", t0=0.0, t1=2.0),
                 _ev(kind="prefetch", name="pf[0]", t0=0.0, t1=1.0,
                     thread="pool-0", nbytes=100)])
    rep = tr.report()
    # the four schema kinds are always present...
    for kind in (t.value for t in TaskType):
        assert kind in rep["per_kind"]
    # ...and the unknown kind is reported, not silently dropped
    pf = rep["per_kind"]["prefetch"]
    assert pf["count"] == 1
    assert pf["busy_s"] == 1.0
    assert pf["bytes"] == 100
    assert pf["bw_Bps"] == 100.0
    assert tr.bytes_moved("prefetch") == 100


def test_bw_guard_when_bytes_but_no_busy_across_kinds():
    tr = _trace([_ev(kind="kv_load", name="kv[0,0]", t0=3.0, t1=3.0,
                     thread="pool-1", nbytes=7),
                 _ev(kind="compute", t0=0.0, t1=4.0)])
    rep = tr.report()
    assert rep["per_kind"]["kv_load"]["bw_Bps"] == 0.0
    assert rep["per_kind"]["compute"]["busy_frac"] == 1.0


def test_bytes_moved_name_prefix_filter():
    tr = _trace([_ev(kind="weight_load", name="w[u[0][0]/exp[1]]",
                     t0=0, t1=1, nbytes=10),
                 _ev(kind="weight_load", name="w[u[0][0]/exp[2]]",
                     t0=1, t1=2, nbytes=20),
                 _ev(kind="weight_load", name="w[u[1][0]]", t0=2, t1=3,
                     nbytes=40)])
    assert tr.bytes_moved("weight_load") == 70
    assert tr.bytes_moved("weight_load", "w[u[0][0]/exp") == 30


# ---------------------------------------------------------------------------
# to_json / from_json
# ---------------------------------------------------------------------------


def test_json_round_trip_events_meta_and_report():
    tr = _trace([_ev(kind="kv_load", name="kv[2,4]", t0=0.5, t1=2.25,
                     thread="vpool-1", nbytes=640, extent=(2, 7)),
                 _ev(kind="compute", name="c[2,4]", t0=2.25, t1=6.0)])
    tr.meta.update(mode="performance", warm=True, depth=2, n_units=6,
                   pool_size=3, calls=[1, 1], sim_bw=None, quant="int4")
    d = tr.to_json()
    # through an actual JSON string, like a committed fixture
    back = Trace.from_json(json.dumps(d))
    assert back.meta == tr.meta
    assert back.events() == tr.events()     # extent tuple survived
    assert back.events()[0].extent == (2, 7)
    assert back.report() == tr.report()
    assert back.to_json() == d              # stable re-dump


def test_from_json_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown Trace JSON"):
        Trace.from_json({"meta": {}, "events": [], "bogus": 1})


def test_from_json_tolerates_missing_optional_event_fields():
    back = Trace.from_json({"events": [
        {"kind": "compute", "name": "c[0,0]", "t_start": 0.0,
         "t_end": 1.0}]})
    (e,) = back.events()
    assert (e.thread, e.nbytes, e.extent) == ("", 0, None)
    assert back.meta == {}


def test_live_trace_round_trip_through_pool():
    # a trace recorded by the real virtual transport round-trips whole
    from repro.core.pipeline import VirtualPool
    pool = VirtualPool(2, cost_fn=lambda t: 3.0)
    t = Task(TaskType.WEIGHT_LOAD, "w[0]", lambda: "h")
    t.nbytes = 123
    pool.submit(t)
    t.wait()
    back = Trace.from_json(json.dumps(pool.trace.to_json()))
    assert back.events() == pool.trace.events()
    assert back.span() == pool.trace.span() == 3.0
