"""The per-layer readers of the engine's phase, wait, queue and step spans
(``bench/metrics/``), each fed a synthetic run: the numbers they read
from known spans, and nothing (``None``) from a program that records no
such span, as the engines before those spans did."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
METRICS = HERE.parent / "metrics"
sys.path.insert(0, str(HERE.parent))

NEW = ("wait_weight_share", "wait_kv_share", "weight_link_gbps",
       "kv_link_gbps", "pool_queue_ms", "prefill_share")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ev(kind, t0, t1, nbytes=0, name="x"):
    return SimpleNamespace(kind=kind, name=name, t_start=t0, t_end=t1,
                           nbytes=nbytes)


def run(events, t0=100.0, t1=110.0):
    return SimpleNamespace(host_events=events, t0=t0, t1=t1,
                           window_s=t1 - t0)


# the task spans alone, as the parent program records them
TASKS_ONLY = [ev("weight_load", 100.0, 101.0, 10**9),
              ev("kv_load", 100.5, 101.5, 10**8),
              ev("kv_save", 101.0, 101.2, 10**7),
              ev("compute", 101.0, 101.1)]


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_without_the_spans(name):
    assert reader(name)(run(TASKS_ONLY)) is None


def test_wait_shares_merge_and_clip_to_the_window():
    events = TASKS_ONLY + [
        ev("wait.weight_load", 99.0, 102.0),     # 2 s inside the window
        ev("wait.weight_load", 101.5, 103.0),    # overlaps: +1 s
        ev("wait.kv_load", 104.0, 105.0),
        ev("wait.kv_save", 104.5, 105.5),        # merged with the load
        ev("wait.head", 106.0, 107.0)]           # neither
    r = run(events)
    assert reader("wait_weight_share")(r) == pytest.approx(30.0)
    assert reader("wait_kv_share")(r) == pytest.approx(15.0)


def test_link_rates_take_the_carrying_phases_over_their_merged_time():
    events = TASKS_ONLY + [
        ev("weight_load.stage", 100.0, 100.1),
        ev("weight_load.put", 100.1, 100.3, 4 * 10**9),
        ev("weight_load.ready", 100.3, 100.5),
        ev("weight_load.put", 100.4, 100.6, 2 * 10**9),   # overlaps
        ev("kv_load.stage", 101.0, 101.5),
        ev("kv_load.put", 101.5, 101.6, 10**8),
        ev("kv_load.pad", 101.6, 101.9),
        ev("kv_load.ready", 101.9, 102.0),
        ev("kv_save.sync", 102.0, 103.0),
        ev("kv_save.fetch", 103.0, 103.2, 2 * 10**8),
        ev("kv_save.scatter", 103.2, 103.3)]
    r = run(events)
    # 6 GB over 100.1-100.6
    assert reader("weight_link_gbps")(r) == pytest.approx(12.0)
    # 0.1 GB over 0.1 + 0.1 s: stage and pad are out, and so are saves
    assert reader("kv_link_gbps")(r) == pytest.approx(0.5)


def test_kv_link_reads_the_load_direction_only():
    """A save's ``fetch`` carries bytes too, but the other way and with a
    wait for device work inside it: it moves nothing in ``kv_link_gbps``,
    and saves alone give it nothing to read."""
    loads = [ev("kv_load.put", 100.0, 100.1, 10**8),
             ev("kv_load.ready", 100.1, 100.2)]
    saves = [ev("kv_save.fetch", 100.0, 101.0, 5 * 10**8, name="sv[0,0]")]
    read = reader("kv_link_gbps")
    assert read(run(TASKS_ONLY + loads + saves)) == pytest.approx(
        read(run(TASKS_ONLY + loads))) == pytest.approx(0.5)
    assert read(run(TASKS_ONLY + saves)) is None


def test_wait_shares_read_zero_when_that_producer_never_held_the_thread():
    """A program that spans its waits but never blocked on one producer
    reads 0 for it, not nothing."""
    events = TASKS_ONLY + [ev("wait.head", 101.0, 102.0, name="head")]
    assert reader("wait_weight_share")(run(events)) == 0.0
    assert reader("wait_kv_share")(run(events)) == 0.0


def test_pool_queue_is_the_mean_per_transfer_task():
    events = TASKS_ONLY + [
        ev("queue.weight_load", 100.0, 100.002),
        ev("queue.kv_load", 100.0, 100.004),
        ev("queue.kv_save", 100.0, 100.030)]
    assert reader("pool_queue_ms")(run(events)) == pytest.approx(12.0)


def test_prefill_share_counts_each_admission_once():
    events = TASKS_ONLY + [
        ev("engine.prefill", 98.0, 101.0, name="r3"),   # 1 s inside
        ev("engine.prefill", 104.0, 106.0, name="r4"),
        ev("engine.decode", 101.0, 104.0, name="rows=16")]
    assert reader("prefill_share")(run(events)) == pytest.approx(30.0)


def test_engine_spans_feed_every_new_reader():
    """A trace recorded by the offloaded engine on the CPU gives every
    new reader a number (the rehearsal checks the same through
    ``bench/cell.py``)."""
    repo = HERE.parent.parent
    sys.path.insert(0, str(repo / "src"))
    import numpy as np
    from repro.configs import get_config, scaled_down
    from repro.serving import EngineSpec, Request, create_engine
    cfg = scaled_down(get_config("tinyllama-1.1b"))
    eng = create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                   placement="host", b_max=2, max_len=64))
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, (12 + 4 * i,)).astype(np.int32), max_new=4))
    import time
    t0 = time.perf_counter()
    eng.run()
    t1 = time.perf_counter()
    eng.shutdown()
    base = eng.trace.t0
    events = [ev(e.kind, e.t_start + base, e.t_end + base, e.nbytes, e.name)
              for e in eng.trace.events()]
    r = run(events, t0, t1)
    for name in NEW:
        v = reader(name)(r)
        assert v is not None and v >= 0, name
    assert 0 < reader("wait_weight_share")(r) < 100
    assert 0 < reader("prefill_share")(r) < 100
