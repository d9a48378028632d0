"""``bench/trace_reduce.py`` and the readers built on it, on a small trace
recorded on a TPU v5e (``fixtures/window.xplane.pb``: a traced window of
the tiny dense cell of ``rehearsal.py``, made with ``bench/cell.py``'s
own profiler options; stored gzipped), and on hand-made spans."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import costs  # noqa: E402
import peaks  # noqa: E402
import rehearsal  # noqa: E402
import trace_reduce as tr  # noqa: E402

@pytest.fixture(scope="module")
def dt(tmp_path_factory):
    return tr.load(rehearsal.unpack_fixture(tmp_path_factory.mktemp("trace")))


def test_window_and_busy_time(dt):
    assert 0.5 < dt.window_s < 5
    busy = dt.busy_s()
    assert 0 < busy < dt.window_s
    assert 0 < dt.idle_share() < 1
    # the union of the op intervals never exceeds their sum
    total = sum(t - s for s, t, _ in tr.clip(dt.ops[0], dt.w0, dt.w1)) * 1e-9
    assert busy <= total + 1e-12


def test_programs_carry_stable_names(dt):
    progs = dt.program_s()
    assert "jit_decode_fn" in progs and progs["jit_decode_fn"] > 0
    assert all("(" not in k for k in progs)
    assert sum(progs.values()) <= dt.window_s


def test_breakdown_lists_at_most_ten(dt):
    bd = tr.breakdown(dt, [], None)
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    ops = [v for _, v in bd["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    idle = sum(v for _, v in tr.breakdown(dt, [], None, top=1000)["idle_gaps"])
    assert idle == pytest.approx(dt.window_s - dt.busy_s(), rel=1e-6)


def test_merge_and_gaps_by_hand():
    assert tr.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    d = tr.DeviceTrace(0, 10, [[(1, 2, "a"), (1.5, 4, "b"), (8, 12, "c")]],
                       [(1, 4, "jit_f(123)"), (8, 12, "jit_g(9)")])
    assert d.busy_s() == pytest.approx(5e-9)
    assert d.gaps(0) == [(0, 1), (4, 8)]
    assert d.program_s() == pytest.approx({"jit_f": 3e-9, "jit_g": 2e-9})
    assert tr.program_name("jit_decode_fn(203485511274741268)") == "jit_decode_fn"


def test_idle_gaps_are_put_down_to_host_spans():
    d = tr.DeviceTrace(0, 10e9, [[(2e9, 3e9, "a")]], [],
                       [(0, 5e9, "bench.step")])
    ev = type("Ev", (), dict(kind="weight_load", t_start=100.0, t_end=101.5))
    bd = tr.breakdown(d, [ev], host_t0=100.0)
    got = dict(bd["idle_gaps"])
    assert got["step: host code"] == pytest.approx(2.0 + 0.5)     # 3-5, 1.5-2
    assert got["step: weight_load"] == pytest.approx(1.5)         # 0-1.5 s
    assert got["between steps: host code"] == pytest.approx(5.0)  # 5-10 s


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks.lookup("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def test_decode_roofline_bound_at_full_width():
    """granite-8b-l12's decode layer at 16 rows: bytes bound it (0.44 GB
    of bf16 weights at 819 GB/s is ~0.53 ms; its ~7 GFLOP take ~36 us)."""
    import json
    c = json.loads((HERE.parent / "configs" / "granite-8b-l12.json").read_text())
    f, b = costs.decode_unit(c, [600] * 16, ("attn", "dense"))
    pk = peaks.lookup("TPU v5 lite")
    assert b / pk["hbm_bytes_per_s"] > f / pk["bf16_flops"]
    assert 0.5e-3 < b / pk["hbm_bytes_per_s"] < 0.7e-3
    assert costs.attn_params(c) + costs.dense_ffn_params(c) == 218_103_808
