"""CPU rehearsal of a benchmark run: ``bench/cell.py`` end to end at
a tiny size in a scratch checkout (``rehearsal.py``), with the look for a
chip replaced inside the test.  Also: a cell, a mix and a metric added as
new files are found with no existing file edited; a broken timed path
makes ``correct`` false; ``BENCHMARK.json`` keeps to its contract."""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import rehearsal  # noqa: E402

REPO = rehearsal.REPO
SEED = 2**31 + 11


def run(cell_mod, name, capsys, trace=0, seconds=2.0):
    rc = cell_mod.main(["--workload", name, "--seed", str(SEED),
                        "--seconds", str(seconds), "--trace", str(trace)])
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), captured.err


def bench_files():
    """{path under bench/: bytes} of every file the benchmark has."""
    return {p.relative_to(REPO / "bench"): p.read_bytes()
            for p in (REPO / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts and ".jax_cache" not in p.parts}


def test_cell_runs_end_to_end(tmp_path, capsys):
    name = rehearsal.checkout(tmp_path)
    cell = rehearsal.load_cell(tmp_path)
    rc, res, err = run(cell, name, capsys)
    assert rc == 0, err[-2000:]
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert res["check"]["served_gap"]["value"] <= res["check"]["served_gap"]["limit"]
    assert err.strip().splitlines()[-1].startswith("check served_gap=")
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}
    assert res["metrics"]["decode_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 1
    assert "device memory:" in err


def test_traced_run_reports_per_layer_metrics(tmp_path, capsys, monkeypatch):
    """The per-layer readers over a CPU run's spans and counters, with the
    device trace taken from the fixture recorded on a TPU v5e."""
    name = rehearsal.checkout(tmp_path)
    cell = rehearsal.load_cell(tmp_path)
    sys.path.insert(0, str(tmp_path / "bench"))
    import peaks
    import trace_reduce
    fixture = trace_reduce.load(rehearsal.unpack_fixture(tmp_path))
    monkeypatch.setattr(trace_reduce, "load", lambda path, n_chips=1: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    rc, res, err = run(cell, name, capsys, trace=1)
    assert rc == 0, err[-2000:]
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if name in m["workloads"]}
    assert set(res["metrics"]) == want
    assert res["device"]["busy_s"] > 0
    assert res["device"]["window_s"] > res["device"]["busy_s"]
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert res["correct"] is True


def test_new_files_are_found(tmp_path, capsys):
    """A metric added as a new reader file and a new BENCHMARK.json entry
    is reported, with no file of the benchmark edited."""
    name = rehearsal.checkout(tmp_path)
    (tmp_path / "bench" / "metrics" / "tokens_in_window.py").write_text(
        "def read(run):\n    return len(run.tokens_in_window())\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "tokens_in_window", "unit": "tokens",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock", "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = bench_files()
    cell = rehearsal.load_cell(tmp_path)
    rc, res, err = run(cell, name, capsys)
    assert rc == 0, err[-2000:]
    assert res["metrics"]["tokens_in_window"]["value"] > 0
    for rel, data in before.items():
        assert (tmp_path / "bench" / rel).read_bytes() == data, rel


def test_a_reference_the_configuration_names_judges_the_run(tmp_path, capsys):
    """The tiny MoE cell's configuration names a new reference file (a copy
    of ``bench/reference.py`` under another name) and its limits file
    bounds ``share_over_0.05``: the run loads that file, reads the number
    and reports ``correct``, with no file of the benchmark edited."""
    name = rehearsal.checkout(tmp_path, moe=True,
                              reference="bench/tiny_moe_reference.py",
                              limits={"share_over_0.05": 5.0})
    before = bench_files()
    cell = rehearsal.load_cell(tmp_path)
    rc, res, err = run(cell, name, capsys)
    assert rc == 0, err[-2000:]
    assert "check: reference bench/tiny_moe_reference.py" in err
    ref = sys.modules["bench_tiny_moe_reference"]
    assert (Path(ref.__file__).resolve()
            == (tmp_path / "bench" / "tiny_moe_reference.py").resolve())
    assert list(res["check"]) == ["share_over_0.05"]
    assert res["check"]["share_over_0.05"]["value"] <= 5.0
    assert err.strip().splitlines()[-1].startswith("check share_over_0.05=")
    assert res["correct"] is True
    for rel, data in before.items():
        assert (tmp_path / "bench" / rel).read_bytes() == data, rel


def test_control_reads_the_shares_for_program_and_control(tmp_path, capsys,
                                                         monkeypatch):
    """``bench/control.py`` on the tiny MoE cell, with the scratch
    checkout's ``cell.py`` (its look for a chip replaced): for each seed
    the program's and the float8 control's ``served_gap`` and
    ``share_over_`` at 0.01, 0.05 and 0.1, then the lower and upper
    readings of each."""
    name = rehearsal.checkout(tmp_path, moe=True)
    monkeypatch.setitem(sys.modules, "cell", rehearsal.load_cell(tmp_path))
    spec = importlib.util.spec_from_file_location(
        "bench_control_test", tmp_path / "bench" / "control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    rc = control.main(["--workload", name, "--seconds", "1",
                       "--seeds", str(SEED), str(SEED + 1)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    summary = json.loads(out[-1])
    numbers = {"served_gap", "share_over_0.01", "share_over_0.05",
               "share_over_0.1"}
    assert set(summary["readings"]) == numbers
    for row in summary["rows"]:
        assert set(row["program"]) == set(row["control"]) == numbers
    got = summary["readings"]["share_over_0.05"]
    # bf16 weights flip near-tie routing on a few tokens; float8 on many
    assert got["upper"] >= 3 * got["lower"]
    assert sum(line.startswith("control tiny.closed seed") for line in out) == 2


def _alter_tokens(cell, fault):
    """Break the timed path where tokens are produced: after each engine
    step, the newest token of every served request is changed."""
    reqs = []
    submit, step = cell._submit, cell._step

    def submit_and_keep(eng, item):
        r = submit(eng, item)
        reqs.append(r)
        return r

    def step_and_alter(eng, done, steps):
        step(eng, done, steps)
        for r in reqs:
            if len(r.out) >= 2:
                if fault == "altered":
                    r.out[-1] = (r.out[-1] + 1) % 512
                else:                       # the step left its token as it was
                    r.out[-1] = r.out[-2]

    cell._submit = submit_and_keep
    cell._step = step_and_alter


@pytest.mark.parametrize("fault", ["altered", "unchanged"])
def test_broken_timed_path_is_not_correct(tmp_path, capsys, fault):
    name = rehearsal.checkout(tmp_path)
    cell = rehearsal.load_cell(tmp_path)
    _alter_tokens(cell, fault)
    rc, res, err = run(cell, name, capsys)
    assert rc == 0, err[-2000:]
    assert res["correct"] is False
    assert res["check"]["served_gap"]["value"] > res["check"]["served_gap"]["limit"]


@pytest.mark.parametrize("fault", ["altered", "unchanged"])
def test_broken_timed_path_fails_the_share_check(tmp_path, capsys, fault):
    """The tiny MoE cell judged by ``share_over_0.05`` alone."""
    name = rehearsal.checkout(tmp_path, moe=True,
                              limits={"share_over_0.05": 5.0})
    cell = rehearsal.load_cell(tmp_path)
    _alter_tokens(cell, fault)
    rc, res, err = run(cell, name, capsys)
    assert rc == 0, err[-2000:]
    assert res["correct"] is False
    assert res["check"]["share_over_0.05"]["value"] > 5.0


def test_no_accelerator_prints_no_result(tmp_path, capsys):
    """The cell's own look for a chip: JAX finds only the CPU here."""
    name = rehearsal.checkout(tmp_path)
    cell = rehearsal.load_cell(tmp_path, stub_chip=False)
    rc, res, err = run(cell, name, capsys)
    assert rc == 2 and res is None
    assert "needs a TPU" in err


# ---------------------------------------------------------------------------
# the traffic generator
# ---------------------------------------------------------------------------

def _generator():
    sys.path.insert(0, str(REPO / "bench"))
    import generator
    return generator


def test_every_seed_serves_the_same_sizes():
    """The seed shuffles the order within blocks of ``b_max`` and draws the
    tokens; the sizes of each block stay those of the mix."""
    gen = _generator()
    mix = json.loads((REPO / "bench" / "traffic" / "offline.json").read_text())
    a, b = gen.make(mix, 512, SEED), gen.make(mix, 512, SEED + 1)
    block = mix["b_max"]
    for lo in range(0, len(a), block):
        sizes = [sorted((len(i.prompt), i.max_new) for i in run[lo:lo + block])
                 for run in (a, b)]
        assert sizes[0] == sizes[1]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [i.max_new for i in gen.make(mix, 512, SEED)] == [i.max_new for i in a]


def test_stagger_frees_slots_one_by_one():
    """With ``stagger`` the first ``b_max`` requests end one after another,
    evenly spread up to the mix's own length; later ones keep it."""
    gen = _generator()
    mix = json.loads((REPO / "bench" / "traffic" / "offline.json").read_text())
    items = gen.make(mix, 512, SEED)
    b, out = mix["b_max"], mix["out_max"]
    first = sorted(i.max_new for i in items[:b])
    assert first == [out * (k + 1) // b for k in range(b)]
    assert {i.max_new for i in items[b:]} == {out}
    plain = gen.make(dict(mix, stagger=False), 512, SEED)
    assert {i.max_new for i in plain} == {out}


# ---------------------------------------------------------------------------
# BENCHMARK.json against its contract
# ---------------------------------------------------------------------------

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/cell.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units_use_allowed_characters(bench):
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.fullmatch(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for n in names:
        assert NAME.fullmatch(n), n
    for text in ([c["why"] for c in bench["configs"]]
                 + [c["source"] for c in bench["configs"]]
                 + [w["why"] for w in bench["workloads"]]
                 + [m["layer"] for m in bench["per_layer"]]
                 + bench["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_name_finds_its_files(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    for w in bench["workloads"]:
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        path = REPO / "bench" / "metrics" / f"{m['name']}.py"
        if not path.is_file():       # <base>.<tag> is read by <base>.py
            path = REPO / "bench" / "metrics" / f"{m['name'].split('.')[0]}.py"
        assert path.is_file(), m["name"]
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        layer = [m for m in bench["per_layer"] if w["name"] in m["workloads"]]
        assert layer
        for m in layer:
            assert m["moves"] in {x["name"] for x in mine}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics_of_one_layer_agree_on_its_name(bench):
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
