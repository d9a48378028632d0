"""A configuration file as data (``bench/cell.py::model_config``), the
operations and bytes counted by layer kind (``bench/costs.py``), and the
check numbers over served tokens' gaps (``bench/cell.py::gap_number``,
``check``): granite's readings stay those of the code before layers were
counted by kind, and a Moonlight-shaped stack (a leading dense layer,
then MoE layers, MLA without a q latent, shared experts) is taken from
its file alone."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

import costs  # noqa: E402
import rehearsal  # noqa: E402

GRANITE = HERE.parent / "configs" / "granite-8b-l12.json"
MOONLIGHT = HERE / "fixtures" / "moonlight-tiny.json"


def load(path):
    return json.loads(path.read_text())


@pytest.fixture(scope="module")
def cell():
    sys.path.insert(0, str(rehearsal.REPO / "src"))
    return rehearsal.load_cell(rehearsal.REPO)


# ---------------------------------------------------------------------------
# the configuration file is the ModelConfig
# ---------------------------------------------------------------------------

def _granite_config_as_before(c):
    """The ``ModelConfig`` that the harness built before it took the file
    as data: a fixed set of keys and one layer kind."""
    from repro.configs.base import LayerSpec, ModelConfig, MoEConfig
    moe = c.get("moe")
    return ModelConfig(
        name=c["name"], family="moe" if moe else "dense",
        num_layers=c["num_layers"], d_model=c["d_model"],
        num_heads=c["num_heads"], num_kv_heads=c["num_kv_heads"],
        head_dim=c["head_dim"], d_ff=c["d_ff"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_seq_len"],
        pattern=(LayerSpec(c["mixer"], c["ffn"]),),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["norm_eps"]),
        tie_embeddings=bool(c["tie_embeddings"]),
        moe=MoEConfig(**moe) if moe else None)


@pytest.mark.parametrize("moe", [False, True], ids=["granite", "tiny-moe"])
def test_granite_file_builds_the_same_model_config(cell, moe):
    c = load(GRANITE) if not moe else rehearsal.tiny_config(True)
    got, want = cell.model_config(c), _granite_config_as_before(c)
    for f in dataclasses.fields(want):
        a, b = getattr(got, f.name), getattr(want, f.name)
        assert a == b and type(a) is type(b), f.name
    assert got == want


def test_moonlight_fixture_builds_its_model_config(cell):
    from repro.configs.base import LayerSpec, MLAConfig, MoEConfig
    m = cell.model_config(load(MOONLIGHT))
    assert m.num_layers == 5 and m.num_periods == 1 and m.remainder == ()
    assert m.pattern == ((LayerSpec("mla", "dense"),)
                         + (LayerSpec("mla", "moe"),) * 4)
    assert m.mla == MLAConfig(q_lora_rank=0, kv_lora_rank=8,
                              qk_nope_head_dim=4, qk_rope_head_dim=2,
                              v_head_dim=4)
    assert m.moe == MoEConfig(num_experts=8, top_k=3, expert_d_ff=6,
                              num_shared=2, shared_d_ff=6,
                              capacity_factor=8.0)
    assert type(m.moe.capacity_factor) is float
    assert type(m.rope_theta) is float and m.rope_theta == 50000.0
    assert m.family == "moe" and m.tie_embeddings is False
    assert m.d_ff == 20 and m.vocab_size == 64


def test_every_model_config_field_flows_through(cell):
    """Keys the harness never named pass as they stand: a window, a
    remainder, q/k norms and a family the file gives."""
    from repro.configs.base import LayerSpec
    c = load(GRANITE)
    for k in ("mixer", "ffn"):
        del c[k]
    c.update(num_layers=3, pattern=[["attn_local", "dense"]],
             remainder=[["attn", "dense"]], window=16, qk_norm=1,
             family="hybrid")
    m = cell.model_config(c)
    assert m.pattern == (LayerSpec("attn_local", "dense"),)
    assert m.remainder == (LayerSpec("attn", "dense"),)
    assert m.num_periods == 2 and m.window == 16
    assert m.qk_norm is True and m.family == "hybrid"


@pytest.mark.parametrize("edit, named", [
    (lambda c: c.update(d_modle=4096), "'d_modle'"),
    (lambda c: c.update(moe=dict(num_experts=4, top_k=2, expert_d_ff=8,
                                 num_expert=4)), "moe.'num_expert'"),
    (lambda c: c.update(pattern=[["attn", "dense"]]), "pattern or mixer"),
    (lambda c: c.pop("reference"), "names no reference"),
], ids=["top-level", "sub-config", "pattern-and-mixer", "no-reference"])
def test_a_key_the_harness_cannot_place_is_an_error(cell, edit, named):
    c = load(GRANITE)
    edit(c)
    with pytest.raises(cell.BenchError, match=named.replace(".", r"\.")):
        cell.model_config(c)
        cell.resident_bytes(c)


# ---------------------------------------------------------------------------
# costs by layer kind
# ---------------------------------------------------------------------------

def _granite_costs_as_before(c, rows, ctx_sum, ctx):
    """``decode_unit``, ``token_flops`` and ``kv_bytes_per_position`` as
    they were counted for one layer kind times ``num_layers``."""
    d, h, hkv, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    ffn = 3 * d * c["d_ff"]
    kvb = 2 * hkv * hd * 2
    p = attn + ffn
    unit = (2 * rows * p + 4 * ctx_sum * h * hd,
            (p + 2 * d) * 2 + ctx_sum * kvb + rows * kvb)
    tok = 2 * (c["num_layers"] * p + d * c["vocab_size"]) \
        + c["num_layers"] * 4 * ctx * h * hd
    return unit, tok, kvb


@pytest.mark.parametrize("ctxs", [[1], [600] * 16, [513, 1024, 530],
                                  list(range(1025, 1041))])
def test_granite_costs_are_todays_integers(ctxs):
    c = load(GRANITE)
    unit, tok, kvb = _granite_costs_as_before(c, len(ctxs), sum(ctxs),
                                              max(ctxs))
    assert costs.decode_unit(c, ctxs, ("attn", "dense")) == unit
    assert costs.token_flops(c, max(ctxs)) == tok
    assert costs.kv_bytes_per_position(c, "attn") == kvb == 4096
    assert costs.layer_counts(c) == {("attn", "dense"): 12}


def test_granite_resident_bytes_and_budget_are_todays(cell):
    c = load(GRANITE)
    # 49152 rows (a multiple of 256) x 4096, tied, plus the final norm
    assert cell.resident_bytes(c) == 2 * (49152 * 4096 + 4096) == 402661376
    res = 402661376
    assert cell.device_budget(c, 16 << 30) == int(
        res + ((16 << 30) - res) * 12 / 36)


def test_moonlight_fixture_costs_by_hand(cell):
    """d 32, 2 heads; MLA q straight from d (q_lora_rank 0), kv latent 8,
    rope 2, nope 4, v 4; dense d_ff 20; 8 experts of 6, top 3, 2 shared of
    6; vocab 64; bf16."""
    c = load(MOONLIGHT)
    assert costs.layers(c) == [("mla", "dense")] + [("mla", "moe")] * 4
    # q 32*2*(4+2)=384, kv_a 32*(8+2)=320, kv_b 8*2*(4+4)=128, o 2*4*32=256
    assert costs.mla_params(c) == 1088
    # router 32*8=256, routed 3*(3*32*6)=1728, shared 2*(3*32*6)=1152
    assert costs.moe_active_params(c) == 3136
    assert costs.kv_bytes_per_position(c, "mla") == (8 + 2) * 2
    # rows at 5 and 7 positions; absorbed: 2*12 positions*2 heads*(2*8+2)
    # = 864; dense unit p = 1088 + 3*32*20 = 3008, norms 2*32 + 8 = 72
    assert costs.decode_unit(c, [5, 7], ("mla", "dense")) == (
        2 * 2 * 3008 + 864, (3008 + 72) * 2 + 12 * 20 + 2 * 20)
    # a MoE layer's unit is its mixer; the experts run apart
    assert costs.decode_unit(c, [5, 7], ("mla", "moe")) == (
        2 * 2 * 1088 + 864, (1088 + 72) * 2 + 12 * 20 + 2 * 20)
    # published form at 10 positions: 2*10*2*(4+2+4) = 400 a layer
    dense = 2 * (1088 + 1920) + 400
    moe = 2 * (1088 + 3136) + 400
    assert costs.token_flops(c, 10) == dense + 4 * moe + 2 * 32 * 64 == 45904
    # padded vocab 256 x 32, untied, final norm 32, a router in each of
    # the four MoE layers (none in the dense one)
    assert cell.resident_bytes(c) == 2 * (256 * 32 * 2 + 32 + 4 * 32 * 8)


def test_window_caps_the_positions_attended():
    c = dict(load(MOONLIGHT), pattern=[["attn_local", "dense"]],
             num_layers=1, num_periods=0, window=4)
    # GQA 32*32 + 2*32*32 + 32*32 = 4096, dense 1920; K and V 2*2*16*2 =
    # 128 bytes a position; rows at 3 and 9 read 3 + min(9, 4) = 7
    assert costs.decode_unit(c, [3, 9], ("attn_local", "dense")) == (
        2 * 2 * 6016 + 4 * 7 * 2 * 16, (6016 + 64) * 2 + 7 * 128 + 2 * 128)
    assert costs.token_flops(c, 9) == (2 * 6016 + 4 * 4 * 2 * 16
                                       + 2 * 32 * 64)


@pytest.mark.parametrize("kind", ["ssm", "cross", "enc"])
def test_layers_not_counted_yet_raise(kind):
    c = dict(load(GRANITE), mixer=kind)
    with pytest.raises(ValueError, match=kind):
        costs.token_flops(c, 8)
    with pytest.raises(ValueError, match=kind):
        costs.decode_unit(c, [8], (kind, "dense"))


def test_roofline_sums_over_layer_kinds():
    """The decode roofline reader prices each layer by its kind: for a
    uniform stack it is ``num_layers`` times one layer, as before."""
    sys.path.insert(0, str(HERE.parent / "metrics"))
    import importlib
    roof = importlib.import_module("unit_decode_roofline")
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    req = SimpleNamespace(prompt=np.zeros(8), t_tokens=[1.0, 2.0, 3.0])
    step = SimpleNamespace(t0=1.5, t1=3.5, decode_steps=2)

    def run(c):
        device = SimpleNamespace(program_s=lambda: {roof.PROGRAM: 1e-3})
        return SimpleNamespace(device=device, steps=[step], requests=[req],
                               peaks=pk, cell=SimpleNamespace(config=c))

    assert roof.decode_calls(run(None)) == [[9, 10]]
    g = load(GRANITE)
    f, b = costs.decode_unit(g, [9, 10], ("attn", "dense"))
    want = 12 * max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    assert roof.read(run(g)) == pytest.approx(100.0 * want / 1e-3, rel=1e-12)
    m = load(MOONLIGHT)
    parts = [costs.decode_unit(m, [9, 10], k) for k in costs.layers(m)]
    want = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
               for f, b in parts)
    assert roof.read(run(m)) == pytest.approx(100.0 * want / 1e-3, rel=1e-12)


# ---------------------------------------------------------------------------
# check numbers
# ---------------------------------------------------------------------------

GAPS = [0.0, 0.0, 0.02, 0.06, 0.3]


@pytest.mark.parametrize("name, value", [
    ("served_gap", 0.3), ("share_over_0.05", 40.0), ("share_over_0.01", 60.0),
    ("share_over_0.1", 20.0), ("share_over_0", 60.0), ("share_over_1", 0.0),
])
def test_gap_numbers_on_synthetic_gaps(cell, name, value):
    assert cell.gap_number(name, GAPS) == pytest.approx(value)


@pytest.mark.parametrize("name", ["share_over_", "share_over_x",
                                  "share_over_-1", "share_over_nan",
                                  "widest_gap"])
def test_a_number_no_run_can_compute_is_refused(cell, name):
    with pytest.raises(cell.BenchError, match="no check number"):
        cell.gap_number(name, GAPS)


def test_a_limits_file_naming_an_unknown_number_fails_before_a_run(tmp_path):
    name = rehearsal.checkout(tmp_path, limits={"share_over_five": 1.0})
    cell = rehearsal.load_cell(tmp_path)
    with pytest.raises(cell.BenchError, match="share_over_five"):
        cell.find_cell(name)


@pytest.mark.parametrize("gaps, correct", [
    ([0.0] * 18 + [0.06, 0.3], True),     # widest 0.3, 10% over 0.05
    ([0.0] * 18 + [0.06, 0.6], False),    # widest over its limit
    ([0.0] * 16 + [0.06] * 4, False),     # 20% over 0.05
], ids=["both-hold", "widest-fails", "share-fails"])
def test_a_limits_file_with_both_numbers_decides_correct(cell, monkeypatch,
                                                         gaps, correct):
    limits = {"served_gap": 0.5, "share_over_0.05": 15.0}
    c = SimpleNamespace(config={}, mix={"max_len": 64}, limits=limits)
    monkeypatch.setattr(cell, "sample", lambda reqs, mix, seed: reqs)
    monkeypatch.setattr(cell, "served_gaps",
                        lambda *a, fp8=False: ([np.asarray(gaps)],
                                               [np.asarray(gaps) + 1]))
    chk, ok, prog, ctl = cell.check(c, 1, ["r"], fp8=True)
    assert ok is correct
    assert list(chk) == ["served_gap", "share_over_0.05"]
    assert chk["share_over_0.05"]["limit"] == 15.0
    assert set(prog) == set(ctl) == {"served_gap", *cell.CONTROL_SHARES,
                                     "share_over_0.05"}
    assert ctl["share_over_0.05"] == 100.0
