"""The plain float32 reference (``bench/reference.py``) against the
offloaded engine on the CPU at a small size, both configurations' layer
kinds: served tokens, first from prefill and then from decoding through
the host-tier cache, sit at the reference's best logit.  The float8
control, on the same sequences, does not: the comparison the benchmark
makes separates the two."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import rehearsal  # noqa: E402
import reference  # noqa: E402

SEED = 2**31 + 7


def served(c, prompts, max_new=12):
    """Serve ``prompts`` through EngineSpec -> resolve -> create_engine
    with the weights on the host tier; returns the finished requests."""
    from repro.serving import Request
    from repro.serving.spec import EngineSpec, create_engine
    sys.path.insert(0, str(rehearsal.REPO / "bench"))
    import cell
    spec = EngineSpec(arch="tiny", cfg=cell.model_config(c), offload=True,
                      placement="host", b_max=2, max_len=64, seed=SEED)
    eng = create_engine(spec.resolve())
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=max_new))
        done = eng.run()
    finally:
        eng.shutdown()
    return sorted(done, key=lambda r: r.rid)


def gaps(c, done, fp8=False):
    """(prefill gaps, decode gaps): the reference's best logit minus its
    logit of the served (or, with ``fp8``, the control's first) token."""
    w = reference.init_weights(c, SEED)
    seqs = [np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
            for r in done]
    ref = reference.logits(c, w, seqs)
    ctl = reference.logits(c, w, seqs, fp8=True) if fp8 else ref
    pre, dec = [], []
    for r, lg, lc in zip(done, ref, ctl):
        rows = lg[len(r.prompt) - 1:]
        tok = (lc[len(r.prompt) - 1:].argmax(-1) if fp8
               else np.asarray(r.out))
        g = rows.max(-1) - rows[np.arange(len(tok)), tok]
        pre.append(g[0])
        dec.extend(g[1:])
    return np.asarray(pre), np.asarray(dec)


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_engine_matches_reference(moe):
    c = rehearsal.tiny_config(moe)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c["vocab_size"], n).astype(np.int32)
               for n in (5, 17, 9)]
    done = served(c, prompts)
    assert [len(r.out) for r in done] == [12, 12, 12]
    pre, dec = gaps(c, done)
    # float32 on both sides: equal up to rounding, so a served token can
    # only trail the best at a near-tie
    assert pre.max() <= 1e-3 and dec.max() <= 1e-2, (pre, dec)
    cpre, cdec = gaps(c, done, fp8=True)
    assert max(cpre.max(), cdec.max()) >= 10 * max(dec.max(), 1e-3)


def test_weight_recipe_is_the_seeds():
    c = rehearsal.tiny_config(False)
    a = reference.init_weights(c, SEED)
    b = reference.init_weights(c, SEED)
    d = reference.init_weights(c, SEED + 1)
    assert np.array_equal(a["layers"]["wq"], b["layers"]["wq"])
    assert not np.array_equal(a["layers"]["wq"], d["layers"]["wq"])
    assert a["layers"]["w_gate"].shape == (2, 64, 128)
    assert float(np.std(np.asarray(a["layers"]["wq"]))) == pytest.approx(
        1 / 8, rel=0.1)


def test_padding_does_not_reach_earlier_positions():
    c = rehearsal.tiny_config(True)
    w = reference.init_weights(c, SEED)
    seq = np.arange(1, 20, dtype=np.int32)
    short = reference.logits(c, w, [seq], pad_to=32)[0]
    long = reference.logits(c, w, [seq, np.arange(1, 70, dtype=np.int32)],
                            pad_to=32)[0]
    np.testing.assert_allclose(short, long, rtol=1e-5, atol=1e-5)
