"""Serving engines hold a decoder's layer weights at the configuration's
stated ``dtype``: drawn at f32 from the seed, rounded once to nearest
even, then stored on the host tier, streamed and computed at that width.
The embedding and the final norm stay f32, and a MoE router stays at its
``router_dtype``.  Each check runs at f32 (the path a configuration that
states ``dtype="float32"`` keeps) and at bf16."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.core.transfer import split_views
from repro.serving import EngineSpec, Request, create_engine
from repro.serving.spec import build_lm

DTYPES = ["float32", "bfloat16"]


def _cfg(dtype, arch="tinyllama-1.1b"):
    return dataclasses.replace(scaled_down(get_config(arch)), dtype=dtype)


def _engine(cfg, offload=True, **kw):
    if offload:
        kw.setdefault("placement", "host")
    return create_engine(EngineSpec(arch=cfg.name, cfg=cfg, offload=offload,
                                    b_max=2, max_len=32, **kw))


def _serve(eng, cfg, max_new=4):
    rng = np.random.default_rng(0)
    for i in range(3):
        prompt = rng.integers(0, cfg.vocab_size, (5 + i,)).astype(np.int32)
        eng.submit(Request(rid=i, prompt=prompt, max_new=max_new))
    out = {r.rid: r.out for r in eng.run()}
    if hasattr(eng, "shutdown"):
        eng.shutdown()
    return out


def _host_units(eng):
    """{unit key: {tensor name: host view}} of the offloaded host tier."""
    return {k: split_views(eng.host.get(k), man)
            for k, man in eng.weights.manifests.items()}


def _f32_draw(cfg, seed=0):
    from repro.models import build_model
    return build_model(cfg).init(jax.random.PRNGKey(seed), jnp.float32)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("dtype", DTYPES)
def test_host_tier_units_at_config_itemsize(dtype):
    """Every tensor of every unit on the host tier, experts included, is
    at the configuration's dtype, and ``nbytes(key)`` (what a WEIGHT_LOAD
    moves) is the tensors' element count times its itemsize."""
    cfg = _cfg(dtype, "llama4-scout-17b-a16e")
    eng = _engine(cfg)
    itemsize = jnp.dtype(dtype).itemsize
    units = _host_units(eng)
    assert any("/exp[" in k for k in units)
    for key, views in units.items():
        assert {v.dtype for v in views.values()} == {jnp.dtype(dtype)}, key
        assert eng.weights.nbytes(key) == itemsize * sum(
            v.size for v in views.values())
        assert eng.host.get(key).nbytes == eng.weights.nbytes(key)
    assert eng.trace.meta["weight_dtype"] == dtype
    assert f"weights={dtype}" in eng.plan.summary()
    eng.shutdown()


def test_weight_load_bytes_halve_at_bf16():
    """The same traffic through the same plan moves the same WEIGHT_LOADs,
    each with half the bytes at bf16 that it has at f32."""
    loads = {}
    for dtype in DTYPES:
        cfg = _cfg(dtype)
        eng = _engine(cfg)
        _serve(eng, cfg)
        loads[dtype] = [(e.name, e.nbytes) for e in eng.trace.events()
                        if e.kind == "weight_load" and not e.span]
    f32, bf16 = loads["float32"], loads["bfloat16"]
    assert f32 and [n for n, _ in f32] == [n for n, _ in bf16]
    assert all(b > 0 and 2 * b == f for (_, f), (_, b) in zip(f32, bf16))


@pytest.mark.parametrize("dtype", DTYPES)
def test_embedding_norm_and_router_keep_their_dtypes(dtype):
    """The embedding and the final norm stay f32 in both engines; a MoE
    router stays at ``router_dtype`` while its experts take ``dtype``."""
    cfg = _cfg(dtype, "llama4-scout-17b-a16e")
    off = _engine(cfg)
    res = _engine(cfg, offload=False)
    for held in (off.resident, res.params):
        for part in ("embed", "final_norm"):
            assert {a.dtype for a in jax.tree.leaves(held[part])} \
                == {jnp.dtype(jnp.float32)}
    router = jnp.dtype(cfg.moe.router_dtype)
    routers = [u.router for u in off.units if u.moe]
    assert routers and {r.dtype for r in routers} == {router}
    tables = [t for t in res.params["pat"] + res.params["rem"] if "wg" in t]
    assert tables
    for t in tables:
        assert t["wg"].dtype == router
        assert t["w_up"].dtype == jnp.dtype(dtype)
    off.shutdown()


@pytest.mark.parametrize("dtype", DTYPES)
def test_offloaded_and_resident_tokens_agree(dtype):
    """Both engines round the same draw by the same rule, so greedy
    decode emits the same tokens at either dtype."""
    cfg = _cfg(dtype)
    assert _serve(_engine(cfg), cfg) == _serve(_engine(cfg, offload=False),
                                               cfg)


@pytest.mark.parametrize("dtype", DTYPES)
def test_served_weights_are_the_rounded_f32_draw(dtype):
    """The offloaded host tier and the resident engine hold, bit for bit,
    the f32 draw rounded to nearest even (numpy's cast): the draw itself
    at f32, and at bf16 a rounding that is not truncation."""
    cfg = _cfg(dtype)
    draw = _f32_draw(cfg)
    off = _engine(cfg)
    res = _engine(cfg, offload=False)
    units = _host_units(off)
    truncated = 0
    for q in range(len(cfg.pattern)):
        for name, leaf in draw["pat"][q].items():
            want = np.asarray(leaf).astype(jnp.dtype(dtype))
            np.testing.assert_array_equal(
                _bits(res.params["pat"][q][name]), _bits(want))
            for p in range(cfg.num_periods):
                np.testing.assert_array_equal(
                    _bits(units[f"u[{p}][{q}]"][name]), _bits(want[p]))
            if dtype == "bfloat16":
                trunc = (_bits(leaf) >> 16).astype(np.uint16)
                truncated += int(np.sum(trunc != _bits(want)))
    assert (truncated > 0) == (dtype == "bfloat16")
    off.shutdown()


@pytest.mark.parametrize("dtype", DTYPES)
def test_pipelined_lm_units_at_config_dtype(dtype):
    """``PipelinedLM`` draws its own f32 weights and holds its units at
    the configuration's dtype by the same rule; INT4 units keep their
    packed leaves and f32 scales."""
    cfg = _cfg(dtype)
    for quant, want in ((None, {jnp.dtype(dtype)}),
                        ("int4", {jnp.dtype(jnp.uint8),
                                  jnp.dtype(jnp.float32)})):
        lm = build_lm(EngineSpec(arch=cfg.name, cfg=cfg, offload=True,
                                 placement="host", b_max=2, max_len=32,
                                 quant=quant))
        held = {dt for man in lm.manifests.values()
                for _, _, dt in man.entries.values()}
        assert held == want, quant
