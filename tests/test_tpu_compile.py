"""Compile-only checks for a described TPU v5e: the served per-layer
programs at tinyllama-1.1b widths and the Pallas kernels at the same
widths.  Nothing runs; the TPU compiler either accepts each program or
raises what the chip would raise (tiling, VMEM, unsupported ops).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU runtime, and a test that
described it at import would make pytest-xdist workers collect different
tests.  The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one)."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.kvstore import _dequant_pad_rows, kv_group
from repro.kernels.decode_attention import (decode_attention_int4_kernel,
                                            decode_attention_kernel)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import int4_matmul
from repro.models import Dist
from repro.models import transformer as T
from repro.serving.offload_engine import head_programs, unit_programs

ARCH = "tinyllama-1.1b"
B, MAX_LEN, PROMPT = 8, 2048, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_of(topo):
    """ShapeDtypeStruct factory on one chip of the described topology,
    with the persistent compilation cache off while the module runs."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape_of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    yield shape_of
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, static_argnums=()):
    return jax.jit(fn, static_argnums=static_argnums).lower(*args).compile()


def _layer_args(cfg, shape_of):
    """Shapes of one streamed unit: its weights and its decode cache."""
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    w = {n: shape_of(l.shape[1:], l.dtype)
         for n, l in params["pat"][0].items()}
    struct, kinds = T.cache_struct(cfg, B, MAX_LEN)
    cache = {n: shape_of(s.shape[1:], s.dtype)
             for n, s in struct["pat"][0].items()}
    return params, w, cache, dict(kinds["pat"][0])


def _angles(cfg, positions, shape_of):
    a = jax.eval_shape(lambda p: T._angles(cfg, p), positions)
    return jax.tree.map(lambda s: shape_of(s.shape, s.dtype), a)


@pytest.mark.parametrize("program", ["decode", "prefill", "embed", "head",
                                     "kv_int4_dequant"])
def test_served_program_compiles(shape_of, program):
    """The programs ``OffloadedServingEngine`` jits per decode step, at
    tinyllama-1.1b widths with f32 weights, b=8 and a 2048-row cache."""
    cfg = get_config(ARCH)
    dist = Dist.local()
    params, w, cache, kinds = _layer_args(cfg, shape_of)
    decode_fn, prefill_fn, _ = unit_programs(
        cfg, dist, cfg.pattern[0], kinds, max_len=MAX_LEN, kv_int4=False)
    embed_fn, head_fn, _ = head_programs(cfg, dist)
    emb = {n: shape_of(l.shape, l.dtype) for n, l in params["embed"].items()}
    fn = {n: shape_of(l.shape, l.dtype)
          for n, l in params["final_norm"].items()}
    d = cfg.d_model
    if program == "decode":
        pos = shape_of((B,), jnp.int32)
        angles = _angles(cfg, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                         shape_of)
        c = _compile(decode_fn, w, shape_of((B, 1, d)), cache, pos, angles)
    elif program == "prefill":
        angles = _angles(cfg, jax.ShapeDtypeStruct((PROMPT,), jnp.int32),
                         shape_of)
        c = _compile(prefill_fn, w, shape_of((1, PROMPT, d)), angles)
    elif program == "embed":
        c = _compile(embed_fn, emb, shape_of((B, 1), jnp.int32), "decode",
                     static_argnums=(2,))
    elif program == "head":
        c = _compile(head_fn, emb, fn, shape_of((B, 1, d)))
    else:
        F = cfg.num_kv_heads * cfg.head_dim
        g = kv_group(F)
        full = (B, MAX_LEN, cfg.num_kv_heads, cfg.head_dim)
        c = _dequant_pad_rows.lower(
            shape_of((B, 256, F // 2), jnp.uint8),
            shape_of((B, 256, F // g)), g, full, jnp.float32).compile()
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("kernel", ["int4_matmul", "flash_attention",
                                    "decode_attention",
                                    "decode_attention_int4"])
def test_kernel_compiles(shape_of, kernel):
    """Each Pallas kernel lowers through Mosaic at tinyllama-1.1b widths:
    the compiled HLO holds the kernel as a ``tpu_custom_call``."""
    cfg = get_config(ARCH)
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    F = hkv * dh
    if kernel == "int4_matmul":
        K, N = cfg.d_model, cfg.d_ff
        c = _compile(lambda x, p, s: int4_matmul(x, p, s),
                     shape_of((B, K)), shape_of((K, N // 2), jnp.uint8),
                     shape_of((K // 128, N)))
    elif kernel == "flash_attention":
        c = _compile(lambda q, k, v: flash_attention(q, k, v),
                     shape_of((1, 512, h, dh)), shape_of((1, 512, hkv, dh)),
                     shape_of((1, 512, hkv, dh)))
    elif kernel == "decode_attention":
        c = _compile(lambda q, k, v, p: decode_attention_kernel(q, k, v, p),
                     shape_of((B, h, dh)), shape_of((B, MAX_LEN, hkv, dh)),
                     shape_of((B, MAX_LEN, hkv, dh)),
                     shape_of((), jnp.int32))
    else:
        g = kv_group(F)
        c = _compile(
            lambda q, kq, ks, vq, vs, p: decode_attention_int4_kernel(
                q, kq, ks, vq, vs, p, hkv=hkv, group=g),
            shape_of((B, h, dh)), shape_of((B, MAX_LEN, F // 2), jnp.uint8),
            shape_of((B, MAX_LEN, F // g)),
            shape_of((B, MAX_LEN, F // 2), jnp.uint8),
            shape_of((B, MAX_LEN, F // g)), shape_of((), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
