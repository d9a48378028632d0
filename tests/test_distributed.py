"""Distributed semantics under 8 fake devices (subprocess — device count
locks at first jax init, so these run in a child python).

The gold check: train loss / prefill outputs computed on a (2, 4) mesh
with full sharding (ring attention, sequence-sharded SSD, EP MoE,
vocab-sharded CE) must equal the single-device reference to float
tolerance, for a dense-GQA, an MoE, an SSM-hybrid and a local-window arch.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ASSIGNED, scaled_down
from repro.launch.sharding import make_dist, param_pspecs, batch_pspecs
from repro.models import build_model, Dist

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(model=4, data=2)

failures = []
for arch in ("granite-8b", "gemma3-4b", "deepseek-v3-671b", "jamba-1.5-large-398b", "mamba2-1.3b"):
    # scaled config with dims divisible by the test mesh
    cfg = scaled_down(ASSIGNED[arch], d_model=64, num_heads=4, num_kv_heads=4,
                      vocab_size=256)
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init(key, jnp.float32)
    b, s = 4, 32
    batch = {"labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
             "tokens": jax.random.randint(jax.random.fold_in(key, 1), (b, s),
                                          0, cfg.vocab_size)}
    if cfg.enc_dec:
        batch["enc_embeds"] = jax.random.normal(
            key, (b, cfg.encoder_seq_len, cfg.d_model)) * 0.05

    loss_ref = float(m.train_loss(params, batch, Dist.local()))
    dist = Dist(mesh=mesh, data_axes=("data",), model_axis="model")
    loss_dist = float(jax.jit(
        lambda p, bt: m.train_loss(p, bt, dist))(params, batch))
    rel = abs(loss_dist - loss_ref) / max(1e-9, abs(loss_ref))
    status = "OK" if rel < 2e-4 else "FAIL"
    if status == "FAIL":
        failures.append((arch, "train", loss_ref, loss_dist))
    print(f"{status} {arch} train: ref={loss_ref:.6f} dist={loss_dist:.6f} rel={rel:.2e}")

    # prefill + one decode step parity
    pre = {k: v for k, v in batch.items() if k != "labels"}
    nt_ref, caches_ref = m.prefill(params, pre, Dist.local(), cache_len=s + 4)
    dist_kv = Dist(mesh=mesh, data_axes=("data",), model_axis="model",
                   kv_axes=("model",))
    nt_dist, caches_dist = jax.jit(
        lambda p, bt: m.prefill(p, bt, dist_kv, s + 4))(params, pre)
    same_tok = bool((np.asarray(nt_ref) == np.asarray(nt_dist)).all())
    d_ref, _ = m.decode_step(params, {"token": nt_ref[:, None],
                                      "pos": jnp.int32(s)}, caches_ref,
                             Dist.local())
    d_dist, _ = jax.jit(lambda p, t, c: m.decode_step(
        p, {"token": t, "pos": jnp.int32(s)}, c, dist_kv))(
        params, nt_dist[:, None], caches_dist)
    same_dec = bool((np.asarray(d_ref) == np.asarray(d_dist)).all())
    status = "OK" if (same_tok and same_dec) else "FAIL"
    if status == "FAIL":
        failures.append((arch, "serve", nt_ref, nt_dist))
    print(f"{status} {arch} serve: prefill_tok_match={same_tok} decode_tok_match={same_dec}")

print("FAILURES:", len(failures))
assert not failures, failures
"""


@pytest.mark.slow
def test_distributed_parity_8dev():
    env = dict(os.environ)
    root = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = str(root / "src")
    # fake CPU devices only: the child never loads an accelerator runtime
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1200)
    print(r.stdout)
    print(r.stderr[-3000:] if r.returncode else "")
    assert r.returncode == 0, f"distributed parity failed:\n{r.stdout}\n{r.stderr[-3000:]}"
