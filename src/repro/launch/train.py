"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 100 --batch 8 --seq 128 --ckpt-dir ckpt

On a real pod this binary runs per host (jax.distributed.initialize is
called when JAX_COORDINATOR is set) with the production mesh; in this
container it runs the same code on the local mesh.
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--sable", action="store_true",
                    help="enable SABLE block-sparse FFN (llama3-8b)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (testing)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.devices:
        # forced host devices are a CPU feature; on a chip they would hide it
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            ap.error("--devices forces host CPU devices: set JAX_PLATFORMS=cpu")
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if os.environ.get("JAX_COORDINATOR"):  # real multi-host pod
        jax.distributed.initialize()

    import dataclasses

    from ..configs import get_config
    from ..configs.shapes import Shape
    from ..data.pipeline import make_dataset
    from ..distributed.sharding import (
        ParallelConfig, batch_specs, make_shardings, param_specs,
    )
    from ..models.transformer import init_params
    from ..optim.adamw import AdamWConfig, adamw_init
    from ..optim.schedule import cosine_schedule
    from ..train.loop import TrainLoop
    from ..train.step import make_train_step
    from .compile_cache import enable_compile_cache
    from .mesh import make_local_mesh

    enable_compile_cache()

    if args.sable:
        from ..configs import llama3_8b

        cfg = llama3_8b.reduced_sable() if args.reduced else llama3_8b.full_sable()
    else:
        cfg = get_config(args.arch, reduced=args.reduced)
    shape = Shape("cli", args.seq, args.batch, "train")
    mesh = make_local_mesh(("data", "model"))
    pc = ParallelConfig()
    opt_cfg = AdamWConfig(lr=args.lr)
    sched = lambda s: cosine_schedule(s, args.lr, warmup=20, total=args.steps)

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params, opt_cfg)
    pshard = make_shardings(mesh, pc, param_specs(cfg, params), params)
    oshard = {"mu": pshard, "nu": pshard, "count": NamedSharding(mesh, P())}
    params = jax.device_put(params, pshard)
    opt_state = jax.device_put(opt_state, oshard)

    ds = make_dataset(cfg, shape)
    example = next(iter(ds))
    bshard = make_shardings(mesh, pc, batch_specs(cfg, example), example)

    step = make_train_step(cfg, opt_cfg, pc, schedule=sched)
    jstep = jax.jit(
        step,
        in_shardings=(pshard, oshard, bshard, NamedSharding(mesh, P())),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1),
    )

    def wrapped(params, opt, batch, i):
        batch = jax.device_put(batch, bshard)
        return jstep(params, opt, batch, jnp.int32(i))

    loop = TrainLoop(wrapped, ds, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every)
    if args.resume:
        params, opt_state, resumed = loop.maybe_restore(params, opt_state)
        print(f"resumed={resumed} at step {loop.step}")
    params, opt_state, metrics = loop.run(
        params, opt_state, args.steps, log_every=args.log_every
    )
    print(f"final loss {float(metrics['loss']):.4f} @ step {loop.step}")


if __name__ == "__main__":
    main()
