"""Training launcher: mesh setup, sharded init, resumable train loop.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --smoke --steps 50 --batch 8 --seq 128 --mesh 1x1

Fault tolerance: checkpoint every --ckpt-every steps (async), SIGTERM
preemption guard writes a final checkpoint, --resume picks up the latest
step and the stateless data pipeline continues from there bit-exactly.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as ckpt
from repro.configs.registry import get_config
from repro.core.platform import configure_compile_cache
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models.schema import count_params, init_params, param_specs
from repro.optim.optimizers import cosine_schedule, get_optimizer
from repro.sharding.partition import MeshContext, spec_for
from repro.training.step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="", help="e.g. 4x2 (data x model); '' = all devices DP")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--override", default="", help="k=v,... ModelConfig overrides")
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        cfg = cfg.replace(**{k: int(v) if v.lstrip("-").isdigit() else v})

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        names = ("data", "model")[-len(shape):] if len(shape) <= 2 else \
            ("pod", "data", "model")
        mesh = make_mesh(shape, names)
    else:
        mesh = make_mesh((len(jax.devices()),), ("data",))
    ctx = MeshContext(mesh, profile=cfg.parallelism_profile)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    opt = get_optimizer(cfg.optimizer, lr_schedule=cosine_schedule(
        args.lr, args.warmup, args.steps)) if cfg.optimizer == "adamw" else \
        get_optimizer(cfg.optimizer)
    step_fn, opt = make_train_step(cfg, ctx, opt, grad_accum=args.grad_accum)

    pspecs = param_specs(cfg, mesh)
    named = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    init_jit = jax.jit(lambda k: init_params(cfg, k), out_shardings=named)
    params = init_jit(jax.random.PRNGKey(args.seed))
    opt_state = jax.jit(opt.init)(params)
    print(f"arch={cfg.name} params={count_params(params):,}")

    bspec = NamedSharding(mesh, spec_for(("batch", None), (args.batch, args.seq), mesh))
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    start = 0
    if args.resume and args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            (params, opt_state), extras = ckpt.restore(
                args.ckpt_dir, last, (params, opt_state),
                shardings=(named, jax.tree.map(lambda _: None, opt_state)))
            start = last
            print(f"resumed from step {start}")

    guard = ckpt.PreemptionGuard()
    t0 = time.time()
    tokens_per_step = args.batch * args.seq
    for step in range(start, args.steps):
        batch = data.sharded_batch_at(step, bspec)
        params, opt_state, metrics = jit_step(params, opt_state, batch)
        if (step + 1) % args.log_every == 0 or step == start:
            loss = float(metrics["loss"])
            dt = (time.time() - t0) / (step - start + 1)
            print(f"step {step+1:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"{tokens_per_step/dt:,.0f} tok/s  {dt*1e3:.0f} ms/step",
                  flush=True)
        preempt = guard.preempted
        if args.ckpt_dir and ((step + 1) % args.ckpt_every == 0 or preempt
                              or step + 1 == args.steps):
            ckpt.save(args.ckpt_dir, step + 1, (params, opt_state),
                      extras={"arch": cfg.name})
        if preempt:
            print(f"preempted at step {step+1}; checkpoint written")
            break
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
