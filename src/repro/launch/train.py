"""End-to-end training driver with fault tolerance and elastic scaling.

Production behaviours exercised here (CPU-scaled, same code paths a pod
would run):

* checkpoint/restart — atomic committed checkpoints every ``--ckpt-every``
  steps; on start the driver restores the latest committed step and the
  data pipeline regenerates the exact stream from it (bitwise-resumable);
* crash injection — ``--crash-at N`` kills the process mid-run (between a
  step and its checkpoint) to prove restart recovers;
* elastic scaling — the checkpoint stores logical arrays; restoring under
  a different mesh/plan re-shards via device_put (``--dp/--tp`` may differ
  across restarts);
* straggler mitigation — per-step wall times feed an EWMA; steps slower
  than ``--straggler-factor``× the EWMA are logged with the offending
  step's metrics (at pod scale this signal drives re-slicing; here it
  drives the log + a counter the tests assert on);
* gradient compression — ``--compress`` switches to the int8
  error-feedback DDP step (shard_map path).

Usage (CPU smoke):
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --steps 40 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

from ..configs import SHAPES, get_config
from ..configs.base import ShapeSpec
from ..data.pipeline import Pipeline
from ..models import registry as model_registry
from ..train import checkpoint as ckpt
from ..train.optimizer import make_optimizer
from ..train.train_step import (TrainState, init_residuals,
                                make_compressed_train_step, make_train_step)
from . import plans as PL
from .mesh import make_host_mesh, make_mesh_spec


def build(cfg, shape, mesh, plan, opt, accum=1, compress=False):
    rt = plan.runtime(mesh)
    api = model_registry.get_model(cfg)
    if compress:
        dp_axis = plan.dp_axes[0] if plan.dp_axes else "data"
        step = make_compressed_train_step(
            api, rt, opt, axis=dp_axis, n_shards=mesh.shape[dp_axis])
    else:
        step = make_train_step(api, rt, opt, accum=accum)
    return api, rt, step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--dp", type=int, default=None)
    ap.add_argument("--tp", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = SHAPES.get(args.shape) or ShapeSpec(
        args.shape, "train", args.seq, args.batch)
    if args.reduced:
        shape = ShapeSpec("train_smoke", "train", args.seq, args.batch)

    if args.dp or args.tp:
        mesh = make_mesh_spec(args.dp or 1, args.tp or 1)
    else:
        mesh = make_host_mesh()
    plan = PL.default_plan(cfg, shape, mesh)
    opt = make_optimizer("adamw", peak_lr=args.lr, warmup=20,
                         total_steps=max(args.steps, 100),
                         state_dtype=plan.opt_state_dtype,
                         factored=plan.opt_factored,
                         momentum=plan.opt_momentum)
    api, rt, step = build(cfg, shape, mesh, plan, opt,
                          accum=plan.accum, compress=args.compress)

    # ---- init or restore ---------------------------------------------------
    with mesh:
        state = TrainState(params=api.init(jax.random.key(0)),
                           opt=opt.init(api.init(jax.random.key(0))),
                           step=jnp.zeros((), jnp.int32))
        residuals = init_residuals(state.params) if args.compress else None
        start = 0
        if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            state = ckpt.restore(args.ckpt_dir, jax.eval_shape(lambda: state))
            start = int(state.step)
            print(f"[restore] resumed from committed step {start} "
                  f"(mesh {dict(mesh.shape)})")

        jit_step = jax.jit(step, donate_argnums=(0,)) if not args.compress \
            else None
        if args.compress:
            from jax.sharding import PartitionSpec as P
            dp_axis = plan.dp_axes[0] if plan.dp_axes else "data"
            jit_step = jax.jit(
                jax.shard_map(
                    step, mesh=mesh,
                    in_specs=(P(), P(), P(dp_axis)),
                    out_specs=(P(), P(), P()),
                    check_vma=False),
                donate_argnums=(0,))

        pipe = Pipeline(cfg, shape, start_step=start, prefetch=2)
        it = iter(pipe)
        ewma, stragglers = None, 0
        t_run = time.time()
        try:
            for i in range(start, args.steps):
                _, batch = next(it)
                t0 = time.time()
                if args.compress:
                    state, residuals, metrics = jit_step(state, residuals,
                                                         batch)
                else:
                    state, metrics = jit_step(state, batch)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                if i > start + 1:  # skip compile step
                    ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
                    if ewma and dt > args.straggler_factor * ewma:
                        stragglers += 1
                        print(f"[straggler] step {i}: {dt:.3f}s vs "
                              f"EWMA {ewma:.3f}s")
                if i % args.log_every == 0 or i == args.steps - 1:
                    print(f"step {i:5d}  loss {loss:.4f}  "
                          f"gnorm {float(metrics['grad_norm']):.2f}  "
                          f"{dt*1e3:.0f} ms")
                if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                    path = ckpt.save(args.ckpt_dir, i + 1, state,
                                     extra={"arch": cfg.name,
                                            "mesh": dict(mesh.shape),
                                            "plan": plan.name})
                    print(f"[ckpt] committed step {i+1} -> {path}")
                if args.crash_at is not None and i + 1 >= args.crash_at:
                    print(f"[crash] simulated failure after step {i+1}",
                          flush=True)
                    os._exit(42)
        finally:
            pipe.close()
        total = time.time() - t_run
        print(f"done: {args.steps - start} steps in {total:.1f}s; "
              f"final loss {loss:.4f}; stragglers {stragglers}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
