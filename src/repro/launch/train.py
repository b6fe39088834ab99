"""End-to-end elastic training driver.

Runs the full USEC loop on whatever devices exist (CPU devices for local
runs; the same code path the dry-run lowers for the production mesh):

  data pipeline (tile-addressable, placement-staged)
   -> USECScheduler (speeds EWMA, elastic membership, LP + filling)
   -> usec train step (uneven per-worker loops, 1+S redundancy, psum)
   -> AdamW -> checkpoint every K steps (restartable, reshardable)

with per-step preemption/straggler simulation driven by --churn/--stragglers.

Example (CPU, 4 workers x 1 model shard; see examples/elastic_training.py):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  python -m repro.launch.train --arch stablelm-1.6b --reduced --workers 4 \\
      --steps 50 --straggler-tolerance 1
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--tile-samples", type=int, default=2)
    ap.add_argument("--tiles-per-worker", type=int, default=2)
    ap.add_argument("--replication", type=int, default=2)
    ap.add_argument("--straggler-tolerance", type=int, default=0)
    ap.add_argument("--drop-stragglers", type=int, default=0,
                    help="simulate this many dropped workers per step")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="per-step preemption probability per worker")
    ap.add_argument("--speed-sigma", type=float, default=0.3,
                    help="lognormal sigma of simulated worker speeds")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    import jax

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import (
        MarkovChurnTrace, USECScheduler, cyclic_placement,
    )
    from repro.data import TokenPipeline
    from repro.launch import sharding as shr
    from repro.launch.mesh import make_worker_mesh
    from repro.models import build_model
    from repro.optim import adamw, warmup_cosine
    from repro.runtime import checkpoint as ckpt
    from repro.runtime.compression import init_state as comp_init
    from repro.runtime.executor import block_plan
    from repro.runtime.simulate import SpeedProcess, StragglerProcess, simulate_step
    from repro.runtime.trainstep import make_usec_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = build_model(cfg)

    n = args.workers
    mesh = make_worker_mesh(n, args.model_shards)
    g_tiles = args.tiles_per_worker * n
    placement = cyclic_placement(n, g_tiles, args.replication)
    pipe = TokenPipeline(cfg, placement, seq_len=args.seq_len,
                         tile_samples=args.tile_samples, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    true_speeds = SpeedProcess(
        base=np.exp(rng.normal(0, args.speed_sigma, n)) + 0.1,
        jitter_sigma=0.05, seed=args.seed,
    )
    sched = USECScheduler(
        placement, rows_per_tile=1,
        initial_speeds=np.ones(n),
        stragglers=args.straggler_tolerance,
        gamma=0.5,
    )
    churn = MarkovChurnTrace(
        n, p_preempt=args.churn, p_arrive=3 * args.churn + 1e-9,
        min_available=max(args.replication, 1 + args.straggler_tolerance),
        seed=args.seed, placement=placement,
        min_holders=1 + args.straggler_tolerance,
    )
    straggle = StragglerProcess(count=args.drop_stragglers, mode="uniform",
                                seed=args.seed)

    params = bundle.init(jax.random.PRNGKey(args.seed))
    with jax.set_mesh(mesh):
        pshard = shr.param_shardings(
            jax.eval_shape(bundle.init, jax.random.PRNGKey(args.seed)), cfg, mesh
        )
        params = jax.device_put(params, pshard)
        opt = adamw.init(params)
        comp = comp_init(params) if args.compress_grads else None
        t_stage = max(len(z) for z in placement.storage_sets())
        b_max = sched.t_max
        step_fn = make_usec_train_step(
            bundle, mesh, t_stage, b_max,
            compress_grads=args.compress_grads,
            grad_shardings=pshard if args.model_shards > 1 else None,
        )

        start = 0
        if args.resume and args.ckpt_dir:
            latest = ckpt.latest_checkpoint(args.ckpt_dir)
            if latest:
                start, tree, extra = ckpt.restore_checkpoint(
                    latest, {"params": params, "opt": opt}
                )
                params, opt = tree["params"], tree["opt"]
                if "speeds" in extra:
                    sched.estimator._s = np.asarray(extra["speeds"])
                print(f"resumed from {latest} at step {start}")

        wall = 0.0
        for step in range(start, args.steps):
            avail = churn.available
            splan = sched.plan_step(avail)
            speeds_now = true_speeds.sample()
            dropped = straggle.sample(avail, speeds_now)
            timing = simulate_step(splan.plan, speeds_now, dropped=dropped)
            wall += timing.completion_time

            staged = pipe.staged_for_step(step)
            bp = block_plan(splan.plan, staged.slot_of, block_rows=1,
                            stragglers=dropped, b_max=b_max)
            lr = warmup_cosine(step, args.lr, 10, args.steps)
            params, opt, comp, metrics = step_fn(
                params, opt, comp,
                {k: jnp.asarray(v) for k, v in staged.arrays.items()},
                jnp.asarray(bp.blk_slot), jnp.asarray(bp.blk_include),
                jnp.asarray(bp.n_blocks)[:, None], jnp.asarray(lr),
            )
            # Workers report measured speeds (Algorithm 1 lines 14-15).
            loads = {w: float(splan.plan.loads()[w]) for w in avail}
            durations = {w: float(loads[w] / speeds_now[w]) for w in avail
                         if w not in dropped and loads[w] > 0}
            sched.report(loads, durations)
            churn.step()

            if args.log_every and step % args.log_every == 0:
                print(
                    f"step {step:4d} loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"c*={splan.c_star:.3f} sim_t={timing.completion_time:.3f} "
                    f"avail={len(avail)} dropped={list(dropped)}",
                    flush=True,
                )
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save_checkpoint(
                    args.ckpt_dir, step + 1, {"params": params, "opt": opt},
                    extra={"speeds": sched.estimator.speeds.tolist()},
                )
        print(f"done: {args.steps - start} steps, simulated wall time {wall:.2f} "
              f"(speed-aware USEC assignment)")
        return float(metrics["loss"]) if args.steps > start else None


if __name__ == "__main__":
    main()
