"""Train-step factories: the paper's technique as a first-class training mode.

usec mode (``make_usec_train_step``)
    shard_map manual over the DP axes: every worker runs a ``fori_loop``
    whose trip count is ITS OWN plan entry (uneven loads compile to uneven
    iteration counts of one SPMD program), gathering microbatch tiles from
    its staged (uncoded, J-replicated) buffers, weighting each tile by the
    plan's inclusion mask (straggler-redundancy dedup), then meeting at a
    single psum. The optimizer update runs outside the manual region under
    GSPMD. Optional int8+error-feedback gradient compression halves the
    reduction bytes.

fsdp mode (``make_fsdp_train_step``)
    pure GSPMD ZeRO-3-style: params sharded over (dp, model), grad
    accumulation via lax.scan over global microbatches, USEC ownership
    entering as per-sample weights. For the >=100B archs where usec mode's
    per-model-shard parameter replication cannot fit HBM (DESIGN.md §6).

Both return a jitted ``step`` plus the sharding pytrees used to place its
inputs, and are exactly what launch/dryrun.py lowers for the 31 cells.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.optim import adamw
from repro.launch import sharding as shr

from . import compression


def _zeros_like_f32(tree, dtype=jnp.float32):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype), tree)


def make_usec_train_step(
    bundle,
    mesh,
    t_stage: int,
    b_max: int,
    peak_lr: float = 3e-4,
    compress_grads: bool = False,
    grad_shardings=None,
    reduced_grad_shardings=None,
    static_trips: Optional[int] = None,
    worker_axes: Optional[tuple] = None,
):
    """USEC (uneven-loop) train step.

    step(params, opt_state, comp_state, staged, mb_slot, mb_inc, n_mb, lr)
      staged:  schema dict, each (N, T_stage, mb, ...)
      mb_slot: (N, B_max) int32   — staged slot per micro-step
      mb_inc:  (N, B_max) float32 — inclusion weight (0 = redundant copy)
      n_mb:    (N, 1) int32       — per-worker trip count

    ``grad_shardings``: params-shaped pytree of NamedShardings (model-axis
    only) used to pin the fp32 gradient accumulator's layout — without it
    GSPMD replicates the fori_loop carry and the accumulator costs a full
    unsharded parameter copy per device.

    ``static_trips``: when set, run exactly that many micro-steps per worker
    (ignoring n_mb) via an unrolled-count loop whose FLOPs are visible to
    XLA's cost analysis — the roofline-accounting variant. The deployable
    program uses the dynamic per-worker trip counts (None).
    """
    cfg = bundle.cfg
    # The manual worker axes: the dp axes by default; in pure-DP mode the
    # whole mesh (params replicated, every chip a USEC worker).
    dp = tuple(worker_axes) if worker_axes else shr.dp_axes(mesh)
    loss_fn = bundle.loss_fn
    from repro.models.layers import dtype_of

    acc_dtype = dtype_of(getattr(cfg, "grad_accum_dtype", "float32"))

    def pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_shardings,
        )

    def manual_body(staged, mb_slot, mb_inc, n_mb, params):
        # Per-worker block: leading worker axis is size 1 here.
        staged = jax.tree.map(lambda a: a[0], staged)
        mb_slot, mb_inc, n_mb = mb_slot[0], mb_inc[0], n_mb[0]

        def micro(i, acc):
            grads, nll, ntok = acc
            batch = jax.tree.map(lambda a: a[mb_slot[i]], staged)
            (loss_i, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            w = mb_inc[i]
            grads = jax.tree.map(
                lambda a, b: a + (w * b.astype(jnp.float32)).astype(acc_dtype),
                grads, g,
            )
            # NOTE: the accumulator is pinned once at init; re-pinning inside
            # the body inserts copies that defeat in-place carry aliasing.
            return (grads, nll + w * loss_i, ntok + w * metrics["n_tokens"])

        acc0 = (pin(_zeros_like_f32(params, acc_dtype)), jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32))
        if static_trips is not None:
            def micro_scan(acc, i):
                return micro(i, acc), None
            grads_nll_ntok, _ = jax.lax.scan(
                micro_scan, acc0, jnp.arange(static_trips)
            )
            grads, nll, ntok = grads_nll_ntok
        else:
            grads, nll, ntok = jax.lax.fori_loop(0, n_mb[0], micro, acc0)
        # The single synchronization point — the paper's "master combine".
        axis = dp if len(dp) > 1 else dp[0]
        nll = jax.lax.psum(nll, axis)
        ntok = jax.lax.psum(ntok, axis)
        if compress_grads:
            return grads, nll, ntok  # reduced outside with compression state
        if acc_dtype != jnp.float32:
            # accumulate locally in bf16 (memory), reduce in f32 (accuracy
            # over up-to-512-way sums); wire cost is negligible either way.
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        grads = jax.lax.psum(grads, axis)
        return grads, nll, ntok

    mapped = jax.shard_map(
        manual_body,
        mesh=mesh,
        in_specs=(P(dp), P(dp), P(dp), P(dp), P()),
        out_specs=(P() if not compress_grads else P(dp), P(), P()),
        axis_names=set(dp),
        check_vma=False,
    )

    if compress_grads:
        compress_map = jax.shard_map(
            lambda g, st: compression.compress_decompress(
                g, st, dp if len(dp) > 1 else dp[0]
            ),
            mesh=mesh,
            in_specs=(P(dp), P()),
            out_specs=(P(), P()),
            axis_names=set(dp),
            check_vma=False,
        )

    def step(params, opt_state, comp_state, staged, mb_slot, mb_inc, n_mb, lr):
        if compress_grads:
            local_grads, nll, ntok = mapped(staged, mb_slot, mb_inc, n_mb, params)
            grads, comp_state = compress_map(local_grads, comp_state)
        else:
            grads, nll, ntok = mapped(staged, mb_slot, mb_inc, n_mb, params)
        denom = jnp.maximum(ntok, 1.0)
        grads = jax.tree.map(lambda g: g / denom, grads)
        if reduced_grad_shardings is not None:
            # ZeRO-1: hold the reduced gradients AND the param view
            # dp-sharded through the optimizer update (m/v are dp-sharded
            # too), so every fp32 temporary lives at 1/workers scale; only
            # the updated bf16 params are gathered back at the end.
            grads = jax.tree.map(
                jax.lax.with_sharding_constraint, grads, reduced_grad_shardings
            )
            params_upd = jax.tree.map(
                jax.lax.with_sharding_constraint, params, reduced_grad_shardings
            )
        else:
            params_upd = params
        new_params, new_opt, om = adamw.update(grads, opt_state, params_upd, lr)
        if reduced_grad_shardings is not None and grad_shardings is not None:
            # gather the updated (bf16) params back to their serving layout
            new_params = jax.tree.map(
                jax.lax.with_sharding_constraint, new_params, grad_shardings
            )
        metrics = {"loss": nll / denom, "n_tokens": ntok, **om}
        return new_params, new_opt, comp_state, metrics

    return jax.jit(step, donate_argnums=(0, 1, 2))


def make_fsdp_train_step(bundle, mesh, n_micro: int, grad_shardings=None):
    """GSPMD train step with scan-based grad accumulation and per-sample
    USEC ownership weights.

    step(params, opt_state, batch, weights, lr)
      batch:   schema dict, leading dim = global batch B (dp-sharded)
      weights: (B,) float32 — USEC inclusion weight per sample

    ``grad_shardings`` pins each per-microbatch gradient to the params'
    (dp, model) layout inside the accumulation loop — without it GSPMD
    materializes full unsharded per-layer grads and all-reduces them
    (memory + wire blow-up; see EXPERIMENTS.md §Perf).
    """
    cfg = bundle.cfg
    loss_fn = bundle.loss_fn

    def pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_shardings,
        )

    def weighted_loss(params, batch, w):
        nll, metrics = loss_fn(params, batch)
        # Per-sample weighting: scale loss sum by mean weight of the
        # microbatch (samples are tile-aligned so weights are 0/1 blocks).
        scale = jnp.mean(w)
        return nll * scale, jax.tree.map(lambda t: t * scale, metrics)

    def step(params, opt_state, batch, weights, lr):
        b = weights.shape[0]
        mb = b // n_micro

        def reshape(a):
            return a.reshape((n_micro, mb) + a.shape[1:])

        batch_m = jax.tree.map(reshape, batch)
        weights_m = weights.reshape(n_micro, mb)

        def micro(acc, xs):
            grads, nll, ntok = acc
            bm, wm = xs
            (loss_i, metrics), g = jax.value_and_grad(weighted_loss, has_aux=True)(
                params, bm, wm
            )
            g = pin(g)
            grads = jax.tree.map(lambda a, c: a + c.astype(jnp.float32), grads, g)
            return (grads, nll + loss_i, ntok + metrics["n_tokens"]), None

        acc0 = (_zeros_like_f32(params), jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32))
        (grads, nll, ntok), _ = jax.lax.scan(micro, acc0, (batch_m, weights_m))
        denom = jnp.maximum(ntok, 1.0)
        grads = jax.tree.map(lambda g: g / denom, grads)
        new_params, new_opt, om = adamw.update(grads, opt_state, params, lr)
        metrics = {"loss": nll / denom, "n_tokens": ntok, **om}
        return new_params, new_opt, metrics

    return jax.jit(step, donate_argnums=(0, 1))
