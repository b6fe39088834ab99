"""ElasticEngine throughput: steps/sec per workload × backend.

Drives the workload-agnostic front door (`repro.api.ElasticEngine`) through
identical Markov churn on both backends and emits ``BENCH_engine.json``:

- **simulate**: analytical steps/sec — each step is an n_draws-wide
  completion-time distribution, so the derived figure also reports
  scenario draws/sec (the batched engine's real unit of work);
- **device**: live steps/sec on 4 forced host devices through the shard_map
  executor (jit cache asserted == 1 per engine across churn), stepwise
  (one dispatch per step, the K=1 path);
- **device_fused**: the same churn process through the ``lax.scan`` fused
  window driver (``fuse_steps=8``): per-step plan arrays ride the scan, so
  churn onto cached plans stays in-window and a window costs ONE dispatch
  + ONE result fetch for K steps. Entries record ``device_dispatches`` and
  ``dispatches_per_step`` (~1/K) next to the steps/sec.

Each (workload, backend) cell runs a one-step warmup first (imports, jax
backend init, executor jit, step-0 plan + neighbor precompile), reported as
``cold_start_s``; ``steps_per_sec`` measures the *steady-state* churn run
that follows — the figure the replan/step optimizations target. The timed
device cells disable the per-step float64 host re-check (``verify``, a
debug knob that costs about as much as a whole fused step); exactness is
enforced by the parity tests and the smoke, which keep it on. A
``sweep_grid`` section times the batched placements × tolerances × policies
sweep (one compile_plan_batch + one stacked simulate per machine
population) against the per-cell loop.

Workloads: power_iteration (matvec fast path), matmat (8-column blocked
path), mapreduce (per-row squared norm + global sum).

Run:  PYTHONPATH=src python benchmarks/bench_engine.py [--steps 12]
      PYTHONPATH=src python benchmarks/bench_engine.py --smoke
(--smoke: tiny structural runs; asserts jit_cache_size == 1, cache-hit
replans under 10 ms, and — fused — exactly ceil(steps/K) dispatches across
boundary-aligned churn, then exits — the CI perf tripwire, no timing
flakiness.)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.launch.hostdev import (  # noqa: E402
    enable_compile_cache,
    ensure_host_devices,
    require_devices,
)

N_WORKERS = 4
ensure_host_devices(N_WORKERS)

import numpy as np  # noqa: E402

DIM = 768
COLS = 8
BASE_SPEEDS = (1000.0, 1400.0, 1900.0, 2600.0)
FUSE_STEPS = 8


def _workloads(x, seed, dim=DIM):
    from repro.api import MapReduceRows, MatMat, MatVecPowerIteration

    rng = np.random.default_rng(seed + 1)
    w = (np.round(rng.normal(size=(dim, COLS)) * 16) / 16).astype(np.float32)

    def make_mapreduce():
        import jax.numpy as jnp

        return MapReduceRows(
            row_fn=lambda xb, w2: jnp.sum(xb.astype(jnp.float32) ** 2,
                                          axis=1, keepdims=True),
            reduce_fn=lambda mapped: float(mapped.sum()),
            out_cols=1,
            ref_row_fn=lambda x64, _w: np.sum(x64 ** 2, axis=1,
                                              keepdims=True),
            name="mapreduce",
        )

    return {
        "power_iteration": lambda: MatVecPowerIteration(seed=seed),
        "matmat": lambda: MatMat(w),
        "mapreduce": make_mapreduce,
    }


def _events(placement, s_tol, steps, seed):
    from repro.core.elastic import MarkovChurnTrace

    tr = MarkovChurnTrace(
        N_WORKERS, p_preempt=0.2, p_arrive=0.6, min_available=1,
        seed=seed, placement=placement, min_holders=1 + s_tol,
    )
    return [tr.step() for _ in range(steps)]


def _run_cell(make_wl, backend, policy, cfg, x, steps, seed, s_tol, clock):
    """One (workload, backend) cell: warmup run, then the timed churn run."""
    from repro.api import ElasticEngine

    engine = ElasticEngine(
        make_wl(), policy, cfg, backend=backend,
        n_machines=N_WORKERS,
        clock=(clock() if backend == "device" else None),
    )
    t0 = time.perf_counter()
    engine.run(x if backend == "device" else None, n_steps=1)
    cold = time.perf_counter() - t0

    d0 = engine.runner.device_dispatches if backend == "device" else 0
    events = _events(engine.placement, s_tol, steps, seed)
    t0 = time.perf_counter()
    res = engine.run(None, n_steps=steps, events=iter(events))
    wall = time.perf_counter() - t0
    if backend == "device" and res.executor_cache_size != 1:
        raise AssertionError(
            f"executor recompiled ({res.executor_cache_size} jit entries)")
    entry = {
        "steps": res.n_steps,
        "wall_s": wall,
        "cold_start_s": cold,
        "steps_per_sec": res.n_steps / wall,
        "plans_compiled": res.plans_compiled,
        "cache_hits": res.cache_hits,
        "total_waste_rows": res.total_waste,
    }
    if backend == "simulate":
        entry["draws_per_sec"] = res.n_steps * cfg.n_draws / wall
    else:
        runner = engine.runner
        hit = [r.replan_s for r in res.reports if r.plan_cache_hit]
        miss = [r.replan_s for r in res.reports
                if r.replanned and not r.plan_cache_hit]
        dispatches = runner.device_dispatches - d0
        entry.update(
            jit_cache_size=res.executor_cache_size,
            device_wall_s=sum(r.wall_s for r in res.reports),
            replan_hit_mean_s=float(np.mean(hit)) if hit else None,
            replan_miss_mean_s=float(np.mean(miss)) if miss else None,
            plans_precompiled=runner.plans_precompiled,
            precompile_s=runner.precompile_s,
            fuse_steps=cfg.fuse_steps,
            device_dispatches=dispatches,
            dispatches_per_step=dispatches / max(res.n_steps, 1),
        )
    return entry, res


# First-arrival cells run under the lookahead's default environment model
# (lognormal jitter sigma=0.3): at the timed cells' near-noiseless 0.05
# there is no barrier worth skipping. Same trace, clock and config for both
# arrivals — the speedup isolates the consume rule.
ASYNC_JITTER = 0.3


def _run_async_section(x, steps, seed, policy, dev_cfg, sim_cfg, s_tol):
    """arrival="first" vs "barrier" on both backends (power iteration)."""
    from dataclasses import replace

    from repro.api import ElasticEngine, MatVecPowerIteration
    from repro.runtime import SyntheticSpeedClock

    cells = {}
    for arrival in ("barrier", "first"):
        engine = ElasticEngine(
            MatVecPowerIteration(seed=seed), policy,
            replace(dev_cfg, arrival=arrival), backend="device",
            n_machines=N_WORKERS,
            clock=SyntheticSpeedClock(list(BASE_SPEEDS),
                                      jitter_sigma=ASYNC_JITTER, seed=seed),
        )
        engine.run(x, n_steps=1)
        events = _events(engine.placement, s_tol, steps, seed)
        t0 = time.perf_counter()
        res = engine.run(None, n_steps=steps, events=iter(events))
        wall = time.perf_counter() - t0
        if arrival == "first" and res.executor_cache_size != 1:
            raise AssertionError(
                f"first-arrival executor recompiled "
                f"({res.executor_cache_size} jit entries)")
        modeled = float(sum(r.modeled_completion for r in res.reports))
        cells[arrival] = {
            "steps": res.n_steps,
            "wall_s": wall,
            "modeled_total_s": modeled,
            "modeled_steps_per_sec": res.n_steps / modeled,
            "realized_stragglers_total": sum(len(r.straggled)
                                             for r in res.reports),
            "jit_cache_size": res.executor_cache_size,
        }
    device = {
        "backend": "device",
        "stragglers": s_tol,
        "jitter_sigma": ASYNC_JITTER,
        **cells,
        "first_vs_barrier_speedup": (
            cells["barrier"]["modeled_total_s"]
            / cells["first"]["modeled_total_s"]),
    }

    # Simulate backend: the same knob swaps the pricing model ("order" vs
    # the legacy "coverage"); the ratio is the analytic cost of waiting for
    # whole workers instead of the idealized per-segment master.
    sim = {}
    for arrival in ("barrier", "first"):
        eng = ElasticEngine(
            MatVecPowerIteration(seed=seed), policy,
            replace(sim_cfg, arrival=arrival, jitter_sigma=ASYNC_JITTER),
            backend="simulate", n_machines=N_WORKERS)
        res = eng.run(n_steps=steps)
        sim[arrival] = {
            "steps": res.n_steps,
            "completion_model": replace(sim_cfg, arrival=arrival)
            .completion_model,
            "mean_completion_s": float(res.completion_times.mean()),
        }
    return {
        "device": device,
        "simulate": {
            "backend": "simulate",
            "stragglers": s_tol,
            "jitter_sigma": ASYNC_JITTER,
            **sim,
            "order_vs_coverage_ratio": (
                sim["first"]["mean_completion_s"]
                / sim["barrier"]["mean_completion_s"]),
        },
    }


def _run_sweep_section(seed):
    """Batched sweep_grid vs the per-cell loop on one grid (draws/sec)."""
    from repro.core import cyclic_placement, man_placement
    from repro.runtime.scenarios import SweepConfig, sweep_grid

    placements = {
        "cyclic": cyclic_placement(8, 8, 3),
        "man": man_placement(6, 3),
    }
    cfg = SweepConfig(n_draws=4000, rows_per_tile=96, seed=seed)
    policies = (("none", 0), ("uniform", 1))
    kw = dict(tolerances=(0, 1), straggler_policies=policies, cfg=cfg)

    t0 = time.perf_counter()
    cells = sweep_grid(placements, batched=True, **kw)
    wall_b = time.perf_counter() - t0
    t0 = time.perf_counter()
    sweep_grid(placements, batched=False, **kw)
    wall_c = time.perf_counter() - t0
    draws = len(cells) * cfg.n_draws
    return {
        "cells": len(cells),
        "n_draws_per_cell": cfg.n_draws,
        "wall_s": wall_b,
        "draws_per_sec": draws / wall_b,
        "per_cell_wall_s": wall_c,
        "speedup_vs_per_cell": wall_c / wall_b,
    }


def run(steps: int = 12, seed: int = 0, out: str = "BENCH_engine.json",
        csv: bool = True, dim: int = DIM):
    from repro.api import EngineConfig, Policy
    from repro.runtime import SyntheticSpeedClock, make_exact_matrix

    x = make_exact_matrix(dim, seed)
    s_tol = 1
    policy = Policy(placement="cyclic", replication=2 + s_tol,
                    stragglers=s_tol)
    cfg = EngineConfig(block_rows=16, verify="exact", n_draws=256, seed=seed,
                       jitter_sigma=0.2, initial_speeds=BASE_SPEEDS)

    def clock():
        return SyntheticSpeedClock(list(BASE_SPEEDS), jitter_sigma=0.05,
                                   seed=seed)

    from dataclasses import replace

    # Device cells time the RUNTIME, so the per-step float64 host re-check
    # (verify="exact", a debug knob costing ~3ms/step — comparable to the
    # whole fused step) is off for the timed churn runs; bit-exactness is
    # enforced by the parity tests and the CI smoke, which keep it on.
    dev_cfg = replace(cfg, verify=None)
    # The fused device cell runs MORE steps on the same Markov churn
    # process: windows of FUSE_STEPS amortize the dispatch round-trip, and
    # the longer trace makes the steady-state figure stable.
    fused_cfg = replace(dev_cfg, fuse_steps=FUSE_STEPS)
    fused_steps = max(steps, 8 * FUSE_STEPS)

    results = {}
    for wname, make_wl in _workloads(x, seed, dim).items():
        results[wname] = {}
        for backend, bcfg, bsteps in (
            ("simulate", cfg, steps),
            ("device", dev_cfg, steps),
            ("device_fused", fused_cfg, fused_steps),
        ):
            entry, _ = _run_cell(make_wl, backend.split("_")[0], policy,
                                 bcfg, x, bsteps, seed, s_tol, clock)
            results[wname][backend] = entry
            if csv:
                if backend == "simulate":
                    extra = f"{entry.get('draws_per_sec', 0):.0f} draws/s"
                else:
                    extra = (
                        f"jit entries {entry['jit_cache_size']}; "
                        f"K={entry['fuse_steps']}; "
                        f"{entry['dispatches_per_step']:.2f} dispatches/step"
                    )
                print(f"engine_{wname}_{backend},"
                      f"{1e6 * entry['wall_s'] / max(entry['steps'], 1):.1f},"
                      f"{entry['steps_per_sec']:.2f} steps/s over "
                      f"{entry['steps']} steps (cold start "
                      f"{entry['cold_start_s']:.2f}s); {extra}")
        fused = results[wname]["device_fused"]
        fused["speedup_vs_stepwise"] = (
            fused["steps_per_sec"] / results[wname]["device"]["steps_per_sec"]
        )
        if csv:
            print(f"engine_{wname}_fused_speedup,0,"
                  f"{fused['speedup_vs_stepwise']:.2f}x vs stepwise device")

    async_cells = _run_async_section(x, steps, seed, policy, dev_cfg, cfg,
                                     s_tol)
    if csv:
        dev = async_cells["device"]
        print(f"engine_async_device,"
              f"{1e6 * dev['first']['wall_s'] / max(dev['first']['steps'], 1):.1f},"
              f"first {dev['first']['modeled_steps_per_sec']:.1f} vs barrier "
              f"{dev['barrier']['modeled_steps_per_sec']:.1f} modeled steps/s "
              f"({dev['first_vs_barrier_speedup']:.2f}x) at jitter "
              f"{ASYNC_JITTER}; jit entries "
              f"{dev['first']['jit_cache_size']}")
        sim_a = async_cells["simulate"]
        print(f"engine_async_simulate,0,"
              f"order/coverage completion ratio "
              f"{sim_a['order_vs_coverage_ratio']:.3f} over "
              f"{sim_a['first']['steps']} steps")

    sweep = _run_sweep_section(seed)
    if csv:
        print(f"engine_sweep_grid,{1e6 * sweep['wall_s']:.0f},"
              f"{sweep['draws_per_sec']:.0f} draws/s over "
              f"{sweep['cells']} cells; "
              f"{sweep['speedup_vs_per_cell']:.2f}x vs per-cell loop")

    doc = {
        "benchmark": "elastic_engine",
        "n_workers": N_WORKERS,
        "dim": dim,
        "matmat_cols": COLS,
        "stragglers": s_tol,
        "fuse_steps": FUSE_STEPS,
        "seed": seed,
        "results": results,
        "async": async_cells,
        "sweep_grid": sweep,
    }
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    if csv:
        print(f"# wrote {out}")
    return doc


def run_smoke(seed: int = 0) -> None:
    """CI tripwire: tiny config, structural assertions, no timing averages.

    Catches step-path regressions (recompiles, replans falling off the
    cache-hit fast path) without depending on absolute machine speed.
    """
    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
    from repro.runtime import SyntheticSpeedClock, make_exact_matrix

    dim = 4 * 32
    x = make_exact_matrix(dim, seed)
    policy = Policy(placement="cyclic", replication=3, stragglers=1)
    cfg = EngineConfig(block_rows=16, verify="exact", n_draws=16, seed=seed,
                       initial_speeds=BASE_SPEEDS)
    engine = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy, cfg, backend="device",
        n_machines=N_WORKERS,
        clock=SyntheticSpeedClock(list(BASE_SPEEDS), jitter_sigma=0.0,
                                  seed=seed),
    )
    engine.run(x, n_steps=1)                    # warmup: jit + step-0 plan
    res = engine.run(None, n_steps=3)           # steady state, static trace
    assert res.executor_cache_size == 1, (
        f"jit cache grew to {res.executor_cache_size}: the step recompiled")
    hits = [r.replan_s for r in res.reports if r.plan_cache_hit]
    assert hits, "no cache-hit steps in a static 3-step run"
    assert max(hits) < 10e-3, (
        f"cache-hit replan took {max(hits) * 1e3:.1f}ms (>= 10ms): "
        "the hit path is doing real work again")
    sim = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy,
        cfg, backend="simulate", n_machines=N_WORKERS)
    sres = sim.run(n_steps=3)
    assert sres.completion_times.shape == (3, cfg.n_draws)
    assert np.isfinite(sres.completion_times).all()

    # Fused windows: K steps per dispatch must stay structural — ONE
    # compiled window driver across churn, and exactly ceil(steps / K)
    # dispatches when churn lands on window boundaries onto precompiled
    # memberships (the speculative precompiler's contract). No timing
    # averages, so this cannot flake on slow runners.
    import math
    from dataclasses import replace

    from repro.core.elastic import scripted_trace

    K, steps = 4, 8
    fused = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy,
        replace(cfg, fuse_steps=K), backend="device",
        n_machines=N_WORKERS,
        clock=SyntheticSpeedClock(list(BASE_SPEEDS), jitter_sigma=0.0,
                                  seed=seed),
    )
    fused.run(x, n_steps=1)        # warmup: jit, step-0 plan + neighbors
    runner = fused.runner
    d0 = runner.device_dispatches
    # Churn at steps 0 and 4 = the window boundaries at K=4, onto
    # memberships the warmup's neighbor precompile already planned.
    fres = fused.run(None, n_steps=steps,
                     events=scripted_trace(N_WORKERS, {
                         0: ((3,), ()), 4: ((), (3,))}))
    dispatches = runner.device_dispatches - d0
    assert fres.executor_cache_size == 1, (
        f"fused jit cache grew to {fres.executor_cache_size} across churn")
    assert dispatches == math.ceil(steps / K), (
        f"{dispatches} dispatches for {steps} steps at fuse_steps={K} "
        f"(expected ceil = {math.ceil(steps / K)}): churn broke a window")
    assert fres.churn_events == 2 and len(fres.reports) == steps

    # First-arrival mode: the per-worker dispatch must hold the same
    # jit-cache-of-1 invariant (worker identity is traced data), derive
    # realized stragglers from arrival order under a jittery clock, and
    # the bench JSONs must carry the async cells (their structure is what
    # downstream tooling reads).
    first = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy,
        replace(cfg, arrival="first"), backend="device",
        n_machines=N_WORKERS,
        clock=SyntheticSpeedClock(list(BASE_SPEEDS), jitter_sigma=0.3,
                                  seed=seed),
    )
    first.run(x, n_steps=1)
    ares = first.run(None, n_steps=4)
    assert ares.executor_cache_size == 1, (
        f"first-arrival jit cache grew to {ares.executor_cache_size}")
    assert any(r.straggled for r in ares.reports), (
        "arrival='first' derived no stragglers under jitter 0.3")

    # Decentralized re-planning: a mid-run scheduler kill must not change a
    # bit vs the central static run above, the jit cache must stay at one
    # entry, and a warmed plan table must serve cached memberships with
    # ZERO on-demand solves (the replicated-table steady-state contract).
    dec = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy,
        replace(cfg, replan="decentral"), backend="device",
        n_machines=N_WORKERS,
        clock=SyntheticSpeedClock(list(BASE_SPEEDS), jitter_sigma=0.0,
                                  seed=seed),
    )
    dec.run(x, n_steps=1)
    dres = dec.run(None, n_steps=3, kill_scheduler_at=1)
    assert dec.runner.scheduler_killed, "fault injection did not land"
    assert dres.executor_cache_size == 1, (
        f"decentral jit cache grew to {dres.executor_cache_size}")
    assert np.array_equal(dres.result.eigvec, res.result.eigvec), (
        "scheduler kill under replan='decentral' changed the output bits")
    assert dres.result.residuals == res.result.residuals
    planner = dec.runner.planning_master
    m = dec.runner.membership
    planner.plan_batch([m])
    solves = planner.on_demand_solves
    planner.plan_step(m)
    assert planner.on_demand_solves == solves, (
        "decentral replan solved on-demand for a cached membership")

    import bench_elastic_runner
    cell = bench_elastic_runner.run_async_cell(x, 0, 3, seed)
    assert cell["s0_bitwise_equal"] and cell["first"]["jit_cache_size"] == 1
    for key in ("first_vs_barrier_speedup", "barrier", "first"):
        assert key in cell, f"async cell missing {key}"
    dcell = bench_elastic_runner.run_decentral_cell(x, 0, 3, seed)
    assert dcell["bitwise_equal_to_central"]
    assert dcell["on_demand_solves_on_cached"] == 0
    assert dcell["jit_cache_size"] == 1
    print(f"bench-smoke OK: jit_cache_size=1, "
          f"cache-hit replan {max(hits) * 1e6:.0f}us, "
          f"simulate {sres.n_steps}x{cfg.n_draws} draws finite, "
          f"fused {dispatches} dispatches / {steps} steps at K={K} "
          f"across churn, first-arrival derived "
          f"{sum(len(r.straggled) for r in ares.reports)} stragglers "
          f"at jit cache 1, async cells present, decentral survived a "
          f"mid-run scheduler kill bitwise with "
          f"{dcell['on_demand_solves_on_cached']} on-demand solves on "
          f"cached memberships (lookup "
          f"{dcell['table_lookup_s'] * 1e6:.0f}us vs solve "
          f"{dcell['on_demand_solve_s'] * 1e6:.0f}us)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny structural-assertion run for CI")
    args = ap.parse_args()
    require_devices(N_WORKERS)
    enable_compile_cache()
    if args.smoke:
        run_smoke(seed=args.seed)
    else:
        run(steps=args.steps, seed=args.seed, out=args.out)
