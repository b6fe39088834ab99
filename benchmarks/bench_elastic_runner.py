"""Trajectory benchmark of the live elastic runner (real execution, 4 workers).

Drives :class:`repro.runtime.ElasticRunner` through Markov churn on forced
host devices and emits a ``BENCH_elastic_runner.json`` trajectory:

- **steps/sec** — measured wall time of the jitted shard_map step,
- **replan latency** — host-side planning cost per step, split by plan-cache
  hit (array swap) vs miss (LP solve + filling + compile + block expansion),
- **transition waste** — rows moved beyond the unavoidable ones per re-plan,
- **cross-check** — the runner's per-step modeled completion (derived from
  the *block plan* the devices actually executed) against the analytical
  predictions of :func:`repro.runtime.simulate.simulate_batch` (derived from
  the *compiled plan*). At S=0 the two must agree to float precision — two
  independent code paths computing the paper's Definition 3. At S=1 the gap
  is the first-arrival headroom: the synchronous psum barrier waits for all
  holders, the paper's master takes the fastest — the measured upside of a
  future async-combine runtime.

Run:  PYTHONPATH=src python benchmarks/bench_elastic_runner.py [--steps 24]
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

BASE_SPEEDS = [1000.0, 1400.0, 1900.0, 2600.0]   # rows/second
DIM = 768


def _markov_events(trace, n):
    for _ in range(n):
        yield trace.step()


def run_phase(x, s_tol: int, steps: int, seed: int):
    """One churn trajectory at tolerance S; returns (trajectory, summary)."""
    from repro.core import cyclic_placement
    from repro.core.elastic import MarkovChurnTrace
    from repro.runtime import (
        ElasticRunner,
        RunnerConfig,
        SyntheticSpeedClock,
        quantize_unit,
    )
    from repro.runtime.simulate import simulate_batch

    placement = cyclic_placement(N_WORKERS, N_WORKERS, 2 + s_tol)
    clock = SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=0.05, seed=seed)
    runner = ElasticRunner(
        x, placement,
        RunnerConfig(block_rows=16, stragglers=s_tol, verify="exact"),
        initial_speeds=BASE_SPEEDS,
        clock=clock,
    )
    trace = MarkovChurnTrace(
        N_WORKERS, p_preempt=0.2, p_arrive=0.6, min_available=1,
        seed=seed, placement=placement, min_holders=1 + s_tol,
    )
    rng = np.random.default_rng(seed + 7)
    w = quantize_unit(rng.normal(size=DIM))
    traj = []
    for i, ev in enumerate(_markov_events(trace, steps)):
        y, rep = runner.step(w, event=ev)
        plan = runner.current_plan
        # Analytical prediction from the compiled plan, under the realized
        # speeds the clock drew for this step. simulate's unit is tile-time
        # at speed tiles/sec; the clock speaks rows/sec -> scale by
        # rows_per_tile to land in seconds.
        realized = clock.history[i]
        predicted = float(simulate_batch(
            plan, (realized / runner.rows_per_tile)[None, :]
        ).completion_times[0])
        w = quantize_unit(y)
        traj.append({
            "step": rep.step,
            "available": list(rep.available),
            "replanned": rep.replanned,
            "plan_cache_hit": rep.plan_cache_hit,
            "replan_s": rep.replan_s,
            "wall_s": rep.wall_s,
            "modeled_completion_s": rep.modeled_completion,
            "predicted_completion_s": predicted,
            "waste_rows": rep.waste,
            "jit_cache_size": rep.jit_cache_size,
        })

    modeled = np.array([t["modeled_completion_s"] for t in traj])
    predicted = np.array([t["predicted_completion_s"] for t in traj])
    rel = np.abs(modeled - predicted) / np.maximum(predicted, 1e-12)
    wall = np.array([t["wall_s"] for t in traj])
    replan = np.array([t["replan_s"] for t in traj])
    hits = np.array([t["plan_cache_hit"] for t in traj], dtype=bool)
    misses = np.array([t["replanned"] and not t["plan_cache_hit"] for t in traj],
                      dtype=bool)
    # Replans triggered by a membership change: pre-neighbor-precompilation
    # these were all cache misses (the ~70ms replan-on-churn cost the paper's
    # "short notice" reaction time is about); now they should be array swaps.
    churny = np.array(
        [t["replanned"] and i > 0 for i, t in enumerate(traj)], dtype=bool)
    summary = {
        "stragglers": s_tol,
        "steps": steps,
        "steps_per_sec": float(len(traj) / wall.sum()),
        # steady state: step 1 pays the one-time executor jit compile
        "steps_per_sec_steady": float((len(traj) - 1) / wall[1:].sum())
        if len(traj) > 1 else None,
        "mean_wall_s": float(wall.mean()),
        "replan_latency_mean_s": float(replan.mean()),
        "replan_latency_cache_hit_s": float(replan[hits].mean()) if hits.any() else None,
        "replan_latency_cache_miss_s": float(replan[misses].mean()) if misses.any() else None,
        "replan_latency_churn_s": float(replan[churny].mean()) if churny.any() else None,
        "plans_compiled": runner.plans_compiled,
        "plans_precompiled": runner.plans_precompiled,
        "precompile_s_total": runner.precompile_s,
        "plan_cache_hits": runner.cache_hits,
        "churn_events": runner.churn_events,
        "total_waste_rows": runner.total_waste,
        "jit_cache_size": runner.executor_cache_size,
        "crosscheck_max_rel_err": float(rel.max()),
        # barrier_vs_first_arrival > 1 means an async combine would win
        "barrier_vs_first_arrival": float((modeled / predicted).mean()),
    }
    if s_tol == 0 and summary["crosscheck_max_rel_err"] > 1e-9:
        raise AssertionError(
            f"S=0 cross-check failed: runner modeled completion diverges from "
            f"simulate_batch by {summary['crosscheck_max_rel_err']:.3e}"
        )
    if runner.executor_cache_size != 1:
        raise AssertionError(
            f"executor recompiled: {runner.executor_cache_size} jit entries")
    return traj, summary


# The async cells measure the first-arrival consume rule under the
# scheduler lookahead's own default environment model (lognormal jitter
# sigma=0.3, a straggler-prone fleet) — at the bench's near-noiseless 0.05
# the slowest worker is barely slower than the rest and there is little
# barrier to stop paying. Both arrivals run the SAME config, trace, and
# duration draws; the speedup is purely the consume rule.
ASYNC_JITTER = 0.3


def run_async_cell(x, s_tol: int, steps: int, seed: int):
    """first vs barrier at tolerance S, same trace/clock: one async cell."""
    from repro.core import cyclic_placement
    from repro.core.elastic import MarkovChurnTrace
    from repro.runtime import (
        ElasticRunner,
        RunnerConfig,
        SyntheticSpeedClock,
        quantize_unit,
    )

    placement = cyclic_placement(N_WORKERS, N_WORKERS, 2 + s_tol)

    def one(arrival):
        runner = ElasticRunner(
            x, placement,
            RunnerConfig(block_rows=16, stragglers=s_tol, verify="exact",
                         arrival=arrival),
            initial_speeds=BASE_SPEEDS,
            clock=SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=ASYNC_JITTER,
                                      seed=seed),
        )
        trace = MarkovChurnTrace(
            N_WORKERS, p_preempt=0.2, p_arrive=0.6, min_available=1,
            seed=seed, placement=placement, min_holders=1 + s_tol,
        )
        w = quantize_unit(
            np.random.default_rng(seed + 7).normal(size=x.shape[1]))
        ys, modeled, straggled = [], [], 0
        for ev in _markov_events(trace, steps):
            y, rep = runner.step(w, event=ev)
            ys.append(np.asarray(y))
            modeled.append(rep.modeled_completion)
            straggled += len(rep.straggled)
            w = quantize_unit(y)
        return ys, np.asarray(modeled), straggled, runner

    ys_b, mod_b, _, _ = one("barrier")
    ys_f, mod_f, n_straggled, runner_f = one("first")
    if runner_f.executor_cache_size != 1:
        raise AssertionError(
            f"first-arrival executor recompiled: "
            f"{runner_f.executor_cache_size} jit entries")
    if s_tol == 0:
        # with no straggler budget nothing can be skipped: the per-worker
        # winner-gather must reproduce the psum barrier bit for bit
        if not all(np.array_equal(a, b) for a, b in zip(ys_f, ys_b)):
            raise AssertionError("S=0 first-arrival diverged from barrier")
    speedup = float(mod_b.sum() / mod_f.sum())
    if s_tol >= 1 and speedup < 1.15:
        raise AssertionError(
            f"S={s_tol} first-arrival speedup {speedup:.3f} < 1.15x")
    return {
        "stragglers": s_tol,
        "steps": steps,
        "jitter_sigma": ASYNC_JITTER,
        "barrier": {
            "arrival": "barrier",
            "modeled_total_s": float(mod_b.sum()),
            "modeled_steps_per_sec": float(steps / mod_b.sum()),
        },
        "first": {
            "arrival": "first",
            "modeled_total_s": float(mod_f.sum()),
            "modeled_steps_per_sec": float(steps / mod_f.sum()),
            "realized_stragglers_total": n_straggled,
            "jit_cache_size": runner_f.executor_cache_size,
        },
        "first_vs_barrier_speedup": speedup,
        "s0_bitwise_equal": bool(s_tol == 0),
    }


def run_decentral_cell(x, s_tol: int, steps: int, seed: int):
    """replan="decentral" vs "central" on the same churn trace: outputs must
    be bitwise-equal, and the decentralized live path must price a re-plan
    as a table LOOKUP (dict probe) instead of a solve. The cell reports the
    lookup latency next to the central planner's cache-hit/miss replan
    costs, and asserts zero on-demand solves on cached memberships — the
    steady-state contract the neighbor precompile maintains."""
    from repro.core import cyclic_placement
    from repro.core.decentral import DecentralPlanner
    from repro.core.elastic import MarkovChurnTrace
    from repro.runtime import (
        ElasticRunner,
        RunnerConfig,
        SyntheticSpeedClock,
        quantize_unit,
    )

    placement = cyclic_placement(N_WORKERS, N_WORKERS, 2 + s_tol)

    def one(replan):
        runner = ElasticRunner(
            x, placement,
            RunnerConfig(block_rows=16, stragglers=s_tol, verify="exact",
                         replan=replan),
            initial_speeds=BASE_SPEEDS,
            clock=SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=0.05,
                                      seed=seed),
        )
        trace = MarkovChurnTrace(
            N_WORKERS, p_preempt=0.2, p_arrive=0.6, min_available=1,
            seed=seed, placement=placement, min_holders=1 + s_tol,
        )
        w = quantize_unit(
            np.random.default_rng(seed + 7).normal(size=x.shape[1]))
        ys, reports = [], []
        for ev in _markov_events(trace, steps):
            y, rep = runner.step(w, event=ev)
            ys.append(np.asarray(y))
            reports.append(rep)
            w = quantize_unit(y)
        return ys, reports, runner

    ys_c, reps_c, _ = one("central")
    ys_d, _, runner_d = one("decentral")
    if not all(np.array_equal(a, b) for a, b in zip(ys_c, ys_d)):
        raise AssertionError(
            "decentral replan diverged bitwise from the central master")
    if runner_d.executor_cache_size != 1:
        raise AssertionError(
            f"decentral executor recompiled: "
            f"{runner_d.executor_cache_size} jit entries")
    planner = runner_d.planning_master
    if not isinstance(planner, DecentralPlanner):
        raise AssertionError("decentral runner is not planning via a replica")

    # Lookup latency: warm the table for the current membership at the
    # current snapshot, then replans are pure dict probes — ZERO solves.
    m = runner_d.membership
    planner.plan_batch([m])
    solves_before = planner.on_demand_solves
    repeat = 50
    t0 = time.perf_counter()
    for _ in range(repeat):
        planner.plan_step(m)
    lookup_s = (time.perf_counter() - t0) / repeat
    solves_on_cached = planner.on_demand_solves - solves_before
    if solves_on_cached != 0:
        raise AssertionError(
            f"{solves_on_cached} on-demand solves on a cached membership "
            f"(the lookup path fell back to solving)")

    # On-demand solve latency for the same membership (table cleared each
    # round) — the cost a cold replica pays, and the denominator of the
    # lookup-vs-solve budget row in docs/architecture.md.
    n_solve = 5
    t0 = time.perf_counter()
    for _ in range(n_solve):
        planner.table.clear()
        planner.plan_step(m)
    solve_s = (time.perf_counter() - t0) / n_solve

    hit = [r.replan_s for r in reps_c if r.plan_cache_hit]
    miss = [r.replan_s for r in reps_c
            if r.replanned and not r.plan_cache_hit]
    return {
        "stragglers": s_tol,
        "steps": steps,
        "bitwise_equal_to_central": True,
        "jit_cache_size": runner_d.executor_cache_size,
        "table_hits": planner.table_hits,
        "on_demand_solves_total": planner.on_demand_solves,
        "on_demand_solves_on_cached": solves_on_cached,
        "table_lookup_s": lookup_s,
        "on_demand_solve_s": solve_s,
        "lookup_vs_solve_speedup": solve_s / max(lookup_s, 1e-12),
        "central_replan_cache_hit_s": float(np.mean(hit)) if hit else None,
        "central_replan_cache_miss_s": float(np.mean(miss)) if miss else None,
    }


def run(steps: int = 24, seed: int = 0, out: str = "BENCH_elastic_runner.json",
        csv: bool = True):
    from repro.runtime import make_exact_matrix

    x = make_exact_matrix(DIM, seed)

    phases = {}
    for s_tol in (0, 1):
        traj, summary = run_phase(x, s_tol, steps, seed)
        phases[f"S{s_tol}"] = {"summary": summary, "trajectory": traj}
        if csv:
            tag = f"elastic_runner_S{s_tol}"
            print(f"{tag}_steps_per_sec,{1e6 / summary['steps_per_sec']:.1f},"
                  f"{summary['steps_per_sec']:.2f} steps/s over {steps} steps, "
                  f"{summary['churn_events']} churn events")
            print(f"{tag}_replan_latency,{summary['replan_latency_mean_s'] * 1e6:.1f},"
                  f"cache hit "
                  f"{(summary['replan_latency_cache_hit_s'] or 0) * 1e6:.0f}us vs "
                  f"miss {(summary['replan_latency_cache_miss_s'] or 0) * 1e6:.0f}us; "
                  f"churn replan "
                  f"{(summary['replan_latency_churn_s'] or 0) * 1e6:.0f}us; "
                  f"{summary['plans_compiled']} compiled "
                  f"({summary['plans_precompiled']} speculative, "
                  f"{summary['precompile_s_total'] * 1e3:.0f}ms off-path) / "
                  f"{summary['plan_cache_hits']} hits")
            print(f"{tag}_crosscheck,{summary['crosscheck_max_rel_err']:.3e},"
                  f"max rel err vs simulate_batch; barrier/first-arrival = "
                  f"{summary['barrier_vs_first_arrival']:.3f}; "
                  f"waste {summary['total_waste_rows']} rows; "
                  f"jit entries {summary['jit_cache_size']}")

    cells = {}
    for s_tol in (0, 1):
        cell = run_async_cell(x, s_tol, steps, seed)
        cells[f"S{s_tol}"] = cell
        if csv:
            tag = f"elastic_runner_async_S{s_tol}"
            print(f"{tag}_speedup,{cell['first_vs_barrier_speedup']:.3f},"
                  f"first {cell['first']['modeled_steps_per_sec']:.1f} vs "
                  f"barrier {cell['barrier']['modeled_steps_per_sec']:.1f} "
                  f"modeled steps/s at jitter {ASYNC_JITTER}; "
                  f"{cell['first']['realized_stragglers_total']} realized "
                  f"stragglers; jit entries "
                  f"{cell['first']['jit_cache_size']}")

    decentral = run_decentral_cell(x, 1, steps, seed)
    if csv:
        print(f"elastic_runner_decentral,"
              f"{decentral['table_lookup_s'] * 1e6:.1f},"
              f"table lookup {decentral['table_lookup_s'] * 1e6:.0f}us vs "
              f"on-demand solve "
              f"{decentral['on_demand_solve_s'] * 1e6:.0f}us "
              f"({decentral['lookup_vs_solve_speedup']:.0f}x); central hit "
              f"{(decentral['central_replan_cache_hit_s'] or 0) * 1e6:.0f}us"
              f" / miss "
              f"{(decentral['central_replan_cache_miss_s'] or 0) * 1e6:.0f}us"
              f"; {decentral['on_demand_solves_on_cached']} solves on "
              f"cached memberships; bitwise equal to central; jit entries "
              f"{decentral['jit_cache_size']}")

    doc = {
        "benchmark": "elastic_runner",
        "n_workers": N_WORKERS,
        "dim": DIM,
        "base_speeds_rows_per_s": BASE_SPEEDS,
        "seed": seed,
        "phases": phases,
        "async": cells,
        "decentral": decentral,
    }
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    if csv:
        print(f"# wrote {out}")
    return doc


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_elastic_runner.json")
    args = ap.parse_args()
    require_devices(N_WORKERS)
    enable_compile_cache()
    run(steps=args.steps, seed=args.seed, out=args.out)
