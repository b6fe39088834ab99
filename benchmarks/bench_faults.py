"""Unannounced-failure recovery: detect→replan→re-execute latency, served
goodput vs fault rate.

Two sections, both deterministic (seeded :class:`ChaosPlan` schedules,
zero-jitter synthetic clocks) and both asserting the recovery invariant
before timing anything — a cell that is not bitwise-equal to its clean
reference is a broken cell, not a slow one:

- **engine cells**: one per fault kind — covered crash / result drop
  (masked as realized stragglers), uncovered crash at S=0 (abort →
  demote → replan → re-execute), stale plan table (re-solve), scheduler
  kill (decentral survival), dispatch timeout (silent worker censored).
  Each reports the fired :class:`FaultRecord`\\ s' modeled detection
  latency (``detect_s``), the measured host-side recovery time
  (``recover_s``, abort to re-executed step), and the whole-run wall
  overhead vs the clean run.
- **serving cells**: a seeded matvec trace driven through
  :class:`ElasticServer` at increasing fault rates (a ``result_drop``
  every k-th dispatch under S=0, so every fault aborts the window and
  requeues its coalesced requests). Reports modeled goodput, faults,
  requeues, failures — the goodput-vs-fault-rate curve
  ``BENCH_faults.json`` tracks.

Run:  PYTHONPATH=src python benchmarks/bench_faults.py [--steps 8]
      PYTHONPATH=src python benchmarks/bench_faults.py --smoke
(--smoke: the crash-recovery cell only — uncovered crash, assert bitwise
recovery + jit cache 1 + a served requeue — for the bench-smoke CI job.)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.launch.hostdev import (  # noqa: E402
    enable_compile_cache,
    ensure_host_devices,
    require_devices,
)

N_WORKERS = 4
ensure_host_devices(N_WORKERS)

import numpy as np  # noqa: E402

BASE_SPEEDS = (1000.0, 1400.0, 1900.0, 2600.0)
DIM = N_WORKERS * 96


def _engine(stragglers=1, replan="central", dispatch_timeout=None,
            speeds=BASE_SPEEDS, verify_results="off"):
    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
    from repro.runtime.elastic_runner import SyntheticSpeedClock

    return ElasticEngine(
        MatVecPowerIteration(seed=0),
        Policy(placement="cyclic", replication=3, stragglers=stragglers,
               replan=replan, verify_results=verify_results),
        EngineConfig(block_rows=16, verify="exact",
                     initial_speeds=BASE_SPEEDS,
                     dispatch_timeout=dispatch_timeout),
        backend="device", n_machines=N_WORKERS,
        clock=SyntheticSpeedClock(list(speeds), jitter_sigma=0.0, seed=0))


def _engine_cell(name, kind, step=3, worker=2, stragglers=1,
                 replan="central", n_steps=8, csv=True,
                 verify_results="off"):
    """One fault kind through a clean-vs-faulted engine pair."""
    from repro.faults import ChaosPlan, FaultSpec
    from repro.runtime.elastic_runner import make_exact_matrix

    x = make_exact_matrix(DIM, 0)
    t0 = time.perf_counter()
    clean = _engine(stragglers=stragglers, replan=replan,
                    verify_results=verify_results).run(x, n_steps=n_steps)
    clean_s = time.perf_counter() - t0

    target = {"worker": worker} if kind in (
        "worker_crash", "result_drop",
        "tile_corruption", "result_corruption") else {}
    plan = ChaosPlan([FaultSpec(kind, step, **target)])
    t1 = time.perf_counter()
    fault = _engine(stragglers=stragglers, replan=replan,
                    verify_results=verify_results).run(
        x, n_steps=n_steps, faults=plan)
    fault_s = time.perf_counter() - t1

    assert np.array_equal(fault.result.eigvec, clean.result.eigvec), name
    assert fault.executor_cache_size == 1, name
    recs = fault.fault_records
    entry = {
        "kind": kind,
        "stragglers": stragglers,
        "replan": replan,
        "actions": [r.action for r in recs],
        "detect_s": max((r.detect_s for r in recs), default=0.0),
        "recover_s": max((r.recover_s for r in recs), default=0.0),
        "recoveries": fault.recoveries,
        "clean_wall_s": clean_s,
        "fault_wall_s": fault_s,
        "overhead_s": fault_s - clean_s,
        "bitwise_equal": True,
        "jit_cache_size": fault.executor_cache_size,
        "integrity": fault.integrity,
    }
    if csv:
        print(f"fault_{name},{1e6 * fault_s / n_steps:.1f},"
              f"{'+'.join(entry['actions']) or 'none'}; "
              f"recover {1e3 * entry['recover_s']:.2f}ms; "
              f"overhead {1e3 * entry['overhead_s']:.1f}ms on "
              f"{n_steps} steps; bitwise ok, jit 1")
    return entry


def _timeout_cell(name="timeout_mask", n_steps=4, csv=True):
    """A worker 100x slower than the planner believes: dispatch_timeout
    censors it into a realized straggler, bitwise-equal to waiting."""
    from repro.runtime.elastic_runner import make_exact_matrix

    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
    from repro.runtime.elastic_runner import SyntheticSpeedClock

    x = make_exact_matrix(DIM, 0)
    # The planner believes all four workers run at 1000 rows/s; worker 0
    # actually crawls at 10 — the timeout separates modeled durations.
    real = [10.0, 1000.0, 1000.0, 1000.0]
    est = (1000.0,) * 4

    def eng(timeout):
        return ElasticEngine(
            MatVecPowerIteration(seed=0),
            Policy(placement="cyclic", replication=3, stragglers=1),
            EngineConfig(block_rows=16, verify="exact", initial_speeds=est,
                         dispatch_timeout=timeout),
            backend="device", n_machines=N_WORKERS,
            clock=SyntheticSpeedClock(real, jitter_sigma=0.0, seed=0))

    t0 = time.perf_counter()
    clean = eng(None).run(x, n_steps=n_steps)
    t1 = time.perf_counter()
    timed = eng(1.0).run(x, n_steps=n_steps)
    t2 = time.perf_counter()
    assert np.array_equal(timed.result.eigvec, clean.result.eigvec)
    recs = timed.fault_records
    entry = {
        "kind": "dispatch_timeout",
        "timeout_s": 1.0,
        "masked": sum(r.action == "masked" for r in recs),
        "detect_s": max((r.detect_s for r in recs), default=0.0),
        "clean_wall_s": t1 - t0,
        "timed_wall_s": t2 - t1,
        "bitwise_equal": True,
    }
    if csv:
        print(f"fault_{name},{1e6 * (t2 - t1) / n_steps:.1f},"
              f"{entry['masked']} slow-worker steps censored at "
              f"timeout {entry['detect_s']:.1f}s; bitwise ok")
    return entry


def _verify_overhead_cell(n_steps=8, csv=True):
    """Freivalds verification cost: the same clean run with the checker
    off vs on every step. The audit is ``O(rows + cols)`` per column
    against the step's ``O(rows · cols)`` matvec, so the fraction should
    stay well under the 10% step-time budget (reported, not asserted —
    wall noise on shared CI boxes is larger than the effect)."""
    from repro.runtime.elastic_runner import make_exact_matrix

    x = make_exact_matrix(DIM, 0)
    # Warm both paths once so neither pays first-compile inside the timer.
    _engine(verify_results="off").run(x, n_steps=2)
    _engine(verify_results="always").run(x, n_steps=2)
    t0 = time.perf_counter()
    off = _engine(verify_results="off").run(x, n_steps=n_steps)
    t1 = time.perf_counter()
    on = _engine(verify_results="always").run(x, n_steps=n_steps)
    t2 = time.perf_counter()
    assert np.array_equal(on.result.eigvec, off.result.eigvec)
    assert on.integrity["sketch_failures"] == 0, on.integrity
    off_s, on_s = t1 - t0, t2 - t1
    frac = (on_s - off_s) / off_s if off_s > 0 else 0.0
    entry = {
        "kind": "verify_overhead",
        "n_steps": n_steps,
        "off_wall_s": off_s,
        "on_wall_s": on_s,
        "overhead_fraction": frac,
        "checks": on.integrity["checks"],
        "tile_audits": on.integrity["tile_audits"],
        "budget_fraction": 0.10,
    }
    if csv:
        print(f"fault_verify_overhead,{1e6 * on_s / n_steps:.1f},"
              f"{on.integrity['checks']} Freivalds checks + "
              f"{on.integrity['tile_audits']} tile audits cost "
              f"{100 * frac:+.1f}% vs unchecked (budget 10%); bitwise ok")
    return entry


def _serve_cell(fault_rate, requests=24, seed=0, csv=True):
    """Seeded matvec trace at a given dispatch fault rate (result_drop
    under S=0: every fault aborts and requeues). Demoted workers re-arrive
    before the next request — the cell measures recovery traffic cost,
    not a shrinking fleet."""
    from repro.api import EngineConfig, Policy
    from repro.faults import ChaosPlan, FaultInjector, FaultSpec
    from repro.runtime.elastic_runner import (
        SyntheticSpeedClock,
        make_exact_matrix,
    )
    from repro.serve import ElasticServer, ServeConfig, SyntheticClock

    x = make_exact_matrix(DIM, seed)
    specs = []
    if fault_rate > 0:
        interval = max(1, int(round(1.0 / fault_rate)))
        specs = [FaultSpec("result_drop", s, worker=(j % N_WORKERS))
                 for j, s in enumerate(range(1, 2 * requests, interval))]
    inj = FaultInjector(ChaosPlan(specs)) if specs else None
    server = ElasticServer(
        x,
        Policy(placement="cyclic", replication=3, stragglers=0),
        EngineConfig(block_rows=16, initial_speeds=BASE_SPEEDS),
        ServeConfig(batch_cols=4, retry_backoff=0.05, max_retries=8),
        clock=SyntheticClock(),
        engine_clock=SyntheticSpeedClock(list(BASE_SPEEDS),
                                         jitter_sigma=0.0, seed=seed),
        n_machines=N_WORKERS,
        fault_injector=inj,
    )
    rng = np.random.default_rng(seed + 7)
    t0 = time.perf_counter()
    for i in range(requests):
        server.submit("matvec",
                      rng.integers(-3, 4, size=DIM).astype(np.float32))
        server.clock.advance(float(rng.exponential(0.05)))
        server.poll()
        lost = [n for n in range(N_WORKERS) if n not in server.available]
        if lost:
            server.feed_event(arrived=lost)
    guard = 0
    while server.queue_depth and guard < 20 * requests:
        server.drain()
        if server.queue_depth:
            server.clock.advance(0.05)   # sit out the retry backoff
            lost = [n for n in range(N_WORKERS)
                    if n not in server.available]
            if lost:
                server.feed_event(arrived=lost)
        guard += 1
    wall_s = time.perf_counter() - t0
    snap = server.metrics_snapshot()
    assert snap["requests"]["completed"] + snap["faults"]["failed"] \
        == requests, snap["requests"]
    entry = {
        "fault_rate": fault_rate,
        "requests": requests,
        "completed": snap["requests"]["completed"],
        "goodput_rps": snap["goodput_rps"],
        "p50": snap["latency"]["p50"],
        "p99": snap["latency"]["p99"],
        "faults": snap["faults"],
        "jit_cache_size": snap["lanes"]["linear"]["jit_cache_size"],
        "wall_s": wall_s,
    }
    if csv:
        f = snap["faults"]
        print(f"fault_serve_rate_{fault_rate},"
              f"{1e6 * wall_s / requests:.1f},"
              f"goodput {snap['goodput_rps']:.1f} req/s; "
              f"{f['count']} faults -> {f['requeued']} requeued, "
              f"{f['failed']} failed; p99 {snap['latency']['p99']:.3f}; "
              f"jit entries {entry['jit_cache_size']}")
    return entry


def run(n_steps: int = 8, seed: int = 0, out: str = "BENCH_faults.json",
        csv: bool = True):
    cells = {
        "covered_crash": _engine_cell(
            "covered_crash", "worker_crash", stragglers=1, n_steps=n_steps,
            csv=csv),
        "uncovered_crash": _engine_cell(
            "uncovered_crash", "worker_crash", stragglers=0,
            n_steps=n_steps, csv=csv),
        "result_drop": _engine_cell(
            "result_drop", "result_drop", stragglers=1, n_steps=n_steps,
            csv=csv),
        "stale_plan_table": _engine_cell(
            "stale_plan_table", "stale_plan_table", stragglers=1,
            n_steps=n_steps, csv=csv),
        "scheduler_kill": _engine_cell(
            "scheduler_kill", "scheduler_kill", stragglers=1,
            replan="decentral", n_steps=n_steps, csv=csv),
        "timeout_mask": _timeout_cell(csv=csv),
        # Silent-corruption defense: wrong bits on time, detected by the
        # Freivalds sketch / tile fingerprints, recovered bitwise. Worker
        # 3 wins output rows under this plan — a corrupt backup worker
        # would be absorbed unobserved.
        "tile_corruption": _engine_cell(
            "tile_corruption", "tile_corruption", worker=3, stragglers=1,
            n_steps=n_steps, csv=csv, verify_results="always"),
        "result_corruption": _engine_cell(
            "result_corruption", "result_corruption", worker=3,
            stragglers=1, n_steps=n_steps, csv=csv,
            verify_results="always"),
        "result_corruption_uncovered": _engine_cell(
            "result_corruption_uncovered", "result_corruption", worker=3,
            stragglers=0, n_steps=n_steps, csv=csv,
            verify_results="always"),
        "verify_overhead": _verify_overhead_cell(n_steps=n_steps, csv=csv),
    }
    goodput = [_serve_cell(rate, requests=3 * n_steps, seed=seed, csv=csv)
               for rate in (0.0, 0.125, 0.25)]
    doc = {
        "benchmark": "fault_recovery",
        "n_workers": N_WORKERS,
        "dim": DIM,
        "n_steps": n_steps,
        "seed": seed,
        "engine_cells": cells,
        "goodput_vs_fault_rate": goodput,
    }
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    if csv:
        print(f"# wrote {out}")
    return doc


def run_smoke(seed: int = 0) -> None:
    """The crash-recovery CI cell: an uncovered crash must abort, demote,
    replan, re-execute — bitwise-equal to the clean run on one jit entry
    — and a fault-aborted served window must requeue and complete."""
    cell = _engine_cell("smoke_uncovered_crash", "worker_crash",
                        stragglers=0, n_steps=4, csv=False)
    assert cell["recoveries"] == 1, cell
    assert cell["actions"] == ["demoted"], cell
    assert cell["recover_s"] > 0.0, cell
    # Corruption cells: silent wrong bits must be detected and recovered
    # bitwise (asserted inside _engine_cell) with the right actions.
    tile = _engine_cell("smoke_tile_corruption", "tile_corruption",
                        worker=3, stragglers=1, n_steps=4, csv=False,
                        verify_results="always")
    assert tile["actions"] == ["restaged"], tile
    assert tile["integrity"]["restaged"] == 1, tile
    res = _engine_cell("smoke_result_corruption", "result_corruption",
                       worker=3, stragglers=1, n_steps=4, csv=False,
                       verify_results="always")
    assert res["actions"] == ["quarantined"], res
    assert res["integrity"]["quarantined"] == 1, res
    assert res["integrity"]["sketch_failures"] == 1, res
    serve = _serve_cell(0.25, requests=8, seed=seed, csv=False)
    assert serve["faults"]["count"] >= 1, serve
    assert serve["faults"]["requeued"] >= 1, serve
    assert serve["completed"] == 8, serve
    assert serve["jit_cache_size"] == 1, serve
    print(f"fault_smoke,0,uncovered crash recovered bitwise in "
          f"{1e3 * cell['recover_s']:.2f}ms on jit cache "
          f"{cell['jit_cache_size']}; corrupt tile restaged + corrupt "
          f"result quarantined bitwise; served {serve['completed']}/8 "
          f"through {serve['faults']['count']} window aborts")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=8,
                    help="engine-cell run length (serve traces use 3x)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_faults.json")
    ap.add_argument("--smoke", action="store_true",
                    help="crash-recovery structural cell for CI")
    args = ap.parse_args()
    require_devices(N_WORKERS)
    enable_compile_cache()
    if args.smoke:
        run_smoke(seed=args.seed)
    else:
        run(n_steps=args.steps, seed=args.seed, out=args.out)
