"""The paper's §V application, executed LIVE: elastic power iteration on
real (forced host) devices under preemption/arrival churn — driven through
the workload-agnostic front door, ``repro.api.ElasticEngine``.

Four workers run distributed power iteration through the shard_map executor
(Pallas ``usec_matvec`` on TPU, jnp reference on CPU). An availability trace
preempts and returns machines mid-run; the runner re-plans per membership
(memoized compiled plans), re-estimates speeds from measured step times
(EWMA, Algorithm 1), and keeps every array padded to the full worker
population — so membership changes swap plan arrays in place and the jitted
step **never recompiles** (asserted via the jit cache size).

The engine call below is the whole API: a ``MatVecPowerIteration`` workload,
a ``Policy`` naming the placement and straggler tolerance, an
``EngineConfig`` — flip ``backend="device"`` to ``"simulate"`` and the same
configuration is evaluated analytically instead (see
``examples/elastic_matmat.py`` for the two-backend version).

The demo matrix is integer-valued and the iterate is kept on a 2^-8 grid,
so every partial sum of ``y = X @ w`` is exactly representable in float32:
the distributed combine is verified **bit-exact** against a float64 host
reference after every step, across every membership state and straggler set.

Compares the cyclic placement against the MAN placement (the storage the
paper's design framework finds best — Table I), each at straggler tolerance
S=0 and S=1 (with one forced straggler per step when S=1).

Run:  PYTHONPATH=src python examples/power_iteration.py [--steps 8]
      (--churn markov for stochastic instead of scripted churn)

Expected output (wall-clock numbers vary with the host):

    == elastic power iteration: 4 workers, dim=768, 8 steps, scripted churn ==
    cyclic     S=0 | churn 5 | plans 5 (hits 3) | waste 1472 rows | latency   1.422 | ...
    optimized  S=0 | churn 5 | plans 5 (hits 3) | waste 1504 rows | latency   1.428 | ...
    cyclic     S=1 | churn 5 | plans 5 (hits 3) | waste 3104 rows | latency   2.951 | ...
    optimized  S=1 | churn 5 | plans 5 (hits 3) | waste 3072 rows | latency   2.967 | ...
    S=0: optimized (MAN) vs cyclic modeled latency: -0.4%  (~0% expected: ...)
    S=1: optimized (MAN) vs cyclic modeled latency: -0.5%  (~0% expected: ...)
    all 32 steps bit-exact (y == X @ w); executor compiled once per runner
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.launch.hostdev import (  # noqa: E402
    enable_compile_cache,
    ensure_host_devices,
    require_devices,
)

N_WORKERS = 4
ensure_host_devices(N_WORKERS)

import numpy as np  # noqa: E402

from repro.api import (  # noqa: E402
    ElasticEngine,
    EngineConfig,
    MatVecPowerIteration,
    Policy,
)
from repro.core.elastic import MarkovChurnTrace, scripted_trace  # noqa: E402
from repro.runtime import SyntheticSpeedClock, make_exact_matrix  # noqa: E402

DIM = 768          # divisible by every placement's tile count (4 and 6)
# EC2-like heterogeneity, 4 workers, in rows/second (the clock's unit).
BASE_SPEEDS = [1000.0, 1300.0, 1700.0, 2200.0]

# Scripted churn: single-machine-down states only, so every placement in the
# grid keeps all tiles reachable (J-1 >= 1) and S=1 plans stay feasible
# (restricted replication >= 2). Three events land within the first three
# steps so even a --steps 3 smoke run exercises preemption AND arrival.
SCRIPT = {
    0: ((3,), ()),        # preempt worker 3
    1: ((1,), (3,)),      # 3 returns, 1 preempted
    2: ((), (1,)),        # 1 returns -> full membership
    4: ((2,), ()),
    5: ((), (2,)),
}


def events_for(args, placement, s_tol):
    if args.churn == "markov":
        tr = MarkovChurnTrace(
            N_WORKERS, p_preempt=0.25, p_arrive=0.6, min_available=1,
            seed=args.seed, placement=placement, min_holders=1 + s_tol,
        )
        return (tr.step() for _ in range(args.steps))
    return scripted_trace(N_WORKERS, SCRIPT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--churn", choices=("scripted", "markov"), default="scripted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    require_devices(N_WORKERS)
    enable_compile_cache()

    x = make_exact_matrix(DIM, args.seed)
    true_eig = float(np.linalg.eigvalsh(x.astype(np.float64))[-1])
    print(f"== elastic power iteration: {N_WORKERS} workers, dim={DIM}, "
          f"{args.steps} steps, {args.churn} churn ==")

    grid = [
        ("cyclic", 0), ("optimized", 0),
        ("cyclic", 1), ("optimized", 1),
    ]
    results, steps_total = {}, 0
    for kind, s_tol in grid:
        # Fresh per-config rng: every cell sees the SAME straggler draws, so
        # the cyclic-vs-optimized latency lines compare placements, not
        # rng-state residue.
        rng = np.random.default_rng(args.seed + 1)

        def one_straggler(step, membership):
            """One forced straggler per step, drawn from the live membership."""
            return (int(rng.choice(membership)),) if len(membership) > 1 else ()

        j = 2 + s_tol   # storage overhead scales with the tolerance
        engine = ElasticEngine(
            MatVecPowerIteration(seed=args.seed),
            Policy(placement="cyclic" if kind == "cyclic" else "man",
                   replication=j, stragglers=s_tol),
            EngineConfig(block_rows=16, verify="exact"),
            backend="device",
            n_machines=N_WORKERS,
            clock=SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=0.03,
                                      seed=args.seed),
        )
        res = engine.run(
            x, n_steps=args.steps,
            events=events_for(args, engine.placement, s_tol),
            straggler_sets=one_straggler if s_tol > 0 else None,
        ).result
        results[(kind, s_tol)] = res
        steps_total += len(res.reports)
        assert res.executor_cache_size == 1, (
            f"membership churn recompiled the executor "
            f"({res.executor_cache_size} jit entries)"
        )
        if args.churn == "scripted" and args.steps >= 3:
            assert res.churn_events >= 3, res.churn_events
        print(f"{kind:10s} S={s_tol} | churn {res.churn_events} | "
              f"plans {res.plans_compiled} (hits {res.cache_hits}) | "
              f"waste {res.total_waste} rows | "
              f"latency {res.total_modeled_latency:7.3f} | "
              f"{res.steps_per_sec:5.1f} steps/s | "
              f"eig {res.eigval:8.3f} (true {true_eig:8.3f}) | "
              f"resid {res.residuals[-1]:.2e}")

    for s_tol in (0, 1):
        cy = results[("cyclic", s_tol)].total_modeled_latency
        mn = results[("optimized", s_tol)].total_modeled_latency
        if cy > 0:
            print(f"S={s_tol}: optimized (MAN) vs cyclic modeled latency: "
                  f"{100 * (1 - mn / cy):+.1f}%  "
                  f"(~0% expected: at N=4 both placements achieve the LP "
                  f"bound; the gap grows with N — paper Table I)")
    print(f"all {steps_total} steps bit-exact (y == X @ w); "
          f"executor compiled once per runner")


if __name__ == "__main__":
    main()
