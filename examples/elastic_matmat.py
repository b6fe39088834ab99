"""One front door, two backends, three workloads: `repro.api.ElasticEngine`.

The same ``EngineConfig`` + ``Policy`` + availability trace drives

1. ``backend="simulate"`` — the batched analytical path: completion-time
   distributions per churn step, no devices touched;
2. ``backend="device"`` — live execution of ``Y = X @ W`` (the
   matrix-matrix workhorse of the CEC literature) on 4 forced host devices
   through the shard_map executor, bit-exact against a float64 host
   reference at every step, under churn AND one forced straggler per step;
3. a ``MapReduceRows`` workload (per-row squared norm, global sum) on the
   same elastic machinery — the "beyond linear computations" direction.

The jitted step never recompiles across membership changes (asserted).

Run:  PYTHONPATH=src python examples/elastic_matmat.py [--steps 6]
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
    MapReduceRows,
    MatMat,
    Policy,
)
from repro.core.elastic import scripted_trace  # noqa: E402
from repro.runtime import make_exact_matrix  # noqa: E402

DIM = 768      # rows of X, divisible by the placement's tile count
COLS = 8       # columns of W

# Single-machine-down churn within the first three steps, so even a
# --steps 3 smoke exercises preemption and arrival.
SCRIPT = {
    0: ((3,), ()),
    1: ((1,), (3,)),
    2: ((), (1,)),
    4: ((2,), ()),
    5: ((), (2,)),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    require_devices(N_WORKERS)
    enable_compile_cache()

    x = make_exact_matrix(DIM, args.seed)
    rng = np.random.default_rng(args.seed + 1)
    # Grid-valued W: every partial of X @ W is exactly representable, so the
    # device backend's verify="exact" holds bitwise at every step.
    w = (np.round(rng.normal(size=(DIM, COLS)) * 16) / 16).astype(np.float32)

    policy = Policy(placement="cyclic", replication=3, stragglers=1)
    cfg = EngineConfig(block_rows=16, verify="exact", n_draws=256,
                       seed=args.seed, jitter_sigma=0.2,
                       initial_speeds=(1000.0, 1300.0, 1700.0, 2200.0))
    print(f"== ElasticEngine: {N_WORKERS} workers, X ({DIM}x{DIM}) @ "
          f"W ({DIM}x{COLS}), {args.steps} steps, scripted churn ==")

    # ---- backend="simulate": the analytical sweep over the same trace ----
    sim = ElasticEngine(MatMat(w), policy, cfg, backend="simulate",
                        n_machines=N_WORKERS)
    sres = sim.run(events=scripted_trace(N_WORKERS, SCRIPT),
                   n_steps=args.steps)
    mean = float(np.mean(sres.completion_times))
    print(f"simulate | {sres.n_steps} steps x {cfg.n_draws} draws | "
          f"plans {sres.plans_compiled} (hits {sres.cache_hits}) | "
          f"waste {sres.total_waste} rows | mean completion {mean:.3f} "
          f"(matvec-row units x {COLS} cols)")

    # ---- backend="device": the same config executed live ----------------
    dev = ElasticEngine(MatMat(w), policy, cfg, backend="device",
                        n_machines=N_WORKERS)
    one = np.random.default_rng(args.seed + 2)
    res = dev.run(
        x, n_steps=args.steps,
        events=scripted_trace(N_WORKERS, SCRIPT),
        straggler_sets=lambda i, avail: (
            (int(one.choice(avail)),) if len(avail) > 1 else ()),
    )
    y = res.result
    ref = x.astype(np.float64) @ w.astype(np.float64)
    assert np.array_equal(y, ref), "device result diverged from X @ W"
    assert res.executor_cache_size == 1, res.executor_cache_size
    wall = sum(r.wall_s for r in res.reports)
    print(f"device   | churn {res.churn_events} | "
          f"plans {res.plans_compiled} (hits {res.cache_hits}) | "
          f"waste {res.total_waste} rows | "
          f"{len(res.reports) / wall:5.1f} steps/s | "
          f"Y == X @ W bit-exact every step | jit entries "
          f"{res.executor_cache_size}")

    # ---- MapReduceRows on the same machinery -----------------------------
    import jax.numpy as jnp

    frob = MapReduceRows(
        row_fn=lambda xb, w2: jnp.sum(xb.astype(jnp.float32) ** 2, axis=1,
                                      keepdims=True),
        reduce_fn=lambda mapped: float(mapped.sum()),
        out_cols=1,
        ref_row_fn=lambda x64, _w: np.sum(x64 ** 2, axis=1, keepdims=True),
        name="frobenius",
    )
    mr = ElasticEngine(frob, policy, cfg, backend="device",
                       n_machines=N_WORKERS)
    res2 = mr.run(x, n_steps=min(args.steps, 3),
                  events=scripted_trace(N_WORKERS, SCRIPT))
    expect = float(np.sum(x.astype(np.float64) ** 2))
    assert res2.result == expect, (res2.result, expect)
    print(f"mapreduce| ||X||_F^2 = {res2.result:.0f} (exact) under the same "
          f"churn | jit entries {res2.executor_cache_size}")


if __name__ == "__main__":
    main()
