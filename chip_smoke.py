#!/usr/bin/env python3
"""Run the elastic USEC engine end to end on TPU and check it exactly.

    python chip_smoke.py             # one chip (N=1 worker)
    python chip_smoke.py --chips 4   # four chips: the multi-worker path only

Every phase enters through the front doors a user calls —
``repro.api.ElasticEngine(backend="device")`` and
``repro.serve.ElasticServer`` — on ``make_exact_matrix(24576, seed)``: a
2.4 GB float32 operand with small integer entries, so every product is
exact and each result is compared bit for bit with a float64 host
reference.

One chip: power iteration stepwise (8 steps, streamed block list) and
fused (16 steps in windows of 8, segmented kernel); an exact matmat with
128 columns; an ``ElasticServer`` answering 16 requests. Four chips: N=4
workers (cyclic J=3, S=1) under scripted churn, barrier and first
arrival, each stepwise and fused.

Each phase prints one ``PHASE {...}`` line: host-clock seconds on the
printed device (first step with its compile, then the steady median —
not a benchmark), the jit cache size (must be 1), whether the exact checks
passed, whether the step program holds a Pallas kernel
(``tpu_custom_call``), and the device's peak bytes in use so far. The last
line is ``{"ok": true, "device": {...}}``, printed only when every phase
passed. Without an accelerator, or outside the repo, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DIM = 24576
BLOCK_ROWS = 16
MATMAT_COLS = 128
MATMAT_GRID = 16          # W on the 2^-4 grid, as in the README quickstart


def _device_line():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _times(reports):
    """(first step s, steady median s) from the runner's per-step walls.
    A fused window reports its wall split evenly over its active steps,
    so a fused run's first steps carry the window's compile."""
    walls = [r.wall_s for r in reports]
    steady = statistics.median(walls[1:]) if len(walls) > 1 else None
    return walls[0], steady


def _kernel_in(runner) -> bool:
    return "tpu_custom_call" in runner.lowered_step_text()


def power_iteration(x, seed, kernel_mode=None):
    """Stepwise (streamed block list) then fused (segmented kernel) power
    iteration on one worker; the fused run's first 8 steps must equal the
    stepwise run bit for bit."""
    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration
    from repro.api import Policy

    policy = Policy(placement="cyclic", replication=1, stragglers=0)
    out = {}
    eng = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy,
        EngineConfig(block_rows=BLOCK_ROWS, verify="exact",
                     matmul_mode=kernel_mode),
        backend="device", n_machines=1)
    res = eng.run(x, n_steps=8)     # verify="exact" raises on any mismatch
    first, steady = _times(res.reports)
    stepwise = res.result.residuals
    out["stepwise"] = {
        "steps": len(res.reports), "first_step_s": first,
        "steady_step_s": steady, "jit_cache": res.executor_cache_size,
        "exact": True, "kernel": _kernel_in(eng.runner),
        "peak_bytes": _peak_bytes(),
    }
    del eng, res
    gc.collect()

    seg = "auto" if kernel_mode is None else kernel_mode
    eng = ElasticEngine(
        MatVecPowerIteration(seed=seed), policy,
        EngineConfig(block_rows=BLOCK_ROWS, verify="exact", fuse_steps=8,
                     segmented=seg, matmul_mode=kernel_mode),
        backend="device", n_machines=1)
    res = eng.run(x, n_steps=16)
    first, steady = _times(res.reports)
    out["fused"] = {
        "steps": len(res.reports), "first_step_s": first,
        "steady_step_s": steady, "jit_cache": res.executor_cache_size,
        "exact": True, "kernel": _kernel_in(eng.runner),
        "fused_equals_stepwise": res.result.residuals[:8] == stepwise,
        "eigval": res.result.eigval, "peak_bytes": _peak_bytes(),
    }
    return out


def matmat_operand(x, seed, cols=MATMAT_COLS):
    """A grid-valued W whose products with ``x`` are exact: every partial
    sum stays below 2^24 grid units (checked, not assumed)."""
    rng = np.random.default_rng(seed + 1)
    w = (np.round(rng.normal(size=(x.shape[1], cols)) * MATMAT_GRID)
         / MATMAT_GRID).astype(np.float32)
    row_abs = np.abs(x).sum(axis=1, dtype=np.float64).max()
    units = row_abs * float(np.abs(w).max()) * MATMAT_GRID
    if units >= 2 ** 24:
        raise AssertionError(
            f"partial sums reach {units:.3g} grid units >= 2^24: not exact")
    return w, units


def matmat(x, seed, kernel_mode=None):
    from repro.api import ElasticEngine, EngineConfig, MatMat, Policy

    w, units = matmat_operand(x, seed)
    eng = ElasticEngine(
        MatMat(w), Policy(placement="cyclic", replication=1, stragglers=0),
        EngineConfig(block_rows=BLOCK_ROWS, verify="exact",
                     matmul_mode=kernel_mode),
        backend="device", n_machines=1)
    res = eng.run(x, n_steps=4)
    first, steady = _times(res.reports)
    return {
        "cols": w.shape[1], "steps": len(res.reports),
        "max_grid_units": units, "first_step_s": first,
        "steady_step_s": steady, "jit_cache": res.executor_cache_size,
        "exact": True, "kernel": _kernel_in(eng.runner),
        "peak_bytes": _peak_bytes(),
    }


def serving(x, x64, seed, kernel_mode=None, n_requests=16):
    """An ElasticServer answering matvec, matmat and one mapreduce query,
    each response checked against host float64."""
    import jax.numpy as jnp

    from repro.api import EngineConfig, MapReduceRows, Policy
    from repro.serve import ElasticServer, ServeConfig

    mapreduce = MapReduceRows(
        row_fn=lambda xb, w2: jnp.sum(
            xb.astype(jnp.float32) ** 2, axis=1, keepdims=True),
        reduce_fn=lambda mapped: float(mapped.astype(np.float64).sum()),
        out_cols=1,
        ref_row_fn=lambda xx, _w: np.sum(xx ** 2, axis=1, keepdims=True),
        name="rows_sumsq",
    )
    server = ElasticServer(
        x, Policy(placement="cyclic", replication=1, stragglers=0),
        EngineConfig(block_rows=BLOCK_ROWS, matmul_mode=kernel_mode),
        ServeConfig(batch_cols=8, max_queue=64),
        mapreduce=mapreduce, n_machines=1)
    rng = np.random.default_rng(seed + 2)
    q = x.shape[1]
    want = {}
    for i in range(n_requests):
        if i == n_requests // 2:
            kind, op = "mapreduce", None
            want_i = float(np.sum(x64 ** 2))
        elif i % 4 == 3:
            op = rng.integers(-3, 4, size=(q, int(rng.integers(2, 5))))
            kind, op = "matmat", op.astype(np.float32)
            want_i = x64 @ op.astype(np.float64)
        else:
            kind = "matvec"
            op = rng.integers(-3, 4, size=q).astype(np.float32)
            want_i = x64 @ op.astype(np.float64)
        ticket = server.submit(kind, op)
        assert ticket.admitted, f"request {i} rejected"
        want[ticket.rid] = (kind, want_i)
    t0 = time.perf_counter()
    responses = server.drain()
    wall = time.perf_counter() - t0
    kinds = {}
    for r in responses:
        kind, ref = want.pop(r.rid)
        assert r.status == "ok", (r.rid, r.status)
        got = r.result
        ok = (got == ref) if kind == "mapreduce" else np.array_equal(
            np.asarray(got, np.float64), ref)
        assert ok, f"request {r.rid} ({kind}) differs from float64"
        kinds[kind] = kinds.get(kind, 0) + 1
    assert not want, f"unanswered requests {sorted(want)}"
    snap = server.metrics_snapshot()
    lanes = server.lanes
    return {
        "requests": len(responses), "by_kind": kinds,
        "drain_s_with_compiles": wall,
        "jit_cache": {k: v["jit_cache_size"]
                      for k, v in snap["lanes"].items()},
        "exact": True, "kernel": _kernel_in(lanes["linear"].runner),
        "peak_bytes": _peak_bytes(),
    }


def four_workers(x, seed, kernel_mode=None, steps=6):
    """N=4 workers, one per chip, cyclic J=3, S=1: worker 2 is preempted
    at step 1 and returns at step 3 (J = 2 + S keeps 1 + S live holders of
    every tile while one worker is away). Barrier and first arrival, each
    stepwise and fused (K=4); every step exact, every run's iterates
    bitwise-equal, each chip holding only its own worker's tiles."""
    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration
    from repro.api import Policy
    from repro.core.elastic import scripted_trace
    from repro.runtime import SyntheticSpeedClock

    speeds = (1000.0, 1300.0, 1700.0, 2200.0)
    runs, eigvecs = {}, {}
    for arrival in ("barrier", "first"):
        for fuse in (1, 4):
            seg = None
            if fuse > 1:
                seg = "auto" if kernel_mode is None else kernel_mode
            eng = ElasticEngine(
                MatVecPowerIteration(seed=seed),
                Policy(placement="cyclic", replication=3, stragglers=1),
                EngineConfig(block_rows=BLOCK_ROWS, verify="exact",
                             fuse_steps=fuse, segmented=seg,
                             arrival=arrival, matmul_mode=kernel_mode,
                             initial_speeds=speeds),
                backend="device", n_machines=4,
                clock=SyntheticSpeedClock(speeds, jitter_sigma=0.1,
                                          seed=seed))
            res = eng.run(x, n_steps=steps, events=scripted_trace(
                4, {1: ((2,), ()), 3: ((), (2,))}))
            runner = eng.runner
            staged = runner.staged_device
            mesh_devices = list(runner.mesh.devices.flat)
            own = all(
                s.index[0] == slice(n, n + 1) and s.data.shape[0] == 1
                and s.device == mesh_devices[n]
                for n, s in enumerate(sorted(
                    staged.addressable_shards,
                    key=lambda s: s.index[0].start)))
            text = runner.lowered_step_text()
            first, steady = _times(res.reports)
            runs[f"{arrival}_k{fuse}"] = {
                "steps": len(res.reports), "churn": res.churn_events,
                "first_step_s": first, "steady_step_s": steady,
                "jit_cache": res.executor_cache_size, "exact": True,
                "own_shard_only": own,
                "staged_bytes_per_chip": staged.addressable_shards[0]
                .data.nbytes,
                "all_reduce": "all_reduce" in text,
                "kernel": "tpu_custom_call" in text,
                "peak_bytes": _peak_bytes(),
            }
            eigvecs[f"{arrival}_k{fuse}"] = res.result.eigvec
            del eng, res, runner, staged
            gc.collect()
    ref = eigvecs["barrier_k1"]
    for name, v in eigvecs.items():
        runs[name]["equals_barrier_k1"] = bool(np.array_equal(v, ref))
    return runs


def _checks(name, rec, expect_kernel):
    """The conditions a phase record must meet."""
    bad = []
    recs = rec.values() if name in ("power_iteration", "four_chips") \
        else [rec]
    for r in recs:
        caches = r["jit_cache"]
        caches = caches.values() if isinstance(caches, dict) else [caches]
        if any(c != 1 for c in caches):
            bad.append(f"jit cache {r['jit_cache']} != 1")
        if not r["exact"]:
            bad.append("not exact")
        if expect_kernel and not r["kernel"]:
            bad.append("no tpu_custom_call in the step program")
        for key in ("fused_equals_stepwise", "own_shard_only",
                    "equals_barrier_k1"):
            if r.get(key) is False:
                bad.append(f"{key} is False")
    if name == "four_chips":
        for run, r in rec.items():
            if run.startswith("barrier") and not r["all_reduce"]:
                bad.append(f"{run}: no all-reduce in the barrier step")
    return bad


def run_phases(chips, dim, seed, kernel_mode=None, expect_kernel=True):
    """Run the phases for ``chips`` and print one PHASE line each;
    returns True when every phase passed."""
    from repro.runtime.elastic_runner import make_exact_matrix

    t0 = time.perf_counter()
    x = make_exact_matrix(dim, seed)
    print(f"# operand {x.shape} float32, {x.nbytes / 2**30:.2f} GiB, "
          f"made in {time.perf_counter() - t0:.1f}s", flush=True)
    if chips == 4:
        phases = [("four_chips",
                   lambda: four_workers(x, seed, kernel_mode))]
    else:
        x64 = None

        def serve():
            nonlocal x64
            x64 = x.astype(np.float64)
            return serving(x, x64, seed, kernel_mode)

        phases = [
            ("power_iteration",
             lambda: power_iteration(x, seed, kernel_mode)),
            ("matmat", lambda: matmat(x, seed, kernel_mode)),
            ("serving", serve),
        ]
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            rec = fn()
            problems = _checks(name, rec, expect_kernel)
        except Exception:  # a failed phase is reported, then fails the run
            traceback.print_exc()
            rec, problems = None, ["raised"]
        gc.collect()
        ok = ok and not problems
        print("PHASE " + json.dumps({
            "phase": name, "ok": not problems, "problems": problems,
            "phase_s": time.perf_counter() - t0,
            "timing": "host clock on the printed device, not a benchmark",
            "result": rec}, default=str), flush=True)
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-worker path (one worker "
                         "per chip)")
    ap.add_argument("--dim", type=int, default=DIM)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = _device_line()
    print(f"# device {dev['platform']} {dev['kind']} x{dev['count']}, "
          f"jax {jax.__version__}", flush=True)
    if dev["platform"] != "tpu":
        print("# no TPU found: this smoke test runs only on the chip",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"# --chips {args.chips} needs {args.chips} chips; found "
              f"{dev['count']}", file=sys.stderr)
        return 2

    from repro.launch.hostdev import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    if not run_phases(args.chips, args.dim, args.seed):
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
