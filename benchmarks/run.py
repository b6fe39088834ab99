"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per line. Usage:

  PYTHONPATH=src python -m benchmarks.run [--full]

``--full`` uses the paper's exact sizes (5000 Monte-Carlo draws, 6000-dim
power iteration); the default is a fast pass with identical semantics.

The device benches run as child processes. This parent never imports jax
(a parent holding the accelerator would starve its children), and any
failed child makes the whole run exit non-zero.
"""

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="structural CI tripwire: 3 tiny engine steps, "
                         "assert jit_cache_size == 1 and cache-hit replan "
                         "< 10ms; fails loudly on any exception")
    args = ap.parse_args(argv)

    if args.smoke:
        for script in ("bench_engine.py", "bench_serve.py", "bench_faults.py"):
            if not _run_devices_subprocess(script, smoke=True):
                raise SystemExit(1)
        print("# bench-smoke PASSED")
        return

    from benchmarks import (
        bench_paper_examples,
        bench_placements,
        bench_power_iteration,
        bench_straggler_tradeoff,
        bench_transition_waste,
    )

    t0 = time.time()
    print("# --- paper §III examples (Fig. 1 / Fig. 3) ---")
    bench_paper_examples.run()
    print("# --- paper Fig. 2 / Table I: placement Monte-Carlo ---")
    bench_placements.run(draws=5000 if args.full else 1000)
    print("# --- batched scenario engine: 1000-trace sweep vs scalar loop ---")
    bench_placements.run_batched_sweep(traces=1000)
    print("# --- paper Remark 1 + filling algorithm + solver scaling ---")
    bench_straggler_tradeoff.run()
    print("# --- paper §V Fig. 4: power iteration on heterogeneous workers ---")
    bench_power_iteration.run(dim=6000 if args.full else 600)
    print("# --- extension: transition-waste-averse re-planning (ref [2] metric) ---")
    bench_transition_waste.run()
    failed = []
    for title, script, steps in (
        ("live elastic runner: real execution under Markov churn",
         "bench_elastic_runner.py", 24 if args.full else 12),
        ("ElasticEngine: steps/sec per workload x backend",
         "bench_engine.py", 16 if args.full else 8),
        ("elastic serving: coalesced query traffic under churn",
         "bench_serve.py", 48 if args.full else 24),
        ("fault recovery: detect->replan->re-execute, goodput vs fault rate",
         "bench_faults.py", 8 if args.full else 4),
    ):
        print(f"# --- {title} ---")
        if not _run_devices_subprocess(script, steps=steps):
            failed.append(script)
    print(f"# total {time.time() - t0:.1f}s")
    if failed:
        print(f"# FAILED: {', '.join(failed)}")
        raise SystemExit(1)


def _run_devices_subprocess(script: str, steps: int = 0,
                            smoke: bool = False) -> bool:
    """Run one device bench in its own interpreter; True when it passed.

    jax pins the device count at first init (the CPU benches force 4 host
    devices), and an accelerator belongs to one process at a time — so
    this parent must not have touched jax."""
    import os
    import subprocess

    if "jax" in sys.modules:
        raise RuntimeError(
            f"benchmarks.run imported jax before starting {script}; the "
            f"child could not reach the accelerator")

    bench_dir = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    # Strip only a pre-existing device-count force (hostdev must set its
    # own); every other XLA flag the user exported is kept.
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    else:
        env.pop("XLA_FLAGS", None)
    argv = [sys.executable, os.path.join(bench_dir, script)]
    argv += ["--smoke"] if smoke else ["--steps", str(steps)]
    proc = subprocess.run(
        argv, capture_output=True, text=True, env=env,
        cwd=os.path.dirname(bench_dir),
    )
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stdout.write(f"# {script} FAILED (rc={proc.returncode})\n")
        sys.stdout.write(proc.stderr[-2000:] + "\n")
    return proc.returncode == 0


if __name__ == "__main__":
    main()
