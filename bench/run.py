#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. The run makes
its inputs from the seed, sets up (generate, stage, compile, warm up),
measures for ``--seconds``, checks what the timed path produced against a
float64 reference, and prints one JSON object as the last line of standard
output: with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics read from a profiler trace of the window's start.
The numbers compared, each with its limit, are the last lines of standard
error and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. JAX's persistent compilation cache lives in
``.jax_cache/`` at the checkout's root unless ``JAX_COMPILATION_CACHE_DIR``
names another directory.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"# the program is not here: {src}/repro is missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, BENCH)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness

    try:
        result, checks = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except harness.NoChip as e:
        print(f"# {e}", file=sys.stderr)
        return 2
    for name, value, limit in checks:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
