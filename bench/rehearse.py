#!/usr/bin/env python3
"""Rehearse benchmark cells on the CPU at a tiny size; prints no result line.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...] [--dim 256]

Each cell runs end to end through the harness (set-up, window, per-layer
record, reference check) with the look for a chip skipped, its operand cut
to ``--dim``, short chunks and loops, and the Pallas kernels in interpret
mode. A cell of more than one chip runs on forced host devices. Timings
printed here are CPU numbers and stand for nothing on the chip.
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


def shrink(cell, dim: int) -> None:
    """Cut a loaded cell to rehearsal size, in place: the operand to
    ``dim``, the kernels to interpret mode, and the traffic's own
    ``rehearse`` overrides (shorter calls and loops)."""
    cell["config"]["dim"] = dim
    tr = cell["traffic"]
    tr.update(tr.get("rehearse", {}))
    tr["engine"] = {"matmul_mode": "interpret"}


def rehearse(cell_name: str, seed: int, seconds: float, dim: int,
             trace: bool = False):
    import harness

    return harness.run_cell(
        cell_name, seed, seconds, trace, t_start=time.perf_counter(),
        require_chip=False, peaks={"hbm_bytes_per_s": 1e9},
        adjust=lambda c: shrink(c, dim))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--seed", type=int, default=2**33 + 7)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness

    cells = args.cells or [w["name"] for w in harness.spec()["workloads"]]
    ok = True
    for name in cells:
        result, checks = rehearse(name, args.seed, args.seconds, args.dim,
                                  bool(args.trace))
        ok = ok and result["correct"]
        print(f"rehearsal {name}: correct={result['correct']} "
              f"metrics={json.dumps(result['metrics'])} "
              f"checks={json.dumps(result['checks'])}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
