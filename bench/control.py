#!/usr/bin/env python3
"""The control: the reference in the program's place, one precision lower.

    python3 bench/control.py --cell powit-n1-steady --seeds 1,2,3

The configurations state float32; the control computes the same work in
bfloat16 (operands, products and the iterate's normalize), on the device,
at the cell's own size, and hands its outputs to the cell's own check: 16
steps of power iteration from the seed's start vector, each step
``y = X w`` and the next iterate in bfloat16, of which the check samples
``check_steps``. It must come out not correct: each line prints the
numbers compared with their limits, ``correct`` and the process's peak
host memory. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import resource
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def bf16(v):
    """Round float32 values to bfloat16 and keep them there: an explicit
    rounding, which XLA may not fold away as it may a pair of converts."""
    import jax

    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def bf16_product(xb, w):
    """``X @ w`` with bfloat16 operands and a bfloat16 result."""
    import jax.numpy as jnp

    return bf16(jnp.dot(xb, bf16(w.astype(jnp.float32)).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32))


def _bf16_iterate(y_b, bits):
    import jax.numpy as jnp

    norm = bf16(jnp.sqrt(bf16(jnp.sum(bf16(y_b * y_b)))))
    u = bf16(y_b / norm)
    return jnp.round(u * (1 << bits)) / (1 << bits)


@functools.lru_cache(maxsize=None)
def _step(bits):
    """One bfloat16 power-iteration step, compiled once per process."""
    import jax

    def step(xb, w):
        y_b = bf16_product(xb, w)
        return y_b, _bf16_iterate(y_b, bits)

    return jax.jit(step)


def powit_state(ctx, x8, steps):
    """An iterative driver's checkable state, filled by the bf16 control."""
    import jax.numpy as jnp

    import reference

    bits = int(ctx.config["quantize_bits"])
    xb = jnp.asarray(x8).astype(jnp.bfloat16)
    rng = np.random.default_rng(ctx.seed)
    w = reference.snap(rng.normal(size=x8.shape[0]), bits)
    sample = []
    for _ in range(steps):
        y, w_next = _step(bits)(xb, jnp.asarray(w))
        w_next = np.asarray(w_next)
        sample.append((w, np.asarray(y), w_next))
        w = w_next
    del xb
    pick = np.random.default_rng([ctx.seed, 1]).choice(
        steps, size=min(steps, int(ctx.traffic["check_steps"])),
        replace=False)
    return {"ctx": ctx, "bits": bits, "x8": x8, "reports": [None] * steps,
            "log": type("Log", (), {"sample": [sample[i] for i in pick]})()}


def readings(cell_name, seed, adjust=None):
    """The control's checks for one cell and seed: [(name, value, limit)]."""
    import data
    import harness

    cell = harness.load_cell(cell_name)
    if adjust is not None:
        adjust(cell)
    ctx = harness.Ctx(name=cell_name, seed=seed, seconds=0.0,
                      chips=cell["chips"], config=cell["config"],
                      traffic=cell["traffic"])
    driver = harness.load_module("drivers", cell["traffic"]["driver"])
    x8 = data.make_operand_int8(int(cell["config"]["dim"]), seed)
    return driver.check(powit_state(ctx, x8, 16))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness

    try:
        harness.chips_in_use(1)
    except harness.NoChip as e:
        print(f"# {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        checks = readings(args.cell, seed)
        correct = all(v <= lim for _, v, lim in checks)
        print("CONTROL " + json.dumps({
            "cell": args.cell, "seed": seed, "correct": correct,
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in checks},
            "seconds": time.perf_counter() - t,
            "host_peak_rss_bytes": 1024 * resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
