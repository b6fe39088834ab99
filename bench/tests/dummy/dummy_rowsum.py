"""A test driver: row sums of the seeded operand, checked exactly."""

import time

import numpy as np

import data


def setup(ctx):
    with ctx.phase("generate"):
        x8 = data.make_operand_int8(int(ctx.config["dim"]), ctx.seed)
    return {"ctx": ctx, "x8": x8}


def window(st, seconds, tracer):
    t0 = time.perf_counter()
    st["sums"] = [st["x8"].sum(axis=1, dtype=np.int64)
                  for _ in range(int(st["ctx"].traffic["sums_per_window"]))]
    st["window_s"] = max(time.perf_counter() - t0, 1e-9)


def report(st):
    n = len(st["sums"])
    return {"e2e": {"sums_per_s": n / st["window_s"]},
            "rec": {"sums": n}, "attempted": n, "failed": 0}


def release(st):
    pass


def check(st):
    want = st["x8"].astype(np.int64).sum(axis=1)
    gap = max(float(np.abs(s - want).max()) for s in st["sums"])
    return [("row_sum_gap", gap, 0.0)]
