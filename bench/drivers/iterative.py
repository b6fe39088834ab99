"""Iterative traffic: power iteration through ``ElasticEngine.run``.

Set-up makes the operand from the seed, stages it, compiles the step
program with the first step and warms up. The window then runs the same
power iteration onward in chunks of ``chunk_steps`` steps, one
``engine.run`` call each, until ``seconds`` have passed; ``step_ms`` is the
window's wall time over the steps it completed.

The check follows the program's own iterate: for ``check_steps`` steps of
the window drawn from the seed it recomputes ``X w_t`` in float64 from the
operand the benchmark made and the configured grid snap of that product,
and compares them with the step's output and the next iterate the program
carried (both exact, limit 0).
"""

from __future__ import annotations

import time

import numpy as np

import data
import reference


class _StepLog:
    """Keeps a uniform sample, drawn from the seed, of the window's steps
    as (operand, result, next iterate carried) triples, by wrapping the
    instance's ``consume`` (the class method is untouched, so fused
    execution stays eligible). Reservoir sampling holds ``size`` triples
    however long the window runs."""

    def __init__(self, workload, size, seed):
        self.size = size
        self.rng = np.random.default_rng([seed, 1])
        self.clear()
        inner = workload.consume

        def consume(result, operand):
            self._close(operand)
            self.pending = (operand, result)
            return inner(result, operand)

        workload.consume = consume

    def clear(self):
        self.sample, self.seen, self.pending = [], 0, None

    def _close(self, carried):
        """The pending step's next iterate is ``carried``: the operand of
        the next step in the same call, or the call's final iterate."""
        if self.pending is None:
            return
        triple = (*self.pending, carried)
        self.pending = None
        if len(self.sample) < self.size:
            self.sample.append(triple)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.sample[j] = triple
        self.seen += 1

    def end_call(self, final_iterate):
        self._close(final_iterate)


def setup(ctx):
    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration
    from repro.api import Policy

    cfg, tr = ctx.config, ctx.traffic
    n = int(cfg["dim"])
    st = {"ctx": ctx, "bits": int(cfg["quantize_bits"]), "reports": [],
          "rows": [], "traced_steps": 0, "traced_rows": 0}
    with ctx.phase("generate"):
        st["x8"] = data.make_operand_int8(n, ctx.seed)
    with ctx.phase("widen"):
        x = data.widen(st["x8"])
    wl = MatVecPowerIteration(quantize_bits=st["bits"], seed=0)
    st["log"] = _StepLog(wl, int(tr["check_steps"]), ctx.seed)
    engine = ElasticEngine(
        wl,
        Policy(placement=cfg["placement"],
               replication=int(cfg["replication"]),
               stragglers=int(cfg["stragglers"])),
        EngineConfig(arrival=cfg["arrival"], **tr.get("engine", {})),
        backend="device", n_machines=int(cfg["n_machines"]))
    with ctx.phase("stage"):
        engine.prepare(x)
    del x
    st["engine"] = engine
    runner = engine.runner

    def on_steps(reports):
        # Rows each worker computes in this dispatch (every held copy).
        rows = runner.current_plan.seg_len.sum(axis=1).astype(np.int64)
        for _ in reports:
            st["rows"].append(rows)

    runner.add_completion_callback(on_steps)
    rng = np.random.default_rng(ctx.seed)
    st["w"] = rng.normal(size=n).astype(np.float32)
    with ctx.phase("compile"):
        _chunk(st, 1)
    with ctx.phase("warmup"):
        _chunk(st, int(tr["warmup_steps"]))
    st["log"].clear()
    st["reports"].clear()
    st["rows"].clear()
    return st


def _chunk(st, steps):
    """One ``engine.run`` call of ``steps`` steps, continuing the iterate."""
    res = st["engine"].run(n_steps=steps, operand=st["w"])
    st["w"] = res.result.eigvec
    st["log"].end_call(st["w"])
    st["reports"].extend(res.reports)
    return res


def window(st, seconds, tracer):
    steps = int(st["ctx"].traffic["chunk_steps"])
    st["chunk_s"] = []
    t0 = t = time.perf_counter()
    while t - t0 < seconds and not tracer.over():
        tracing = tracer.tick()
        n0 = len(st["rows"])
        with tracer.span("bench.engine_run"):
            _chunk(st, steps)
        t, t_prev = time.perf_counter(), t
        st["chunk_s"].append(t - t_prev)
        if tracing:
            st["traced_steps"] += steps
            st["traced_rows"] += int(sum(r.sum() for r in st["rows"][n0:]))
    st["window_s"] = time.perf_counter() - t0
    tracer.stop()


def report(st):
    reps = st["reports"]
    steps = len(reps)
    win = st["window_s"]
    walls = sum(r.wall_s for r in reps)
    n = st["x8"].shape[1]
    rec = {
        "steps": steps,
        "window_s": win,
        "host_s": win - walls,
        "replans": sum(bool(r.replanned) for r in reps),
        "straggled": sum(len(r.straggled) for r in reps),
        "chunk_s": st["chunk_s"],
        "traced_steps": st["traced_steps"],
        # Least bytes the traced steps must read: every row a worker
        # computes, once, at the configured float32 width.
        "traced_hbm_bytes": st["traced_rows"] * n * 4,
    }
    return {"e2e": {"step_ms": 1e3 * win / steps}, "rec": rec,
            "attempted": steps, "failed": 0}


def release(st):
    st.pop("engine", None)


def check(st):
    """Widest gaps, in grid units, over the sampled steps, and the steps
    due for the check that the sample lacks."""
    bits = st["bits"]
    sample = st["log"].sample
    due = min(int(st["ctx"].traffic["check_steps"]), len(st["reports"]))
    y_gap = it_gap = 0.0
    if sample:
        ws = np.stack([np.asarray(w, np.float64) for w, _, _ in sample], 1)
        ref = reference.products(st["x8"], ws)
        for j, (_, y, w_next) in enumerate(sample):
            y_gap = max(y_gap, reference.grid_gap(y, ref[:, j], bits))
            it_gap = max(it_gap, reference.grid_gap(
                w_next, reference.snap(ref[:, j], bits), bits))
    return [("product_gap_units", y_gap, 0.0),
            ("iterate_gap_units", it_gap, 0.0),
            ("unchecked_steps", float(max(0, due - len(sample))), 0.0)]
