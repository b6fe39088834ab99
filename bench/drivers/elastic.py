"""Elastic traffic: power iteration through ``ElasticEngine.run`` under churn.

The closed loop of ``iterative`` (``chunk_steps``-step ``engine.run``
calls back to back, the iterate carried across), with the traffic's churn
fed through ``run(events=...)``: at step ``event_step`` of one call a
worker is preempted, and at the same step of the next call it returns, so
one worker is away for half the steps and the plan changes inside a call.
The worker that leaves is drawn from the seed each cycle
(:func:`schedule`). Events are announced at a step boundary; there is no
injected straggler set, the engine derives each step's realized
straggler itself.

Set-up makes the operand from the seed, stages it, compiles the step
program with one step at full membership, and warms up with one cycle per
worker, each worker away once, so every membership's plan is cached
before the window.

The check is ``iterative``'s (product and iterate, exact) over a sample
drawn from the seed that holds steps at full membership, steps with a
worker away and first steps after an event, ``check_steps // 3`` of each
kind where the window ran as many; a kind the window never ran shows in
``unchecked_steps``. For the
same steps it holds what the step ran to the placement guarantees,
against the plain reference of cyclic placement (:mod:`placement`) and
the schedule's own record of who was away. The rows each worker computed
are read from the block lists the step dispatched, and the row each
worker won from the first-arrival combine itself: the driver wraps the
runner's ``_winner_combine`` on the instance, keeps the include weights
and loaded workers each step's gather took, and after the window runs
that same gather on partials that carry their worker's index. The gaps:
rows computed by a worker that does not hold them or is away
(``held_rows_gap``), rows with fewer than S+1 available computing copies
(``coverage_gap``), rows whose combined copy came from a worker that is
away, masked, or did not compute the row (``winner_gap``); each limit
is 0.
"""

from __future__ import annotations

import collections
import itertools
import time

import numpy as np

import data
import harness
import placement

iterative = harness.load_module("drivers", "iterative")
KINDS = ("full", "away", "event")   # the sample's strata


def schedule(seed, n_machines, first=()):
    """Endless ``(preempted, arrived)`` pairs, one per call: a worker
    leaves in one call and returns in the next. The workers of ``first``
    leave in turn, then one drawn from the seed each cycle."""
    rng = np.random.default_rng([seed, 2])
    drawn = iter(lambda: int(rng.integers(n_machines)), None)
    for k in itertools.chain(first, drawn):
        yield (k,), ()
        yield (), (k,)


class _StepLog(iterative._StepLog):
    """``iterative``'s step log with one reservoir of ``size`` steps per
    stratum of :data:`KINDS`. The driver's completion callback queues each
    step's stratum and placement record in step order; the step's close
    takes them."""

    def clear(self):
        super().clear()
        self.tags = collections.deque()
        self.strata = {}
        self.placed = []

    def _close(self, carried):
        if self.pending is None:
            return
        kind, placed = self.tags.popleft()
        s = self.strata.setdefault(kind, {"seen": 0, "kept": []})
        item = ((*self.pending, carried), placed)
        self.pending = None
        if len(s["kept"]) < self.size:
            s["kept"].append(item)
        else:
            j = int(self.rng.integers(0, s["seen"] + 1))
            if j < self.size:
                s["kept"][j] = item
        s["seen"] += 1

    def end_call(self, final_iterate):
        super().end_call(final_iterate)
        kept = [item for s in self.strata.values() for item in s["kept"]]
        self.sample = [triple for triple, _ in kept]
        self.placed = [placed for _, placed in kept]


def setup(ctx):
    from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration
    from repro.api import Policy

    cfg, tr = ctx.config, ctx.traffic
    n, n_workers = int(cfg["dim"]), int(cfg["n_machines"])
    st = {"ctx": ctx, "bits": int(cfg["quantize_bits"]), "reports": [],
          "rows": [], "traced_steps": 0, "traced_rows": 0,
          "members": tuple(range(n_workers)),
          "churn": schedule(ctx.seed, n_workers, first=range(n_workers))}
    with ctx.phase("generate"):
        st["x8"] = data.make_operand_int8(n, ctx.seed)
    with ctx.phase("widen"):
        x = data.widen(st["x8"])
    wl = MatVecPowerIteration(quantize_bits=st["bits"], seed=0)
    if int(tr["check_steps"]) % len(KINDS):
        raise ValueError(f"check_steps {tr['check_steps']} does not split "
                         f"into the {len(KINDS)} kinds of step")
    st["log"] = _StepLog(wl, int(tr["check_steps"]) // len(KINDS), ctx.seed)
    engine = ElasticEngine(
        wl,
        Policy(placement=cfg["placement"],
               replication=int(cfg["replication"]),
               stragglers=int(cfg["stragglers"])),
        EngineConfig(arrival=cfg["arrival"], **tr.get("engine", {})),
        backend="device", n_machines=n_workers)
    with ctx.phase("stage"):
        engine.prepare(x)
    del x
    st["engine"] = engine
    runner = engine.runner
    st["combine"] = combine = runner._winner_combine

    def winner_combine(parts, loaded, entry, include):
        # What the step's gather takes; read back by the check.
        st["combined"] = (tuple(loaded), entry, include)
        return combine(parts, loaded, entry, include)

    runner._winner_combine = winner_combine

    def on_steps(reports):
        # Rows each worker computes in this dispatch (every held copy).
        rows = runner.current_plan.seg_len.sum(axis=1).astype(np.int64)
        for rep in reports:
            k = st["k"]
            st["k"] += 1
            avail = st["after"] if k >= st["at"] else st["before"]
            kind = ("event" if k == st["at"] else
                    "full" if len(avail) == n_workers else "away")
            st["rows"].append(rows)
            # None: the step took no first-arrival combine.
            combined = st.pop("combined", None)
            st["log"].tags.append(
                (kind, (combined, tuple(rep.straggled), avail)))
            if kind == "event":
                st["event_replan_s"].append(rep.replan_s)
            # Absent on a program without the first-arrival combine clock.
            st["combine_s"].append(getattr(rep, "combine_s", None))

    runner.add_completion_callback(on_steps)
    _clear(st)
    rng = np.random.default_rng(ctx.seed)
    st["w"] = rng.normal(size=n).astype(np.float32)
    warm = int(tr["warmup_steps"])
    with ctx.phase("compile"):
        _chunk(st, 1, None)
    with ctx.phase("warmup"):
        for _ in range(2 * n_workers):
            _chunk(st, warm, warm // 2)
    st["probes0"] = runner.probe_solves
    _clear(st)
    return st


def _clear(st):
    st["log"].clear()
    st["reports"].clear()
    st["rows"].clear()
    st.update(event_replan_s=[], combine_s=[])


def _chunk(st, steps, at):
    """One ``engine.run`` call of ``steps`` steps, continuing the iterate,
    with the schedule's next event at step ``at`` (None: no event)."""
    from repro.core.elastic import ElasticEvent

    before = after = st["members"]
    events = None
    if at is not None:
        gone, back = next(st["churn"])
        after = tuple(sorted(set(before) - set(gone) | set(back)))
        events = [None] * steps
        events[at] = ElasticEvent(step=at, preempted=gone, arrived=back,
                                  available=after)
    st.update(k=0, at=steps if at is None else at, before=before,
              after=after)
    res = st["engine"].run(n_steps=steps, operand=st["w"], events=events)
    st["members"] = after
    st["w"] = res.result.eigvec
    st["log"].end_call(st["w"])
    st["reports"].extend(res.reports)
    return res


def window(st, seconds, tracer):
    tr = st["ctx"].traffic
    steps, at = int(tr["chunk_steps"]), int(tr["event_step"])
    st["chunk_s"] = []
    t0 = t = time.perf_counter()
    while t - t0 < seconds and not tracer.over():
        tracing = tracer.tick()
        n0 = len(st["rows"])
        with tracer.span("bench.engine_run"):
            _chunk(st, steps, at)
        t, t_prev = time.perf_counter(), t
        st["chunk_s"].append(t - t_prev)
        if tracing:
            st["traced_steps"] += steps
            st["traced_rows"] += int(sum(r.sum() for r in st["rows"][n0:]))
    st["window_s"] = time.perf_counter() - t0
    tracer.stop()


def report(st):
    out = iterative.report(st)
    reps = st["reports"]
    runner = st["engine"].runner
    rec = out["rec"]
    rec.update(
        events=len(st["event_replan_s"]),
        event_replan_s=st["event_replan_s"],
        away_steps=sum(len(r.available) < runner.placement.n_machines
                       for r in reps),
        plan_misses=sum(r.replanned and not r.plan_cache_hit for r in reps),
        probe_solves=runner.probe_solves - st["probes0"],
        # What the clock feeds the speed estimator: the last step's
        # per-worker durations, and the estimates they left.
        measured={int(k): float(v) for k, v in reps[-1].measured.items()},
        speeds_est=[float(s) for s in runner.planning_master.speeds],
    )
    if st["combine_s"] and None not in st["combine_s"]:
        rec["combine_s"] = sum(st["combine_s"])
    return out


release = iterative.release


def _copies(combine, combined, rows):
    """Per worker, (rows,) masks of the rows it computed (the block lists
    of the loaded workers) and of the rows whose copy the combine took
    from it: ``combine``, the step's own gather, run again with the
    step's include weights on partials that hold their worker's index."""
    loaded, entry, include = combined
    bp = entry.block
    computed = np.zeros((bp.n_blocks.shape[0], rows), bool)
    for n in loaded:
        starts = bp.blk_goff[n, :bp.n_blocks[n]].astype(np.int64)
        span = starts[:, None] + np.arange(bp.block_rows)
        computed[n, span.ravel()] = True
    tagged = [np.full(rows, n, np.float32) for n in loaded]
    source = combine(tagged, list(loaded), entry, include)
    won = source[None, :] == np.arange(len(computed))[:, None]
    return computed, won


def check(st):
    """``iterative``'s gaps over the stratified sample, and the placement
    gaps, widest over the sampled steps, in rows. Each kind of step owes
    ``check_steps // 3`` steps, or as many as the window ran of it (a
    traced window of three calls holds three event steps), and at least
    one: a kind the window never ran reads in ``unchecked_steps``. A
    state without strata and placement records (the control's) is
    checked as ``iterative`` checks it, with placement gaps of 0."""
    cfg, log = st["ctx"].config, st["log"]
    strata = getattr(log, "strata", None)
    if strata is not None:
        due = sum(min(log.size, strata[k]["seen"]) if k in strata else 1
                  for k in KINDS)
        st = dict(st, reports=[None] * due)
    checks = iterative.check(st)
    n_workers, rows = int(cfg["n_machines"]), int(cfg["dim"])
    worst = np.zeros(3)
    for combined, masked, avail in getattr(log, "placed", []):
        if combined is None:
            # A step that bypassed the configured combine: nothing it
            # ran can be held to the guarantees.
            worst[:] = rows
            continue
        computed, won = _copies(st["combine"], combined, rows)
        worst = np.maximum(worst, placement.gaps(
            computed, won, avail, masked, n_workers,
            int(cfg["replication"]), int(cfg["stragglers"])))
    names = ("held_rows_gap", "coverage_gap", "winner_gap")
    return checks + [(nm, float(v), 0.0) for nm, v in zip(names, worst)]
