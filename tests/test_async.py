"""First-arrival (async) execution: runner, fused windows, simulate models.

The ``arrival="first"`` consume rule end to end — bitwise reduction to the
barrier at S=0, bitwise equality with realized-straggler injection at S>0,
EWMA absorption of late arrivals, fused-window composition — plus the
order-statistic ("order") and bulk-synchronous ("barrier") completion
models that :func:`repro.runtime.simulate.simulate_batch` grew so the
policy lookahead can price S under the semantics the runner executes.

Host-side tests are pure NumPy; device tests run on forced host devices in
a subprocess (see ``conftest.run_with_devices``).
"""

import numpy as np
import pytest

from conftest import run_with_devices
from repro.core import USECScheduler, cyclic_placement, compile_plan, solve_assignment
from repro.runtime.simulate import simulate_batch


# ---------------------------------------------------------------------- #
# simulate_batch completion models (pure host)
# ---------------------------------------------------------------------- #
def _plan(n=4, s=1, speeds=None, rows_per_tile=96):
    p = cyclic_placement(n, n, 2 + s)
    sp = np.ones(n) if speeds is None else np.asarray(speeds, float)
    sol = solve_assignment(p, sp, available=tuple(range(n)), stragglers=s)
    return compile_plan(p, sol, rows_per_tile=rows_per_tile, stragglers=s,
                        speeds=sp)


def test_order_statistic_completion_drops_slowest_s():
    plan = _plan(n=4, s=1)
    speeds = np.array([[1.0, 2.0, 4.0, 0.25]])   # worker 3 is the laggard
    cov = simulate_batch(plan, speeds, completion="coverage")
    order = simulate_batch(plan, speeds, completion="order")
    barrier = simulate_batch(plan, speeds, completion="barrier")
    t = barrier.finish_times[0]
    active = plan.n_valid > 0
    # barrier = max over active finish times; order = (n_active - S)-th
    # order statistic (here: second-largest); coverage never exceeds order
    # (any N-S workers cover every segment).
    assert barrier.completion_times[0] == t[active].max()
    assert order.completion_times[0] == np.sort(t[active])[-2]
    assert cov.completion_times[0] <= order.completion_times[0]
    assert order.completion_times[0] <= barrier.completion_times[0]
    # the laggard dominates the barrier but not the first-arrival master
    assert barrier.completion_times[0] > order.completion_times[0]


def test_order_completion_with_drops_waits_for_surviving_arrivals():
    plan = _plan(n=4, s=1)
    speeds = np.ones((1, 4))
    t = simulate_batch(plan, speeds).finish_times[0]
    # one drop consumes the whole straggler budget: completion becomes the
    # max over the three survivors (all must arrive).
    order = simulate_batch(plan, speeds, dropped=[(2,)], completion="order")
    keep = [n for n in range(4) if n != 2 and plan.n_valid[n] > 0]
    assert order.feasible[0]
    assert order.completion_times[0] == max(t[n] for n in keep)
    # two drops exceed S: the wait never completes.
    res = simulate_batch(plan, speeds, dropped=[(1, 2)], completion="order",
                         on_infeasible="inf")
    assert not res.feasible[0] and np.isinf(res.completion_times[0])
    with pytest.raises(RuntimeError, match="exceeds"):
        simulate_batch(plan, speeds, dropped=[(1, 2)], completion="order")


def test_barrier_completion_never_finishes_under_any_drop():
    plan = _plan(n=4, s=1)
    speeds = np.ones((2, 4))
    res = simulate_batch(plan, speeds, dropped=[(), (3,)],
                         completion="barrier", on_infeasible="inf")
    assert res.feasible[0] and np.isfinite(res.completion_times[0])
    assert not res.feasible[1] and np.isinf(res.completion_times[1])


def test_simulate_batch_rejects_unknown_completion_model():
    plan = _plan()
    with pytest.raises(ValueError, match="completion"):
        simulate_batch(plan, np.ones((1, 4)), completion="psychic")


def test_lookahead_prices_candidates_under_order_model():
    """select_straggler_tolerance(completion="order"): an S below the
    expected straggler rate scores +inf (the first-arrival wait never ends
    on draws with more drops than tolerance), so the pick moves to S>=1 —
    the lookahead now prices the semantics the async runner executes."""
    p = cyclic_placement(4, 4, 3)
    sched = USECScheduler(p, rows_per_tile=96, initial_speeds=np.ones(4),
                          stragglers=0)
    best, scores = sched.select_straggler_tolerance(
        range(4), candidates=(0, 1), n_draws=64, expected_stragglers=1,
        completion="order", seed=5)
    assert np.isinf(scores[0]) and np.isfinite(scores[1])
    assert best == 1
    # same draws under the legacy coverage model: S=0 is equally infeasible,
    # and the feasible candidate's score is no cheaper under "order" (the
    # order statistic waits for whole workers, coverage only for segments).
    best_cov, scores_cov = sched.select_straggler_tolerance(
        range(4), candidates=(0, 1), n_draws=64, expected_stragglers=1,
        completion="coverage", seed=5)
    assert np.isinf(scores_cov[0]) and best_cov == 1
    assert scores[1] >= scores_cov[1]


# ---------------------------------------------------------------------- #
# Device: first-arrival runner semantics
# ---------------------------------------------------------------------- #
_RUNNER_PRELUDE = """
import numpy as np
from repro.core import cyclic_placement
from repro.core.elastic import MarkovChurnTrace
from repro.runtime import (ElasticRunner, RunnerConfig, SyntheticSpeedClock,
                           make_exact_matrix, quantize_unit)

BASE = [1000.0, 1400.0, 1900.0, 2600.0]
DIM = 256

def run_steps(arrival, s_tol, steps=8, seed=0, inject=None, jitter=0.3):
    x = make_exact_matrix(DIM, seed)
    placement = cyclic_placement(4, 4, 2 + s_tol)
    runner = ElasticRunner(
        x, placement,
        RunnerConfig(block_rows=16, stragglers=s_tol, verify="exact",
                     arrival=arrival),
        initial_speeds=BASE,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=jitter, seed=seed),
    )
    trace = MarkovChurnTrace(4, p_preempt=0.2, p_arrive=0.6, min_available=1,
                             seed=seed, placement=placement,
                             min_holders=1 + s_tol)
    w = quantize_unit(np.random.default_rng(seed + 7).normal(size=DIM))
    ys, reps = [], []
    for i in range(steps):
        sets = None if inject is None else inject[i]
        y, rep = runner.step(w, event=trace.step(), stragglers=sets)
        ys.append(y); reps.append(rep)
        w = quantize_unit(y)
    return ys, reps, runner
"""


def test_first_arrival_reduces_to_barrier_bitwise_at_s0():
    out = run_with_devices(_RUNNER_PRELUDE + """
yb, rb, runner_b = run_steps("barrier", 0)
yf, rf, runner_f = run_steps("first", 0)
# At S=0 every segment has exactly one holder: no arrival can be skipped,
# and the per-worker winner-gather must reproduce the psum barrier bitwise.
assert all((a == b).all() for a, b in zip(yb, yf))
assert all(r.straggled == () for r in rf)
# one compiled program computes every worker's partial
assert runner_f.executor_cache_size == 1, runner_f.executor_cache_size
# per-step completion identical too: nothing is skipped
mb = [r.modeled_completion for r in rb]
mf = [r.modeled_completion for r in rf]
assert mb == mf
print("S0-BITWISE-OK")
""", n_devices=4)
    assert "S0-BITWISE-OK" in out


def test_first_arrival_matches_stepwise_with_realized_injected():
    out = run_with_devices(_RUNNER_PRELUDE + """
yf, rf, runner_f = run_steps("first", 1)
realized = [r.straggled for r in rf]
assert any(realized), "straggler-prone clock should realize stragglers"
# replaying the realized sets through the barrier path (injection) must
# reproduce the async outputs bitwise: masking is the SAME include weights,
# only the combine differs (winner gather vs psum of winner + zeros).
yb, rb, _ = run_steps("barrier", 1, inject=realized)
assert all((a == b).all() for a, b in zip(yf, yb))
# first-arrival completion is the order statistic: never above the
# barrier's max over all loaded workers, strictly below whenever the
# realized straggler was the slowest.
for r in rf:
    mx = max(r.measured.values())
    assert r.modeled_completion <= mx + 1e-15
    if r.straggled:
        assert r.modeled_completion < mx
assert runner_f.executor_cache_size == 1
print("S1-REALIZED-OK")
""", n_devices=4)
    assert "S1-REALIZED-OK" in out


def test_first_arrival_absorbs_late_durations_into_ewma():
    out = run_with_devices(_RUNNER_PRELUDE + """
yf, rf, runner = run_steps("first", 1, steps=4)
# a late worker is a measurement, not a loss: every realized straggler's
# duration is in the step's measured dict...
for r in rf:
    assert set(r.straggled) <= set(r.measured)
# ... and actually reaches the estimator: after ingesting, a straggler's
# EWMA estimate moves off the (scaled) seed value.
seed_speeds = np.asarray(BASE, float) / runner.rows_per_tile
straggled_ever = sorted({n for r in rf for n in r.straggled})
assert straggled_ever
runner.ingest_pending()
s_hat = runner.scheduler.speeds
moved = [n for n in straggled_ever if abs(s_hat[n] - seed_speeds[n]) > 1e-12]
assert moved, (s_hat, seed_speeds)
print("EWMA-ABSORB-OK")
""", n_devices=4)
    assert "EWMA-ABSORB-OK" in out


def test_fused_first_arrival_matches_stepwise_k1_and_k4():
    """Fused windows compose with the async mode: under a homogeneous
    policy (plans depend on membership only, so the EWMA-ingestion cadence
    cannot diverge plans between drivers) the fused driver must realize
    the SAME straggler sets and produce bitwise-identical outputs as the
    stepwise first-arrival path, for K in {1, 4}."""
    out = run_with_devices("""
import numpy as np
from repro.api.policy import Policy
from repro.api.workload import MatVecPowerIteration
from repro.core import cyclic_placement
from repro.runtime import (ElasticRunner, RunnerConfig, SyntheticSpeedClock,
                           make_exact_matrix, quantize_unit)

BASE = [1000.0, 1400.0, 1900.0, 2600.0]
DIM = 256
STEPS = 8

def mk(fuse):
    x = make_exact_matrix(DIM, 0)
    placement = cyclic_placement(4, 4, 3)
    return ElasticRunner(
        x, placement,
        RunnerConfig(block_rows=16, arrival="first", fuse_steps=fuse),
        initial_speeds=BASE,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.3, seed=0),
        workload=MatVecPowerIteration(),
        policy=Policy(stragglers=1, homogeneous=True),
    )

w0 = quantize_unit(np.random.default_rng(7).normal(size=DIM))

rs = mk(1)
ys_s, sets_s = [], []
w = w0
for _ in range(STEPS):
    y, rep = rs.step(w)
    ys_s.append(np.asarray(y)); sets_s.append(rep.straggled)
    w = quantize_unit(y)

for K in (1, 4):   # window length; the driver always dispatches fuse_steps
    rf = mk(4)
    ys_f, sets_f = [], []
    w = w0
    for _ in range(STEPS // K):
        w, ys, ws, reps = rf.step_window(w, straggler_sets=[None] * K)
        ys_f += [np.asarray(y) for y in ys]
        sets_f += [r.straggled for r in reps]
    assert sets_f == sets_s, (K, sets_f, sets_s)
    assert all((a == b).all() for a, b in zip(ys_f, ys_s)), K
    assert rf.executor_cache_size == 1, rf.executor_cache_size
assert any(sets_s), "expected realized stragglers under jitter 0.3"
print("FUSED-ASYNC-OK")
""", n_devices=4)
    assert "FUSED-ASYNC-OK" in out


def test_engine_arrival_knob_device_and_simulate():
    """EngineConfig.arrival plumbs through both backends: the device
    backend derives realized sets (straggler_sets=None), the simulate
    backend switches its completion model to the order statistic."""
    out = run_with_devices("""
import numpy as np
from repro.api import ElasticEngine, EngineConfig, MatVec, Policy
from repro.core import cyclic_placement
from repro.core.elastic import MarkovChurnTrace
from repro.runtime import SyntheticSpeedClock, make_exact_matrix, quantize_unit

BASE = [1000.0, 1400.0, 1900.0, 2600.0]
DIM = 256
p = cyclic_placement(4, 4, 3)
W0 = quantize_unit(np.random.default_rng(11).normal(size=DIM))

def run_dev(arrival):
    trace = MarkovChurnTrace(4, p_preempt=0.2, p_arrive=0.6, min_available=1,
                             seed=0, placement=p, min_holders=2)
    eng = ElasticEngine(
        MatVec(), Policy(stragglers=1),
        EngineConfig(verify="exact", arrival=arrival),
        backend="device", placement=p,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.3, seed=0),
    )
    return eng.run(make_exact_matrix(DIM, 0), n_steps=6,
                   events=(trace.step() for _ in range(6)), operand=W0)

res_f = run_dev("first")
assert res_f.executor_cache_size == 1
assert any(r.straggled for r in res_f.reports)
res_b = run_dev("barrier")
assert all(r.straggled == () for r in res_b.reports)
# order-statistic completion never exceeds the barrier's per-step max
for rf, rb in zip(res_f.reports, res_b.reports):
    assert rf.modeled_completion <= max(rb.measured.values()) + 1e-15

# Simulate backend: arrival="first" prices with the "order" model,
# arrival="barrier" keeps the legacy "coverage" analytic model (bitwise
# stability). Coverage is a LOWER bound on the order statistic: when the
# (N-S)-th worker arrives, at most S of a segment's 1+S holders are
# missing, so every segment is already covered.
def run_sim(arrival):
    trace = MarkovChurnTrace(4, p_preempt=0.2, p_arrive=0.6, min_available=1,
                             seed=0, placement=p, min_holders=2)
    eng = ElasticEngine(
        MatVec(), Policy(stragglers=1),
        EngineConfig(rows_per_tile=64, seed=3, n_draws=128,
                     initial_speeds=BASE, arrival=arrival),
        backend="simulate", placement=p,
    )
    return eng.run(n_steps=6, events=(trace.step() for _ in range(6)))

sim_f = run_sim("first")
sim_b = run_sim("barrier")
assert sim_f.n_steps == sim_b.n_steps == 6
assert np.isfinite(sim_f.completion_times).all()
assert (sim_f.completion_times >= sim_b.completion_times - 1e-15).all()
assert (sim_f.completion_times > sim_b.completion_times).any()
print("ENGINE-ARRIVAL-OK")
""", n_devices=4)
    assert "ENGINE-ARRIVAL-OK" in out
