"""AvailabilityTrace edge cases + live elastic runner integration.

The trace tests are pure NumPy (no jax); the runner tests execute on forced
host devices in a subprocess (see ``conftest.run_with_devices``).
"""

import numpy as np
import pytest

from conftest import run_with_devices
from repro.core import (
    custom_placement,
    cyclic_placement,
    compile_plan,
    solve_assignment,
)
from repro.core.elastic import (
    AvailabilityTrace,
    ElasticEvent,
    MarkovChurnTrace,
    scripted_trace,
)
from repro.core.placement import LostTileError
from repro.runtime.simulate import simulate_step


# ---------------------------------------------------------------------- #
# AvailabilityTrace edge cases
# ---------------------------------------------------------------------- #
def test_all_machines_preempted_at_once():
    tr = AvailabilityTrace(4)
    ev = tr.apply(preempt=range(4))
    assert ev.available == ()
    assert ev.preempted == (0, 1, 2, 3)
    # An empty availability set is a data-availability failure for every
    # placement: restrict() must raise, not return an empty plan.
    p = cyclic_placement(4, 4, 2)
    with pytest.raises(LostTileError):
        p.restrict(ev.available)


def test_arrival_only_events():
    tr = AvailabilityTrace(5, available0=[0, 1])
    ev = tr.apply(arrive=[2, 3])
    assert ev.preempted == ()
    assert ev.arrived == (2, 3)
    assert ev.available == (0, 1, 2, 3)
    # arrivals of already-present or out-of-range machines are no-ops
    ev2 = tr.apply(arrive=[0, 3, 4, 99])
    assert ev2.arrived == (4,)
    assert ev2.available == (0, 1, 2, 3, 4)
    # a pure no-op event still advances the step counter deterministically
    ev3 = tr.apply()
    assert (ev3.preempted, ev3.arrived) == ((), ())
    assert ev3.step == 3


def test_single_survivor_membership():
    # Machine 0 holds every tile (tile 0 exclusively): the system must keep
    # running (and plan sensibly) when it is the only survivor.
    p = custom_placement(4, [(0,)] + [(0, g % 3 + 1) for g in range(5)])
    restricted = p.restrict([0])
    assert all(h == (0,) for h in restricted.holders)
    sol = solve_assignment(p, np.ones(4), available=[0], stragglers=0)
    plan = compile_plan(p, sol, rows_per_tile=8, stragglers=0)
    assert plan.n_valid[0] > 0 and not plan.n_valid[1:].any()
    t = simulate_step(plan, np.ones(4))
    # the lone survivor computes all 6 tiles' rows
    assert t.completion_time == pytest.approx(6.0)
    # ... but losing machine 0 is unrecoverable (tile 0 has no other holder)
    with pytest.raises(LostTileError):
        p.restrict([1, 2, 3])


def test_markov_trace_deterministic_under_fixed_seed():
    p = cyclic_placement(6, 6, 3)

    def roll(seed):
        tr = MarkovChurnTrace(6, p_preempt=0.3, p_arrive=0.5, min_available=2,
                              seed=seed, placement=p, min_holders=2)
        return [tr.step() for _ in range(40)]

    a, b = roll(7), roll(7)
    assert [e.available for e in a] == [e.available for e in b]
    assert [(e.preempted, e.arrived) for e in a] == \
        [(e.preempted, e.arrived) for e in b]
    c = roll(8)
    assert [e.available for e in a] != [e.available for e in c]
    # the floor constraints held at every step
    for e in a:
        assert len(e.available) >= 2
        assert p.restrict(e.available).replication >= 2


def test_scripted_trace_yields_exact_script():
    events = scripted_trace(4, {0: ((3,), ()), 2: ((), (3,))})
    e0 = next(events)
    assert (e0.preempted, e0.arrived, e0.available) == ((3,), (), (0, 1, 2))
    e1 = next(events)
    assert (e1.preempted, e1.arrived) == ((), ())
    e2 = next(events)
    assert (e2.arrived, e2.available) == ((3,), (0, 1, 2, 3))


# ---------------------------------------------------------------------- #
# Live runner (forced host devices, subprocess)
# ---------------------------------------------------------------------- #
def test_runner_exact_under_churn_without_recompilation():
    out = run_with_devices("""
import numpy as np
from repro.core import cyclic_placement
from repro.core.elastic import scripted_trace
from repro.runtime import (ElasticRunner, RunnerConfig, SyntheticSpeedClock,
                           run_power_iteration)

rng = np.random.default_rng(0)
dim = 4 * 96
a = rng.integers(-3, 4, size=(dim, dim))
x = (a + a.T + 30 * np.eye(dim, dtype=np.int64)).astype(np.float32)

# S=1 on a 3-replicated placement: survives any single preemption AND one
# straggler per step; verify="exact" bit-checks y == X @ w every step.
p = cyclic_placement(4, 4, 3)
runner = ElasticRunner(
    x, p, RunnerConfig(block_rows=16, stragglers=1, verify="exact"),
    clock=SyntheticSpeedClock([1000.0, 1300.0, 1800.0, 2400.0],
                              jitter_sigma=0.05, seed=0),
)
script = {0: ((2,), ()), 1: ((), (2,)), 2: ((0,), ()), 4: ((), (0,))}
picker = np.random.default_rng(1)
# Which step each on-demand plan solve ran in.
solved_at = []
solve = runner.planning_master.plan_step
def plan_step(*args, **kwargs):
    solved_at.append(runner._step)
    return solve(*args, **kwargs)
runner.planning_master.plan_step = plan_step
res = run_power_iteration(
    runner, 7, events=scripted_trace(4, script),
    straggler_sets=lambda i, avail: (int(picker.choice(avail)),),
    seed=0,
)
assert res.churn_events >= 3, res.churn_events
assert res.executor_cache_size == 1, res.executor_cache_size
assert res.plans_compiled >= 2       # membership changes forced fresh plans
assert res.cache_hits >= 1           # ... and revisits reused them
assert res.total_waste >= 0
assert res.residuals[-1] < res.residuals[0]   # power iteration converging
# A cache-hit replan adopts a memoized plan and solves nothing; a miss
# solves once. (Counted, not timed: on a loaded host a hit's drift probe
# and a miss's small solve are a few ms apart at most.)
hit = [r.step - 1 for r in res.reports if r.plan_cache_hit]
miss = [r.step - 1 for r in res.reports
        if r.replanned and not r.plan_cache_hit]
assert hit and miss
assert not set(hit) & set(solved_at), (hit, solved_at)
assert sorted(solved_at) == miss, (miss, solved_at)
print("RUNNER-OK", res.plans_compiled, res.cache_hits, res.churn_events)
""", n_devices=4)
    assert "RUNNER-OK" in out


def test_runner_plan_cache_lru_eviction_and_recompile():
    out = run_with_devices("""
import numpy as np
from repro.core import cyclic_placement
from repro.core.elastic import scripted_trace
from repro.runtime import (ElasticRunner, RunnerConfig, SyntheticSpeedClock,
                           quantize_unit)

rng = np.random.default_rng(0)
dim = 4 * 32
a = rng.integers(-2, 3, size=(dim, dim))
x = (a + a.T + 10 * np.eye(dim, dtype=np.int64)).astype(np.float32)
p = cyclic_placement(4, 4, 3)
# Noiseless clock matching the initial estimates: the EWMA never drifts, so
# cache behavior is purely a function of the visited membership sequence.
BASE = [1000.0] * 4
clock = lambda: SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0)
# Cap the cache at 2 entries with speculative precompilation off, so the
# eviction path is driven purely by the visited membership sequence.
runner = ElasticRunner(
    x, p, RunnerConfig(block_rows=16, stragglers=0, verify="exact",
                       precompile_neighbors=False, plan_cache_size=2),
    initial_speeds=BASE, clock=clock())
w = quantize_unit(rng.normal(size=dim))
# Walk memberships A, B, C, A: with capacity 2, A is evicted by C and must
# recompile on revisit — and still verify bit-exactly.
script = {1: ((3,), ()), 2: ((2,), (3,)), 3: ((), (2,))}
events = scripted_trace(4, script)
seen = []
for i in range(4):
    y, rep = runner.step(w, event=next(events))
    seen.append((rep.available, rep.plan_cache_hit))
assert len(runner._plan_cache) <= 2
assert runner.plans_evicted >= 1, runner.plans_evicted
# The revisit of the full membership was evicted -> fresh compile, not a hit.
assert seen[0][0] == seen[3][0] == (0, 1, 2, 3)
assert not seen[3][1]
assert runner.plans_compiled == 4
# Unbounded (default) keeps every entry and the revisit hits.
runner2 = ElasticRunner(
    x, p, RunnerConfig(block_rows=16, stragglers=0, verify="exact",
                       precompile_neighbors=False),
    initial_speeds=BASE, clock=clock())
events = scripted_trace(4, script)
hits = []
for i in range(4):
    y, rep = runner2.step(w, event=next(events))
    hits.append(rep.plan_cache_hit)
assert hits[3] and runner2.plans_compiled == 3 and runner2.plans_evicted == 0
print("LRU-OK", runner.plans_evicted)
""", n_devices=4)
    assert "LRU-OK" in out


def test_runner_rejects_stragglers_beyond_tolerance():
    out = run_with_devices("""
import numpy as np
from repro.core import cyclic_placement
from repro.runtime import ElasticRunner, RunnerConfig, quantize_unit

rng = np.random.default_rng(0)
dim = 4 * 32
a = rng.integers(-2, 3, size=(dim, dim))
x = (a + a.T + 10 * np.eye(dim, dtype=np.int64)).astype(np.float32)
runner = ElasticRunner(x, cyclic_placement(4, 4, 2),
                       RunnerConfig(block_rows=16, stragglers=0))
w = quantize_unit(rng.normal(size=dim))
y, rep = runner.step(w)                      # S=0, no stragglers: fine
assert rep.jit_cache_size == 1
try:
    runner.step(w, stragglers=(0,))          # any straggler exceeds S=0
except RuntimeError as e:
    assert "exceeds" in str(e), e
    print("TOLERANCE-OK")
""", n_devices=4)
    assert "TOLERANCE-OK" in out


def test_runner_rejects_out_of_range_straggler_ids():
    """Straggler-id validation (regression): the fused window assembler
    silently FILTERED out-of-range ids from injected sets while the
    stepwise path passed them through unvalidated — a typo in a replay
    script changed semantics without a peep. Both drivers now raise
    ValueError naming the offending id."""
    out = run_with_devices("""
import numpy as np
from repro.core import cyclic_placement
from repro.runtime import ElasticRunner, RunnerConfig, quantize_unit

rng = np.random.default_rng(0)
dim = 4 * 64
x = rng.integers(-2, 3, size=(dim, dim)).astype(np.float32)
p = cyclic_placement(4, 4, 3)
w = quantize_unit(rng.normal(size=dim))

runner = ElasticRunner(x, p, RunnerConfig(block_rows=16, stragglers=1))
try:
    runner.step(w, stragglers=(99,))
    raise SystemExit("stepwise accepted id 99")
except ValueError as e:
    assert "99" in str(e) and "0..3" in str(e), e
try:
    runner.step(w, stragglers=(-1,))
    raise SystemExit("stepwise accepted id -1")
except ValueError as e:
    assert "-1" in str(e), e
y, rep = runner.step(w, stragglers=(3,))     # in-range still works
assert rep.straggled == (3,)

from repro.api.workload import MatVecPowerIteration
fused = ElasticRunner(
    x, p, RunnerConfig(block_rows=16, stragglers=1, fuse_steps=2),
    workload=MatVecPowerIteration())
try:
    fused.step_window(w, straggler_sets=[(1,), (99,)])
    raise SystemExit("fused accepted id 99")
except ValueError as e:
    assert "99" in str(e), e
w2, ys, ws, reps = fused.step_window(w, straggler_sets=[(1,), (3,)])
assert [r.straggled for r in reps] == [(1,), (3,)]
print("ID-VALIDATION-OK")
""", n_devices=4)
    assert "ID-VALIDATION-OK" in out


def test_homogeneous_policy_skips_drift_gate_and_probe_solves():
    """Homogeneous-mode drift gate (regression): the paper's equal-speed
    baseline plans ignore the EWMA entirely, yet the runner still priced a
    fresh c* probe per cached-plan step and re-planned whenever measured
    speeds drifted — recompiling identical plans. With
    ``Policy(homogeneous=True)`` the cache must hit on membership alone:
    zero probe solves under a drifting clock."""
    out = run_with_devices("""
import numpy as np
from repro.api.policy import Policy
from repro.core import cyclic_placement
from repro.runtime import (ElasticRunner, RunnerConfig, SyntheticSpeedClock,
                           make_exact_matrix, quantize_unit)

BASE = [1000.0, 1400.0, 1900.0, 2600.0]
dim = 4 * 64
x = make_exact_matrix(dim, 0)
p = cyclic_placement(4, 4, 2)
w = quantize_unit(np.random.default_rng(3).normal(size=dim))

def run(policy, jitter):
    runner = ElasticRunner(
        x, p, RunnerConfig(block_rows=16, verify="exact",
                           precompile_neighbors=False),
        initial_speeds=BASE,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=jitter, seed=0),
        policy=policy)
    for _ in range(6):
        y, rep = runner.step(w)
    return runner

homo = run(Policy(stragglers=0, homogeneous=True), jitter=0.5)
assert homo.probe_solves == 0, homo.probe_solves
assert homo.plans_compiled == 1, homo.plans_compiled
# the heterogeneous master DOES pay probes under the same drift — the
# homogeneous skip is a real savings, not a vacuous counter
hetero = run(Policy(stragglers=0), jitter=0.5)
assert hetero.probe_solves > 0, hetero.probe_solves
print("HOMOGENEOUS-GATE-OK", hetero.probe_solves)
""", n_devices=4)
    assert "HOMOGENEOUS-GATE-OK" in out


def test_tolerance_recommit_evicts_stale_plans():
    """Stale-tolerance plan cache (regression): committing a new S via
    ``select_straggler_tolerance(commit=True)`` cleared the scheduler's
    previous plan but NOT the runner's plan cache — the next step reused a
    cached plan compiled under the old S, silently executing with the
    stale tolerance. Cache entries now record their S and are evicted on
    mismatch."""
    out = run_with_devices("""
import numpy as np
from repro.core import cyclic_placement
from repro.runtime import (ElasticRunner, RunnerConfig, SyntheticSpeedClock,
                           make_exact_matrix, quantize_unit)

BASE = [1000.0, 1400.0, 1900.0, 2600.0]
dim = 4 * 64
x = make_exact_matrix(dim, 0)
p = cyclic_placement(4, 4, 3)          # replication 3: S=1 feasible
w = quantize_unit(np.random.default_rng(3).normal(size=dim))
runner = ElasticRunner(
    x, p, RunnerConfig(block_rows=16, stragglers=0, verify="exact",
                       precompile_neighbors=False),
    initial_speeds=BASE,
    clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0))
y0, rep0 = runner.step(w)
assert runner.current_plan.stragglers == 0
compiled_before = runner.plans_compiled
# the lookahead re-commits the tolerance mid-run (candidates=(1,) forces
# a deterministic pick)
best, _ = runner.scheduler.select_straggler_tolerance(
    runner.membership, candidates=(1,), n_draws=16,
    expected_stragglers=1, commit=True)
assert best == 1 and runner.scheduler.stragglers == 1
y1, rep1 = runner.step(w)
# the cached S=0 plan must NOT be reused: fresh S=1 plan, same membership
assert runner.current_plan.stragglers == 1, runner.current_plan.stragglers
assert runner.plans_compiled == compiled_before + 1
assert not rep1.plan_cache_hit
# ... and the new tolerance actually buys straggler survival
y2, rep2 = runner.step(w, stragglers=(3,))
assert np.array_equal(y2, y0)
print("STALE-S-OK")
""", n_devices=4)
    assert "STALE-S-OK" in out


@pytest.mark.parametrize("arrival", ["barrier", "first"])
def test_worker_data_lives_on_its_own_device(arrival):
    """Staged tiles and plan rows are placed by worker (worker n's slice on
    worker n's device only), the step program expects exactly that layout
    (no resharding on dispatch), the barrier step psums while the
    first-arrival partials stay on their own devices."""
    out = run_with_devices("""
import numpy as np
from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
from repro.runtime import make_exact_matrix

eng = ElasticEngine(
    MatVecPowerIteration(seed=0),
    Policy(placement="cyclic", replication=3, stragglers=1),
    EngineConfig(block_rows=16, verify="exact", arrival=%r),
    backend="device", n_machines=4)
eng.run(make_exact_matrix(4 * 96, 0), n_steps=2)
r = eng.runner
devs = list(r.mesh.devices.flat)
staged = r.staged_device
assert staged.shape == (4, 3, 96, 4 * 96), staged.shape
shards = sorted(staged.addressable_shards, key=lambda s: s.index[0].start)
assert [s.device for s in shards] == devs
assert all(s.data.shape == (1, 3, 96, 4 * 96) for s in shards)
assert all(s.index[0] == slice(n, n + 1) for n, s in enumerate(shards))
for a in r._current.dev:
    assert a.sharding.is_equivalent_to(r._by_worker, a.ndim), a.sharding
fn = r._worker_exec or r._executor
w = r._put_replicated(np.zeros(4 * 96, np.float32))
args = (staged, *r._current.dev, w)
compiled = fn.lower(*args).compile()
for a, want in zip(args, compiled.input_shardings[0]):
    assert a.sharding.is_equivalent_to(want, a.ndim), (a.sharding, want)
text = r.lowered_step_text()
assert ("all_reduce" in text) == (%r == "barrier"), text[:400]
if %r == "first":
    ys = fn(*args)
    assert ys.shape == (4, 4 * 96)
    assert [s.device for s in sorted(ys.addressable_shards,
            key=lambda s: s.index[0].start)] == devs
print("PLACED-OK")
""" % (arrival, arrival, arrival), n_devices=4)
    assert "PLACED-OK" in out
