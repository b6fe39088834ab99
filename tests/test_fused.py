"""Fused multi-step device windows: bitwise parity, flush, telemetry.

The fused driver (``fuse_steps=K``) must be a pure perf transform: every
parity test here asserts the fused run's outputs are BITWISE equal to the
stepwise (K=1) path — across churn, forced stragglers, early window
flushes and all three shipped workloads — while the jit cache stays at one
entry and dispatches collapse to ~steps/K.

Device tests run on forced host devices in a subprocess
(``conftest.run_with_devices``).
"""

import numpy as np
import pytest

from conftest import run_with_devices

_COMMON = """
import math
import numpy as np
from repro.api import (ElasticEngine, EngineConfig, MapReduceRows, MatMat,
                       MatVecPowerIteration, Policy)
from repro.core.elastic import scripted_trace
from repro.runtime import SyntheticSpeedClock, make_exact_matrix

BASE = [1000., 1400., 1900., 2600.]
DIM = 4 * 96
X = make_exact_matrix(DIM, 0)
POLICY = Policy(placement="cyclic", replication=3, stragglers=1)
SCRIPT = {0: ((2,), ()), 1: ((), (2,)), 3: ((0,), ()), 5: ((), (0,)),
          6: ((3,), ()), 8: ((), (3,))}

def engine(workload, fuse_steps, **cfg_kw):
    # Noiseless clock + matching initial speeds keep the EWMA pinned at
    # (within ulps of) its fixed point, so the drift gate never fires and
    # membership sequences/plan-cache behavior stay deterministic.
    kw = dict(block_rows=16, verify="exact", fuse_steps=fuse_steps,
              initial_speeds=tuple(BASE))
    kw.update(cfg_kw)
    return ElasticEngine(
        workload, POLICY, EngineConfig(**kw), backend="device",
        n_machines=4,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0))

def run_churn(workload, fuse_steps, steps=9, **cfg_kw):
    pick = np.random.default_rng(1)
    bad = lambda i, avail: (int(pick.choice(avail)),)
    eng = engine(workload, fuse_steps, **cfg_kw)
    res = eng.run(X, n_steps=steps, events=scripted_trace(4, SCRIPT),
                  straggler_sets=bad)
    return eng, res

def assert_report_parity(a, b):
    # Step-sequence parity: same memberships, same realized stragglers,
    # same step count. (Plan-level telemetry like per-step waste is NOT
    # asserted here: the EWMA ingests measurements per step vs per window,
    # and that ulp-level difference can flip a degenerate LP between
    # equally-optimal vertices. Outputs stay bitwise-equal regardless —
    # and the homogeneous-policy test below pins full plan/waste parity
    # where the estimator cannot influence the solve.)
    assert [r.available for r in a.reports] == \\
        [r.available for r in b.reports]
    assert [r.straggled for r in a.reports] == \\
        [r.straggled for r in b.reports]
    assert a.n_steps == b.n_steps
"""


def test_fused_k_bitwise_parity_power_iteration():
    out = run_with_devices(_COMMON + """
base_eng, base = run_churn(MatVecPowerIteration(seed=0), 1)
for K in (4, 7):
    eng, res = run_churn(MatVecPowerIteration(seed=0), K)
    pi, pb = res.result, base.result
    assert np.array_equal(pi.eigvec, pb.eigvec), K
    assert pi.residuals == pb.residuals and pi.eigval == pb.eigval, K
    assert_report_parity(res, base)
    assert res.executor_cache_size == 1, res.executor_cache_size
    # Windows span churn once memberships are cached: plan swaps are
    # in-window data. Early cold-cache misses still flush (steps 3 and 6
    # adopt memberships the precompiler has not covered yet), so the
    # deterministic window structure is [0][1,2][3,4,5][6,7,8] for both K
    # — 4 dispatches for 9 steps instead of 9, and exactly ceil(steps/K)
    # once warm (see the dispatch-count test).
    assert eng.runner.device_dispatches == 4, (
        K, eng.runner.device_dispatches)
print("FUSED-PI-PARITY-OK", base.result.eigval)
""", n_devices=4)
    assert "FUSED-PI-PARITY-OK" in out


def test_fused_k_bitwise_parity_matmat_and_mapreduce():
    out = run_with_devices(_COMMON + """
import jax.numpy as jnp

rng = np.random.default_rng(5)
W = (np.round(rng.normal(size=(DIM, 8)) * 16) / 16).astype(np.float32)
_, base = run_churn(MatMat(W), 1)
for K in (4, 7):
    _, res = run_churn(MatMat(W), K)
    assert np.array_equal(res.result, base.result), K
    assert_report_parity(res, base)
    assert res.executor_cache_size == 1
assert np.array_equal(base.result,
                      X.astype(np.float64) @ W.astype(np.float64))

def make_mr():
    return MapReduceRows(
        row_fn=lambda xb, w2: jnp.sum(xb.astype(jnp.float32) ** 2, axis=1,
                                      keepdims=True),
        reduce_fn=lambda mapped: float(mapped.sum()),
        out_cols=1,
        ref_row_fn=lambda x64, w: np.sum(x64 ** 2, axis=1, keepdims=True),
    )

_, base = run_churn(make_mr(), 1)
for K in (4, 7):
    _, res = run_churn(make_mr(), K)
    assert res.result == base.result, K
    assert_report_parity(res, base)
assert base.result == float(np.sum(X.astype(np.float64) ** 2))
print("FUSED-WORKLOADS-PARITY-OK", base.result)
""", n_devices=4)
    assert "FUSED-WORKLOADS-PARITY-OK" in out


def test_fused_flush_on_plan_cache_miss_stays_bitwise():
    """With the speculative precompiler OFF, every churn event is a
    plan-cache miss — the window assembler must flush early (more
    dispatches than ceil(steps/K)), and the outputs must STILL be bitwise
    equal to stepwise."""
    out = run_with_devices(_COMMON + """
_, base = run_churn(MatVecPowerIteration(seed=0), 1,
                    precompile_neighbors=False)
eng, res = run_churn(MatVecPowerIteration(seed=0), 4,
                     precompile_neighbors=False)
pi, pb = res.result, base.result
assert np.array_equal(pi.eigvec, pb.eigvec)
assert pi.residuals == pb.residuals
assert_report_parity(res, base)
nd = eng.runner.device_dispatches
# SCRIPT churns at steps 0,1,3,5,6,8 -> misses force mid-window flushes.
assert nd > math.ceil(9 / 4), nd
assert res.executor_cache_size == 1, res.executor_cache_size
# every step still executed exactly once
assert res.n_steps == 9 and len(res.reports) == 9
print("FUSED-FLUSH-OK", nd)
""", n_devices=4)
    assert "FUSED-FLUSH-OK" in out


def test_fused_dispatch_count_and_tail_window():
    """Static membership: device_dispatches == ceil(steps / K), including
    a ragged tail window (inactive padding steps are discarded)."""
    out = run_with_devices(_COMMON + """
for steps, K in ((8, 4), (10, 4), (9, 7), (3, 8)):
    eng = engine(MatVecPowerIteration(seed=0), K)
    res = eng.run(X, n_steps=steps)
    assert eng.runner.device_dispatches == math.ceil(steps / K), (
        steps, K, eng.runner.device_dispatches)
    assert res.n_steps == steps and len(res.reports) == steps
    assert res.executor_cache_size == 1
    # and the fused run equals the stepwise run bit for bit
    eng1 = engine(MatVecPowerIteration(seed=0), 1)
    base = eng1.run(X, n_steps=steps)
    assert np.array_equal(res.result.eigvec, base.result.eigvec)
    assert res.result.residuals == base.result.residuals
print("FUSED-DISPATCH-OK")
""", n_devices=4)
    assert "FUSED-DISPATCH-OK" in out


def test_fused_homogeneous_policy_full_plan_and_waste_parity():
    """With ``homogeneous=True`` every plan solves under unit speeds — the
    estimator cannot influence the LP, so fused and stepwise runs compile
    IDENTICAL plan sequences and the full per-step telemetry (waste,
    replans, cache hits) matches exactly, not just the outputs."""
    out = run_with_devices(_COMMON + """
HPOL = Policy(placement="cyclic", replication=3, stragglers=1,
              homogeneous=True)

def run_h(K):
    pick = np.random.default_rng(1)
    bad = lambda i, avail: (int(pick.choice(avail)),)
    eng = ElasticEngine(
        MatVecPowerIteration(seed=0), HPOL,
        EngineConfig(block_rows=16, verify="exact", fuse_steps=K,
                     initial_speeds=tuple(BASE)),
        backend="device", n_machines=4,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0))
    return eng.run(X, n_steps=9, events=scripted_trace(4, SCRIPT),
                   straggler_sets=bad)

base = run_h(1)
for K in (4, 7):
    res = run_h(K)
    assert np.array_equal(res.result.eigvec, base.result.eigvec), K
    assert res.result.residuals == base.result.residuals, K
    assert_report_parity(res, base)
    assert [r.waste for r in res.reports] == \\
        [r.waste for r in base.reports], K
    assert res.total_waste == base.total_waste
    # (replanned matches: identical plan sequences change at the same
    # steps. plan_cache_hit may differ — the speculative precompiler
    # targets per-miss memberships stepwise but end-of-window memberships
    # fused, so WHO compiled a plan differs even when the plan does not.)
    assert [r.replanned for r in res.reports] == \\
        [r.replanned for r in base.reports], K
print("FUSED-HOMOGENEOUS-PARITY-OK", base.total_waste)
""", n_devices=4)
    assert "FUSED-HOMOGENEOUS-PARITY-OK" in out


def test_fused_window_slowdown_triggers_cstar_priced_replan():
    """Satellite regression: speed estimation under fused windows. The
    EWMA is fed per-window per-worker times (window wall / K in
    tile-units/s), so a mid-run slowdown must still drift the estimate
    past tolerance and trip the c*-priced re-plan gate — the adopted plan
    sheds load from the slowed worker."""
    out = run_with_devices("""
import numpy as np
from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
from repro.runtime import make_exact_matrix

BASE = np.array([1000., 1400., 1900., 2600.])
DIM = 4 * 96

class SlowdownClock:
    # Worker 3 collapses to 1/8 speed after `slow_after` duration queries.
    def __init__(self, slow_after):
        self.slow_after = slow_after
        self.calls = 0
    def durations(self, row_loads, available, wall):
        s = BASE.copy()
        if self.calls >= self.slow_after:
            s[3] /= 8.0
        self.calls += 1
        return {n: float(row_loads[n]) / s[n]
                for n in available if row_loads[n] > 0}

eng = ElasticEngine(
    MatVecPowerIteration(seed=0),
    Policy(placement="cyclic", replication=3, stragglers=1),
    EngineConfig(block_rows=16, verify="exact", fuse_steps=4,
                 initial_speeds=tuple(BASE)),
    backend="device", n_machines=4, clock=SlowdownClock(slow_after=8))
res = eng.run(X := make_exact_matrix(DIM, 0), n_steps=32)
runner = eng.runner
# Steps 1..8: estimator at fixed point, ONE plan total. After the
# slowdown the drift gate must price and adopt a fresh plan.
replans = [r.step for r in res.reports
           if r.replanned and not r.plan_cache_hit]
assert replans[0] == 1 and len(replans) >= 2, replans
assert replans[1] > 8, replans
loads = runner.current_plan.loads()
assert loads[3] < loads[:3].max() / 2, loads  # slowed worker sheds load
# ... and the re-planned run still verifies exactly every step (cfg above
# runs verify="exact"), with the executor never recompiling.
assert res.executor_cache_size == 1
print("FUSED-EWMA-OK", replans[:3], loads.round(2).tolist())
""", n_devices=4)
    assert "FUSED-EWMA-OK" in out


_PATH_CASES = {
    # The gathered flat matmul ("ref") and the interpret-mode Pallas
    # ("interpret") segmented paths, stepwise and fused.
    "segmented": """
_, base = run_churn(MatVecPowerIteration(seed=0), 1, steps=6)
for seg, K in (("ref", 1), ("ref", 4), ("interpret", 1)):
    _, res = run_churn(MatVecPowerIteration(seed=0), K, steps=6,
                       segmented=seg)
    pi, pb = res.result, base.result
    assert np.array_equal(pi.eigvec, pb.eigvec), (seg, K)
    assert pi.residuals == pb.residuals, (seg, K)
    assert res.executor_cache_size == 1

rng = np.random.default_rng(5)
W = (np.round(rng.normal(size=(DIM, 4)) * 16) / 16).astype(np.float32)
_, mm_base = run_churn(MatMat(W), 1, steps=5)
_, mm_seg = run_churn(MatMat(W), 4, steps=5, segmented="ref")
assert np.array_equal(mm_seg.result, mm_base.result)
""",
    # segmented=None with Pallas kernels (interpreted here) streams a
    # one-column block list: the stepwise barrier step, the first-arrival
    # partials and the fused window.
    "stream": """
bases = {}
for K, arrival in ((1, "barrier"), (4, "barrier"), (1, "first")):
    if arrival not in bases:
        eng, bases[arrival] = run_churn(MatVecPowerIteration(seed=0), 1,
                                        steps=6, arrival=arrival)
        assert eng.runner.block_path == "loop", arrival
    eng, res = run_churn(MatVecPowerIteration(seed=0), K, steps=6,
                         arrival=arrival, matmul_mode="interpret")
    assert eng.runner.block_path == "stream", (K, arrival)
    pi, pb = res.result, bases[arrival].result
    assert np.array_equal(pi.eigvec, pb.eigvec), (K, arrival)
    assert pi.residuals == pb.residuals, (K, arrival)
    assert_report_parity(res, bases[arrival])
    assert res.executor_cache_size == 1
""",
    # More than one column, or a non-linear workload: the per-block loop.
    "loop": """
import jax.numpy as jnp

rng = np.random.default_rng(5)
W = (np.round(rng.normal(size=(DIM, 2)) * 16) / 16).astype(np.float32)
mr = MapReduceRows(
    row_fn=lambda xb, w2: jnp.sum(xb.astype(jnp.float32) ** 2, axis=1,
                                  keepdims=True),
    reduce_fn=lambda mapped: float(mapped.sum()), out_cols=1,
    ref_row_fn=lambda x64, w: np.sum(x64 ** 2, axis=1, keepdims=True))
for wl in (MatMat(W), mr):
    eng, res = run_churn(wl, 1, steps=3, matmul_mode="interpret")
    assert eng.runner.block_path == "loop", type(wl).__name__
    assert res.executor_cache_size == 1
_, base = run_churn(MatMat(W), 1, steps=3)
eng, res = run_churn(MatMat(W), 1, steps=3, matmul_mode="interpret")
assert np.array_equal(res.result, base.result)
""",
}


@pytest.mark.parametrize("case", sorted(_PATH_CASES))
def test_segmented_executor_paths_match_fori_loop(case):
    """Engine-level block-list paths against the per-block fori_loop
    executor, bitwise on integer-grid data under churn with forced
    stragglers, the jit cache at one entry: the segmented paths, the
    streamed one-column path (``block_path`` "stream"), and the cases
    that keep the loop (``block_path`` "loop")."""
    out = run_with_devices(_COMMON + _PATH_CASES[case]
                           + "print('PATH-PARITY-OK')\n", n_devices=4)
    assert "PATH-PARITY-OK" in out


def test_normalize_is_correctly_rounded_on_numpy_and_jax():
    """The iterate normalize's sqrt and divide are built from int32 ops
    and return IEEE round-to-nearest-even bits — NumPy's — on every
    backend. (A TPU's own float32 sqrt and divide are approximate, which
    would break the fused window's bitwise parity with the host.)"""
    import jax
    import jax.numpy as jnp

    from repro.runtime.elastic_runner import (
        _normalize,
        _rn_div,
        _rn_sqrt,
        _tree_sumsq,
    )

    rng = np.random.default_rng(0)
    n = 1 << 18
    a = ((rng.random(n) - 0.5) * 2e4).astype(np.float32)
    a[:4] = (0.0, -0.0, 1.0, -1.0)
    s = (rng.random(n) * 1e6 + 1e-3).astype(np.float32)
    squares = np.float32(rng.integers(1, 4096, size=1024)) ** 2
    div_jax = jax.jit(lambda a, d: _rn_div(a, d, jnp))
    sqrt_jax = jax.jit(lambda s: _rn_sqrt(s, jnp))
    for d in (np.float32(1.0), np.float32(3.0), np.float32(123.456),
              np.float32(1e-20), np.float32(7e20)):
        want = (a / d).view(np.int32)
        assert np.array_equal(_rn_div(a, d, np).view(np.int32), want)
        assert np.array_equal(np.asarray(div_jax(a, d)).view(np.int32),
                              want)
    for v in (s, squares):
        assert np.array_equal(_rn_sqrt(v, np), np.sqrt(v))
        assert np.array_equal(np.asarray(sqrt_jax(v)), np.sqrt(v))
    v = rng.normal(size=24576).astype(np.float32)
    want = v / np.sqrt(_tree_sumsq(v, np))
    assert np.array_equal(_normalize(v, np), want)
    assert np.array_equal(
        np.asarray(jax.jit(lambda v: _normalize(v, jnp))(v)), want)
