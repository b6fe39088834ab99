"""Live elastic execution: the generic churn-driven device backend.

Everything below PR 1 *simulated* completion times; this module actually
executes a placement's plan across membership changes. It closes the loop the
paper runs on EC2 (§V): an :class:`~repro.core.elastic.AvailabilityTrace`
feeds :class:`~repro.core.elastic.ElasticEvent`\\ s into a master that

1. re-estimates worker speeds (EWMA, Algorithm 1 line 4) from *measured*
   per-worker step times of the previous step,
2. re-plans on membership change — compiled plans are **memoized per
   membership** and invalidated only when the speed estimate drifts past a
   tolerance, so revisited availability states reuse their plan in O(N),
3. executes the step through the shard_map executor
   (:func:`repro.runtime.executor.make_matvec_executor`) with the
   *workload's* per-block compute as the kernel — the Pallas ``usec_matvec``
   kernel on TPU for the matvec workloads (jnp reference on CPU — the
   dispatch of :func:`repro.kernels.ops.executor_matmul`), the blocked
   matmat path for :class:`~repro.api.workload.MatMat`, or any row-wise map.

The runner is workload-agnostic: the computation arrives as a
:class:`~repro.api.workload.Workload` (defaulting to plain matvec) and the
scheduler is configured through one :class:`~repro.api.policy.Policy`. The
preferred entry point is :class:`repro.api.ElasticEngine` with
``backend="device"``; :func:`run_power_iteration` below survives as a thin
deprecation shim over it.

Two consume rules (``RunnerConfig.arrival``): the legacy ``"barrier"`` step
blocks on every included worker inside one psum dispatch, while ``"first"``
is the paper's first-arrival master — per-worker partials dispatched as
independently fetchable device calls, the first ``N_t - S`` modeled arrivals
consumed, the realized slowest-S set masked out of a host-side winner-gather
combine, and every late worker's duration still absorbed into the EWMA.

The static-shape contract: every array is padded to the **max-N membership**
(the full machine population). A preempted machine is a worker slot with
``n_blocks == 0`` and all-zero include weights — its shard runs an empty
``fori_loop`` and contributes zeros to the ``psum``. Membership changes
therefore swap plan *arrays* in place; the jitted step never recompiles
(:attr:`ElasticRunner.executor_cache_size` stays at 1, asserted by the
example and the runner tests).

Per-worker step times: on a real heterogeneous deployment each worker
reports its own wall time. A single timeshared host cannot observe those, so
the runner takes a pluggable clock — :class:`HostSharedClock` apportions the
measured step wall time by row share (the truth on a timeshared CPU), and
:class:`SyntheticSpeedClock` replays an EC2-like heterogeneous speed process
so examples/benchmarks exercise the EWMA adaptation reproducibly. Real step
wall time is always measured and reported (steps/sec telemetry).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.elastic import ElasticEvent, transition_waste
from repro.core.placement import LostTileError, Placement
from repro.core.scheduler import StepPlan

__all__ = [
    "ElasticRunner",
    "HostSharedClock",
    "PowerIterationResult",
    "RunnerConfig",
    "StepReport",
    "SyntheticSpeedClock",
    "make_exact_matrix",
    "quantize_unit",
    "run_power_iteration",
    "unit_vector",
]


# ---------------------------------------------------------------------- #
# Configuration / per-step report
# ---------------------------------------------------------------------- #
@dataclass(frozen=True)
class RunnerConfig:
    """Knobs of the live runner.

    block_rows: fixed-size work unit of the executor; must divide
      rows_per_tile (plans are compiled with ``row_align == block_rows``).
    stragglers: straggler tolerance S baked into every plan (superseded by
      an explicit ``policy=`` on the runner).
    gamma: EWMA mixing factor for the speed estimator (ditto).
    speed_tolerance: a memoized plan for a revisited membership is reused
      while ``max_n |s_hat[n]/s_plan[n] - 1| <= speed_tolerance`` over the
      available machines; past that drift, a cheap fresh solve prices the
      re-plan and the old plan is kept (re-baselined) unless it is more
      than ``speed_tolerance`` slower than the new optimum — so estimator
      noise never buys a plan swap (and its transition waste) for a
      negligible c* gain.
    matmul_mode: kernel dispatch handed to the workload's ``executor_fn``
      (None = Pallas on TPU, jnp reference elsewhere).
    verify: per-step output check against a float64 host reference —
      ``"exact"`` (bitwise; integer-valued data), ``"allclose"``, or None.
      The check itself is the workload's ``verify``.
    allclose_atol: tolerance of the ``"allclose"`` mode.
    precompile_neighbors: after any step that had to compile a fresh plan,
      speculatively batch-compile every single-preemption / single-arrival
      neighbor of the adopted membership (one
      :meth:`USECScheduler.plan_batch` call, off the step critical path) so
      the next churn event is a plan-cache *hit* — an O(100us) array swap
      instead of an O(ms) solve.
    plan_cache_size: LRU cap on memoized plans (entries, not bytes); None
      keeps the legacy unbounded behavior. Long Markov traces over large N
      visit many membership states — the cap bounds host + device memory,
      and an evicted state is simply re-compiled on its next visit.
    fuse_steps: K, iterations per device dispatch. 1 is the stepwise legacy
      path (one round-trip per step); K > 1 runs windows of K steps through
      the ``lax.scan`` fused driver (:meth:`ElasticRunner.step_window`) —
      the iterate update and straggler include masks stay on device, so a
      window costs one dispatch + one result fetch for K steps. Windows are
      always K long in the graph (flushed/tail steps are inactive padding),
      so the fused executor compiles exactly once.
    segmented: per-worker block-list execution — None keeps the per-block
      ``fori_loop``; "auto"/"pallas"/"interpret"/"ref" route the whole
      block list through the workload's ``segmented_fn`` (the
      scalar-prefetched Pallas kernel on TPU, one gathered flat matmul on
      CPU). Accumulation order differs from the loop in the last ulp on
      non-exact data (on the integer-grid matrices of the examples and
      parity tests, all paths agree bitwise).
    arrival: the master's consume rule. ``"barrier"`` (legacy) blocks on
      every included worker — the psum combine needs all shards.
      ``"first"`` implements the paper's first-arrival master: workers are
      dispatched as independently fetchable per-worker partials
      (:func:`repro.runtime.executor.make_worker_executor`), the master
      consumes the first ``N_t - S`` completions (modeled arrival order:
      the clock's durations), the realized slowest-S set is masked out of
      the combine via the ordinary include weights, and the late workers'
      durations still feed the EWMA — a straggler is a measurement, not a
      loss. Modeled completion becomes the (N_t - S)-th order statistic of
      worker finish times instead of the max. At S=0 every segment has one
      holder, no arrival can be skipped, and the path reduces to the
      barrier result bitwise. Composes with ``fuse_steps > 1``: fused
      windows derive each step's realized set at assembly time and mask it
      in-graph through the include gather.
    replan: who makes re-planning decisions on the live path.
      ``"central"`` (legacy) routes every planning call through the
      Algorithm-1 master (:attr:`ElasticRunner.scheduler`) — a single
      point of failure. ``"decentral"`` evaluates the pure local rule of
      :mod:`repro.core.decentral` over replicated (membership bitmask,
      versioned speed table, plan table) state instead: plans are
      bitwise-identical to the central solver's, repeated memberships
      under an unchanged speed snapshot are pure table lookups, and
      :meth:`ElasticRunner.kill_scheduler` mid-run does not stop the job.
      (An explicit ``policy=`` with ``replan="decentral"`` opts in too;
      either flag wins.)
    verify_results: silent-corruption defense (``"off"`` | ``"sample"``
      | ``"always"``). On verified steps the runner (1) audits every
      staged replica tile against its staging-time fingerprint and
      re-stages a corrupt tile from a surviving replica holder, and (2)
      Freivalds-checks the step output against seeded ±1 sketches of X
      (linear workloads; O(rows+cols) per column vs O(rows·cols)
      recompute — see :class:`repro.faults.integrity.IntegrityChecker`).
      A corrupt partial is discarded (first-arrival: realized straggler;
      barrier: masked + re-dispatched; fused: rows recomputed from a
      replica tile), its timing is censored from the EWMA, and repeat
      offenders are graylisted for a probation window. ``"sample"``
      verifies every :data:`repro.faults.integrity.SAMPLE_PERIOD`-th
      step. Unlike ``verify`` this needs no full float64 recompute, so
      it is cheap enough to leave on in production.
    """

    block_rows: int = 16
    stragglers: int = 0
    gamma: float = 0.5
    speed_tolerance: float = 0.10
    matmul_mode: Optional[str] = None
    verify: Optional[str] = None
    allclose_atol: float = 1e-3
    precompile_neighbors: bool = True
    plan_cache_size: Optional[int] = None
    fuse_steps: int = 1
    segmented: Optional[str] = None
    arrival: str = "barrier"
    replan: str = "central"
    dispatch_timeout: Optional[float] = None
    verify_results: str = "off"

    def __post_init__(self):
        # String knobs fail HERE, at construction, naming the allowed set —
        # not steps later inside the runner (or never, for knobs like
        # ``verify`` whose misspelling used to silently disable the check).
        _validate_choice("arrival", self.arrival, ("barrier", "first"))
        _validate_choice("replan", self.replan, ("central", "decentral"))
        _validate_choice("verify", self.verify,
                         (None, "exact", "allclose"))
        _validate_choice("segmented", self.segmented,
                         (None, "auto", "pallas", "interpret", "ref"))
        _validate_choice("verify_results", self.verify_results,
                         ("off", "sample", "always"))
        if self.dispatch_timeout is not None and self.dispatch_timeout <= 0:
            raise ValueError(
                f"dispatch_timeout must be > 0 (modeled seconds), got "
                f"{self.dispatch_timeout}")


def _validate_choice(name: str, value, allowed) -> None:
    """Raise ValueError naming the bad value and the allowed set."""
    if value not in allowed:
        raise ValueError(
            f"{name} must be one of {allowed}, got {value!r}")


@dataclass
class StepReport:
    """Telemetry of one executed elastic step."""

    step: int
    available: Tuple[int, ...]
    replanned: bool            # a different plan took effect this step
    plan_cache_hit: bool       # ... and it came from the membership cache
    replan_s: float            # host-side planning latency (solve+compile or cache swap)
    wall_s: float              # measured device step wall time (jit call, blocked)
    modeled_completion: float  # max over loaded workers of clocked duration
    straggled: Tuple[int, ...]
    waste: int                 # transition waste vs the previous step's plan
    jit_cache_size: int        # executor compile count so far (stays 1)
    measured: Dict[int, float] # per-worker durations fed to the EWMA next step
    speeds_hat: np.ndarray     # estimator state the plan was built under


# ---------------------------------------------------------------------- #
# Per-worker clocks
# ---------------------------------------------------------------------- #
class HostSharedClock:
    """Per-worker durations on a timeshared host: wall time × row share.

    Forced host devices execute on one CPU, so worker n's slice of the
    measured wall clock is (to first order) its share of the total assigned
    rows. The induced throughput ``nu_n = load_n / duration_n`` is equal
    across workers — which is the truth on a timeshared host, so the EWMA
    converges to uniform speeds.

    Clocks receive per-worker **row** loads (not tile units): row counts
    mean the same thing under every placement, so modeled completion times
    are comparable across placements with different tile sizes.
    """

    def durations(
        self, row_loads: np.ndarray, available: Sequence[int], wall: float
    ) -> Dict[int, float]:
        loaded = [n for n in available if row_loads[n] > 0]
        total = float(sum(row_loads[n] for n in loaded))
        if total <= 0:
            return {}
        return {n: wall * float(row_loads[n]) / total for n in loaded}


class SyntheticSpeedClock:
    """Replays a heterogeneous speed process: duration = row-load / speed.

    Speeds are in rows per second. Models the paper's EC2 observation
    (persistently different speeds with per-step jitter) on a host that
    cannot produce real heterogeneity. The realized per-step speed vectors
    are recorded in :attr:`history` so benchmarks can cross-check the
    runner's step times against :func:`repro.runtime.simulate.simulate_batch`
    predictions.
    """

    def __init__(
        self,
        base: Sequence[float],
        jitter_sigma: float = 0.0,
        drift_sigma: float = 0.0,
        seed: int = 0,
    ):
        from .simulate import SpeedProcess

        self.process = SpeedProcess(
            base=np.asarray(base, dtype=np.float64),
            jitter_sigma=jitter_sigma,
            drift_sigma=drift_sigma,
            seed=seed,
        )
        self.history: List[np.ndarray] = []

    def durations(
        self, row_loads: np.ndarray, available: Sequence[int], wall: float
    ) -> Dict[int, float]:
        s = self.process.sample()
        self.history.append(s)
        return {
            n: float(row_loads[n]) / float(s[n])
            for n in available
            if row_loads[n] > 0
        }

    def state_dict(self) -> Dict:
        """JSON-able snapshot of the speed process (PCG64 RNG state +
        drift vector + draw count). A checkpoint stores this so a resumed
        run replays the SAME realized speed sequence an uninterrupted run
        would have drawn — the EWMA trajectory, and with it every plan
        decision, continues bit for bit."""
        return {
            "rng": self.process._rng.bit_generator.state,
            "drift": [float(v) for v in self.process._drift],
            "draws": len(self.history),
        }

    def load_state(self, state: Dict) -> None:
        """Restore :meth:`state_dict` output (history restarts empty: the
        draw count is carried in the RNG state itself)."""
        self.process._rng.bit_generator.state = state["rng"]
        self.process._drift = np.asarray(state["drift"], dtype=np.float64)


# ---------------------------------------------------------------------- #
# The runner
# ---------------------------------------------------------------------- #
@dataclass
class _CacheEntry:
    step_plan: StepPlan
    block: "object"                    # BlockPlan
    include0: np.ndarray               # no-straggler include weights
    rows: Dict[int, Set[int]]          # global rows per machine (waste accounting)
    s_plan: np.ndarray                 # estimator state the plan was built under
    block_loads: np.ndarray            # (N,) tile-unit loads derived from blocks
    dev: Tuple                         # (slot, off, goff, include0, n_blocks) on device
    stragglers: int                    # tolerance S the plan was compiled under
    dev_valid: "object"                # (N, B) float32 real-block mask on device


class ElasticRunner:
    """Executes one workload's steps across an elastic availability trace.

    Build once per (matrix, placement); then per step optionally apply an
    :class:`ElasticEvent` and call :meth:`step`. All jax state (mesh,
    executor, staged matrix) is constructed in ``__init__`` and never
    rebuilt.

    ``workload`` supplies the per-block compute and the verification
    reference (default: plain matvec, the legacy behavior); ``policy``
    configures the scheduler (default: a Policy carrying the cfg's
    ``stragglers``/``gamma``, preserving the legacy kwargs).
    """

    def __init__(
        self,
        x: np.ndarray,
        placement: Placement,
        cfg: RunnerConfig = RunnerConfig(),
        initial_speeds: Optional[Sequence[float]] = None,
        clock=None,
        mesh=None,
        worker_axis: str = "data",
        workload=None,
        policy=None,
    ):
        import jax

        from repro.launch.mesh import make_worker_mesh

        from .executor import (
            make_fused_executor,
            make_matvec_executor,
            make_worker_executor,
            stage_matrix,
            worker_sharding,
        )

        if workload is None:
            from repro.api.workload import MatVec

            workload = MatVec()
        if policy is None:
            from repro.api.policy import Policy

            policy = Policy(stragglers=cfg.stragglers, gamma=cfg.gamma,
                            replan=cfg.replan)
        self.workload = workload
        self.policy = policy
        self.cfg = cfg
        self.placement = placement
        N, G = placement.n_machines, placement.n_tiles
        q, _ = x.shape
        if q % G:
            raise ValueError(f"X has {q} rows, not a multiple of G={G} tiles")
        self.rows_per_tile = q // G
        if self.rows_per_tile % cfg.block_rows:
            raise ValueError(
                f"block_rows={cfg.block_rows} must divide rows_per_tile="
                f"{self.rows_per_tile}"
            )
        self.rows_total = q
        s0 = (
            np.ones(N) if initial_speeds is None
            else np.asarray(initial_speeds, dtype=np.float64)
        )
        # Clocks and callers speak rows/second; the EWMA's measurements
        # arrive in tile-units/second (the LP's unit: block_loads / wall).
        # Seed the estimator in the measurement unit, or partially-measured
        # memberships mix scales — a measured worker converges to tile-unit
        # magnitude while an unmeasured one keeps its rows/s seed, and the
        # phantom relative drift forces spurious re-plans (the
        # device-vs-simulate plan divergence). The LP itself is
        # scale-invariant, so step-0 plans keep their ratios.
        self.scheduler = policy.make_scheduler(
            placement,
            rows_per_tile=self.rows_per_tile,
            initial_speeds=s0 / self.rows_per_tile,
            row_align=cfg.block_rows,
            kind="central",
        )
        # The PLANNING MASTER is what the live path (plan adoption, drift
        # gate, neighbor precompile, EWMA ingest) actually consults. In
        # central mode it IS the Algorithm-1 scheduler above; in decentral
        # mode it is one worker's replica of the pure local rule + plan
        # table (every worker holding the same replicated state would
        # evaluate identical bits), and the central scheduler becomes a
        # cold standby that kill_scheduler() can remove without stopping
        # the run.
        self.replan_mode = (
            "decentral"
            if "decentral" in (cfg.replan, getattr(policy, "replan", "central"))
            else "central"
        )
        if self.replan_mode == "decentral":
            self._master = policy.make_scheduler(
                placement,
                rows_per_tile=self.rows_per_tile,
                initial_speeds=s0 / self.rows_per_tile,
                row_align=cfg.block_rows,
                kind="decentral",
            )
        else:
            self._master = self.scheduler
        self.scheduler_killed = False
        self.clock = clock if clock is not None else HostSharedClock()
        # Static block capacity: a worker never computes more rows than it
        # stores (segments of one tile are disjoint), so stored-tiles *
        # rows_per_tile / block_rows bounds its trip count for EVERY
        # membership — one (N, B) shape for the whole run.
        z = placement.storage_sets()
        self.b_max = max(len(zn) for zn in z) * (self.rows_per_tile // cfg.block_rows)

        self._staged = stage_matrix(x, placement, self.rows_per_tile)
        self.mesh = mesh if mesh is not None else make_worker_mesh(N)
        self.worker_axis = worker_axis
        seg_fn = None
        if cfg.segmented is not None:
            seg_mode = None if cfg.segmented == "auto" else cfg.segmented
            seg_fn = workload.segmented_fn(seg_mode,
                                           block_rows=cfg.block_rows)
        self._executor = make_matvec_executor(
            self.mesh, worker_axis, rows_total=q, block_rows=cfg.block_rows,
            matmul=workload.executor_fn(cfg.matmul_mode),
            out_cols=workload.out_cols,
            segmented_fn=seg_fn,
        )
        # First-arrival mode computes per-worker partials (no psum), each
        # on its own worker's device; ONE compiled program serves every
        # worker (the jit-cache-of-1 invariant holds).
        self._worker_exec = None
        if cfg.arrival == "first":
            self._worker_exec = make_worker_executor(
                self.mesh, worker_axis, rows_total=q,
                block_rows=cfg.block_rows,
                matmul=workload.executor_fn(cfg.matmul_mode),
                out_cols=workload.out_cols,
                segmented_fn=seg_fn,
            )
        # The fused window driver shares the stepwise per-worker body; the
        # workload's fused_update is the in-graph iterate step. None means
        # the workload cannot fuse (host-side consume with no device twin):
        # callers fall back to stepwise dispatch.
        self._fused = None
        self.fuse_supported = True
        if cfg.fuse_steps > 1:
            upd = workload.fused_update(cfg.matmul_mode)
            if upd is None:
                self.fuse_supported = False
            else:
                self._fused = make_fused_executor(
                    self.mesh, worker_axis, rows_total=q,
                    block_rows=cfg.block_rows, fuse_steps=cfg.fuse_steps,
                    matmul=workload.executor_fn(cfg.matmul_mode),
                    out_cols=workload.out_cols, update=upd,
                    segmented_fn=seg_fn,
                )
        # Placement: worker n's staged tiles and plan rows live on worker
        # n's device (the layout the shard_map in_specs ask for, so no
        # dispatch reshards); operands and masks are replicated. The fused
        # carry is replicated too, so a host-provided first operand matches
        # the carry a window returns — otherwise the second dispatch would
        # recompile on the sharding change.
        from jax.sharding import NamedSharding, PartitionSpec

        self._jax = jax
        self._by_worker = worker_sharding(self.mesh, worker_axis)
        self._by_step_worker = worker_sharding(self.mesh, worker_axis, lead=1)
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        self._staged_dev = self._put_workers(self._staged.staged)

        # With an explicit prior we trust its ratios; with the all-ones
        # default a never-measured machine carries no information, so it is
        # pinned at the measured fleet's geometric mean until it reports
        # (see step()) — otherwise the unit placeholder would make e.g. a
        # freshly arrived machine look arbitrarily slow next to machines
        # whose estimates already converged to the measurement scale.
        self._speed_seeded = initial_speeds is not None
        self._measured_ever: Set[int] = set()
        self._x64 = x.astype(np.float64) if cfg.verify else None
        self._plan_cache: "OrderedDict[Tuple[int, ...], _CacheEntry]" = OrderedDict()
        self._membership: Tuple[int, ...] = tuple(range(N))
        self._current: Optional[_CacheEntry] = None
        self._operand_shape: Tuple[int, ...] = ()   # last dispatched operand
        self._pending_loads: Dict[int, float] = {}
        self._pending_durations: Dict[int, float] = {}
        self._step = 0
        # Device-staged plan stacks of recent fused windows, keyed by the
        # window's entry sequence (identity): revisited window signatures —
        # the steady state, but also the churn/steady alternation of a
        # bursty trace — reuse them without re-stacking or re-uploading.
        # Holding the entries in the key keeps their ids stable.
        self._window_dev: "OrderedDict[Tuple[int, ...], Tuple[Tuple, Tuple]]" \
            = OrderedDict()
        self._window_dev_cap = 8
        self.device_dispatches = 0    # executor calls (windows count as 1,
                                      # first-arrival counts each worker)
        self.churn_events = 0
        self.plans_compiled = 0       # every solve+compile, incl. speculative
        self.plans_precompiled = 0    # ... of which were neighbor precompiles
        self.plans_evicted = 0        # LRU evictions from the plan cache
        self.cache_hits = 0
        self.probe_solves = 0         # drift-gate c* pricing solves
        self.precompile_s = 0.0       # host time spent off the critical path
        self.total_waste = 0
        # Wall estimate for assembly-time clock draws in fused first-arrival
        # windows (realized sets must be known before dispatch). Clocks that
        # matter for reproducibility (SyntheticSpeedClock) ignore the wall.
        self._last_step_wall = 1.0
        # Per-window completion observers: each callback receives the list
        # of StepReports a dispatch produced, after the results are fetched
        # and verified but before control returns to the caller. The
        # serving layer's metrics ride this; callbacks must not raise and
        # must not mutate the reports.
        self._completion_callbacks: List = []
        # Unannounced-failure seams (repro.faults): the injector is
        # consulted at each step's head; pending_demotions collects workers
        # whose covered crash was masked this step — the caller (engine /
        # server) turns them into a synthesized preemption event before the
        # next step. Uncovered faults never get this far: the step raises
        # FaultAbort pre-dispatch with the demotion set on the exception.
        self.fault_injector = None
        self.pending_demotions: Set[int] = set()
        # Silent-corruption defense (cfg.verify_results): staged-tile
        # fingerprints + Freivalds sketch products, built from the SAME
        # host bits the executor staged — a clean run can never disagree
        # with its own checker. Sketches only apply to linear workloads
        # (y = X @ w); tile auditing is workload-agnostic.
        self._integrity = None
        if cfg.verify_results != "off":
            from repro.faults.integrity import IntegrityChecker

            self._integrity = IntegrityChecker(
                x,
                staged=self._staged.staged,
                slot_of=self._staged.slot_of,
                holders=placement.holders,
                block_rows=cfg.block_rows,
                linear=getattr(workload, "linear", False),
                exact=(cfg.verify == "exact"),
            )
        # Injected-but-undetected corruption specs by worker: consumed at
        # the injection seam, recorded when (if) the defense catches them.
        self._live_tile_specs: Dict[int, object] = {}
        self._live_result_specs: Dict[int, object] = {}
        self.integrity = {
            "restaged": 0,
            "quarantined": 0,
            "repaired_rows": 0,
            "graylist_events": 0,
        }

    def _put_workers(self, a: np.ndarray):
        """Place an array whose leading axis is the worker axis: worker
        n's slice goes to worker n's device."""
        return self._jax.device_put(a, self._by_worker)

    def _put_replicated(self, a):
        return self._jax.device_put(a, self._replicated)

    def integrity_snapshot(self) -> Dict[str, int]:
        """Integrity counters: runner-side recovery counts plus the
        checker's check/failure/audit totals (zeros when off)."""
        out = dict(self.integrity)
        if self._integrity is not None:
            out.update(self._integrity.counters())
        else:
            out.update({"checks": 0, "sketch_failures": 0,
                        "tile_audits": 0})
        return out

    def add_completion_callback(self, cb) -> None:
        """Register ``cb(reports: List[StepReport])`` to fire once per
        dispatch — with ``[report]`` on the stepwise/first-arrival paths,
        with the window's per-active-step report list on the fused path.
        Observers see every executed step exactly once, in step order."""
        self._completion_callbacks.append(cb)

    def remove_completion_callback(self, cb) -> None:
        self._completion_callbacks.remove(cb)

    def _notify_completion(self, reports) -> None:
        for cb in self._completion_callbacks:
            cb(reports)

    # ------------------------------------------------------------------ #
    @property
    def membership(self) -> Tuple[int, ...]:
        return self._membership

    @property
    def current_plan(self):
        """The :class:`~repro.core.plan.CompiledPlan` of the last executed
        step (None before the first step) — benchmarks cross-check it
        against the analytical simulator."""
        return None if self._current is None else self._current.step_plan.plan

    @property
    def planning_master(self):
        """The object the live path consults for every planning decision:
        the central :class:`USECScheduler` in ``replan="central"`` mode,
        a :class:`~repro.core.decentral.DecentralPlanner` replica in
        ``replan="decentral"`` mode. Telemetry (effective S, speed
        estimates) must read THIS, not :attr:`scheduler` — after a
        :meth:`kill_scheduler` the latter is a tombstone."""
        return self._master

    def kill_scheduler(self, reason: str = "fault injection") -> None:
        """Kill the central scheduler mid-run (fault injection).

        :attr:`scheduler` is replaced by a tombstone whose every attribute
        access raises :class:`~repro.core.decentral.SchedulerKilledError`.
        In ``replan="central"`` mode the planning master IS the scheduler,
        so the very next planning decision (plan adoption, drift probe,
        EWMA ingest) fails loudly. In ``replan="decentral"`` mode the live
        path never touches the master — the run continues on the
        replicated rule/table, bitwise-identical to an uninterrupted run,
        and the jit cache is untouched."""
        from repro.core.decentral import DeadScheduler

        dead = DeadScheduler(reason)
        if self._master is self.scheduler:
            self._master = dead
        self.scheduler = dead
        self.scheduler_killed = True

    def set_stragglers(self, stragglers: int) -> None:
        """Re-commit the straggler tolerance S mid-run (the serving
        layer's degraded shed mode rides this). Mirrors what
        ``select_straggler_tolerance(commit=True)`` does to the masters:
        ``t_max`` re-derives unless it was pinned explicitly, and every
        memoized plan compiled under the old S is evicted lazily by the
        stale-S gate in :meth:`_plan_for` / :meth:`plan_is_ready` (plan
        stamps carry S, so the decentral table self-invalidates too)."""
        s = int(stragglers)
        if s < 0:
            raise ValueError(f"stragglers must be >= 0, got {s}")
        targets = [self._master]
        if not self.scheduler_killed and self.scheduler is not self._master:
            targets.append(self.scheduler)
        for m in targets:
            if m.stragglers == s:
                continue
            m.stragglers = s
            if not m._t_max_explicit:
                m.t_max = m._derive_t_max()

    def invalidate_plan_state(self) -> int:
        """Drop every replicated planning artifact (the
        ``stale_plan_table`` fault): the memoized plan cache, the fused
        window's device stacks, and — in decentral mode — the replicated
        :class:`~repro.core.decentral.PlanTable`. Plans are a pure
        function of (membership, speed snapshot, S), so the next step
        re-solves and produces the same bits; the cost is one replan, not
        a recompile of the executor. Returns the number of decentral
        table entries dropped (0 in central mode)."""
        self._plan_cache.clear()
        self._window_dev.clear()
        n = 0
        table = getattr(self._master, "table", None)
        if table is not None:
            n = len(table)
            table.clear()
        return n

    @property
    def staged_device(self):
        """The staged tiles as placed on the devices: (N, T, rows_per_tile,
        r), sharded so worker n's tiles live on worker n's device only."""
        return self._staged_dev

    def lowered_step_text(self) -> str:
        """StableHLO of the step program this runner dispatches (the fused
        window, the first-arrival partials or the barrier step), lowered
        against the current plan's placed arrays without compiling — so a
        caller can see which kernels and collectives a step runs. Needs
        one executed step."""
        if self._current is None:
            raise RuntimeError("lowered_step_text needs one executed step")
        w = self._put_replicated(np.zeros(self._operand_shape, np.float32))
        if self._fused is not None and self._window_dev:
            K, N = self.cfg.fuse_steps, self.placement.n_machines
            stacks = next(reversed(self._window_dev.values()))[1]
            fn, args = self._fused, (
                self._staged_dev, *stacks,
                self._put_replicated(np.zeros((K, N), bool)),
                self._put_replicated(np.zeros((K,), bool)), w)
        else:
            fn = self._worker_exec or self._executor
            args = (self._staged_dev, *self._current.dev, w)
        return fn.lower(*args).as_text()

    @property
    def executor_cache_size(self) -> int:
        """Compiled-program count across the step drivers (expected: 1
        forever — a fused run compiles only the window driver, a stepwise
        run only the per-step executor, a first-arrival run only the
        per-worker partial; churn and worker identity are data either
        way)."""
        fs = [f for f in (self._executor, self._fused, self._worker_exec)
              if f is not None]
        if not all(hasattr(f, "_cache_size") for f in fs):
            return -1
        return int(sum(f._cache_size() for f in fs))

    def apply_event(self, ev: ElasticEvent) -> None:
        """Adopt the event's availability set (validates tile reachability)."""
        avail = tuple(sorted(ev.available))
        if not avail:
            # Let restrict() raise the canonical LostTileError with context.
            self.placement.restrict(avail)
        if ev.is_churn:
            self.churn_events += 1
        if avail != self._membership:
            self.placement.restrict(avail)   # raises LostTileError on data loss
            self._membership = avail

    # ------------------------------------------------------------------ #
    def _store_entry(self, avail: Tuple[int, ...], splan: StepPlan,
                     s_plan: np.ndarray) -> _CacheEntry:
        """Build a cache entry from a planned step: expand blocks, account
        rows (waste bookkeeping), stage the plan arrays on device, insert
        into the LRU cache. This is the whole per-plan host cost; once an
        entry exists, adopting it is an O(1) array swap.

        Exception safety: every fallible operation — the block expansion,
        the row accounting, every device upload — completes BEFORE the
        cache insert below, which is the commit point. A raise anywhere in
        the build leaves the cache exactly as it was: no key ever maps to
        a half-built entry whose device arrays don't exist (it would serve
        a partial plan on its next hit). Tested by the fault-injected
        regression in ``tests/test_faults.py``."""
        from .executor import block_plan

        bp = block_plan(
            splan.plan, self._staged.slot_of, self.cfg.block_rows,
            b_max=self.b_max,
        )
        rows = {n: splan.plan.rows_of(n) for n in range(self.placement.n_machines)}
        block_loads = (
            bp.n_blocks.astype(np.float64) * self.cfg.block_rows / self.rows_per_tile
        )
        # Plan arrays live on device with the cache entry: a cache hit (or a
        # no-straggler step) uploads nothing, so the measured step wall time
        # is executor time, not host->device transfer.
        put = self._put_workers
        dev = (
            put(bp.blk_slot), put(bp.blk_off), put(bp.blk_goff),
            put(bp.blk_include), put(bp.n_blocks),
        )
        entry = _CacheEntry(
            step_plan=splan, block=bp, include0=bp.blk_include.copy(),
            rows=rows, s_plan=s_plan, block_loads=block_loads, dev=dev,
            stragglers=int(splan.plan.stragglers),
            dev_valid=put((bp.blk_seg_t >= 0).astype(np.float32)),
        )
        # ---- commit point: nothing below can raise on a built entry ----
        self._plan_cache[avail] = entry
        self._plan_cache.move_to_end(avail)
        self.plans_compiled += 1
        cap = self.cfg.plan_cache_size
        if cap is not None:
            while len(self._plan_cache) > max(int(cap), 1):
                # Evict least-recently-used, but never the live membership.
                for key in self._plan_cache:
                    if key != self._membership:
                        del self._plan_cache[key]
                        self.plans_evicted += 1
                        break
                else:  # pragma: no cover - cache holds only the live entry
                    break
        return entry

    def _plan_drift(self, entry: _CacheEntry, avail: Tuple[int, ...],
                    s_hat: np.ndarray) -> float:
        """Relative speed drift between the current estimates and the
        snapshot a memoized plan was built under. The assignment LP is
        scale-invariant, so only *relative* drift can make a plan stale —
        compare the mean-normalized vectors (the EWMA's absolute scale is
        tile-units per wall-second and moves a lot while the ratios stay
        put). Shared by :meth:`_plan_for` and :meth:`plan_is_ready` so the
        adoption gate and the window assembler's flush rule cannot
        diverge."""
        idx = np.asarray(avail, dtype=np.int64)
        a = s_hat[idx] / s_hat[idx].mean()
        b = entry.s_plan[idx] / entry.s_plan[idx].mean()
        return float(np.max(np.abs(a / b - 1.0)))

    def _plan_for(self, avail: Tuple[int, ...]) -> Tuple[_CacheEntry, bool]:
        """Memoized planning: returns (entry, cache_hit)."""
        master = self._master
        s_hat = master.speeds
        entry = self._plan_cache.get(avail)
        if entry is not None and entry.stragglers != master.stragglers:
            # A mid-run select_straggler_tolerance(commit=True) changed S:
            # a plan compiled under the old tolerance has the wrong segment
            # redundancy and must never be served again — evict, recompile.
            del self._plan_cache[avail]
            entry = None
        if entry is not None:
            self._plan_cache.move_to_end(avail)
            if master.homogeneous:
                # Homogeneous planning ignores the EWMA (all-ones speeds),
                # so estimator drift cannot stale a memoized plan — the
                # drift gate and its probe solve are pure overhead here.
                self.cache_hits += 1
                return entry, True
            drift = self._plan_drift(entry, avail, s_hat)
            if drift <= self.cfg.speed_tolerance:
                self.cache_hits += 1
                return entry, True
            # Drift past tolerance: price the re-plan before paying for it.
            # One cheap non-lexicographic solve gives the fresh optimum; if
            # the memoized plan is still within (1 + tol) of it, swapping
            # plans would move rows (transition waste) for almost no c*
            # gain — keep the plan and re-baseline its speed snapshot.
            # (This is what kept the device backend compiling one plan more
            # than the simulate backend on the same trace: estimator noise
            # alone forced a re-solve, and the near-identical fresh plan
            # still shuffled integerized rows.)
            # (The probe is a throwaway non-lexicographic solve: when the
            # gate does decide to re-plan, plan_step solves again with its
            # own lexicographic settings so every adopted plan is exactly
            # what on-demand planning would have produced. The duplicate
            # ~1ms solve only occurs on genuine-drift steps.)
            c_new = master.probe_c_star(avail)
            self.probe_solves += 1
            old_c = entry.step_plan.solution.time_of(master.plan_speeds)
            if old_c <= (1.0 + self.cfg.speed_tolerance) * c_new + 1e-12:
                entry.s_plan = s_hat
                self.cache_hits += 1
                return entry, True
        splan = master.plan_step(avail)
        entry = self._store_entry(avail, splan, s_hat)
        return entry, False

    def _adopt_plan(self) -> Tuple[_CacheEntry, bool, bool, int]:
        """Plan the current membership and account the transition. Returns
        ``(entry, cache_hit, replanned, waste)``. The ONE definition of
        plan adoption + transition-waste accounting, shared by
        :meth:`step` and :meth:`step_window` so the two drivers' telemetry
        cannot diverge."""
        prev = self._current
        entry, cache_hit = self._plan_for(self._membership)
        replanned = prev is None or entry is not prev
        waste = 0
        if replanned and prev is not None:
            preempted = [
                n for n in range(self.placement.n_machines)
                if n not in set(self._membership)
            ]
            waste = transition_waste(prev.rows, entry.rows, preempted)
            self.total_waste += waste
        self._current = entry
        return entry, cache_hit, replanned, waste

    def _precompile_neighbors(self, avail: Tuple[int, ...]) -> int:
        """Speculatively compile all single-preemption/arrival neighbors of
        ``avail`` in one batched solve+compile, so the next churn event hits
        the plan cache. Runs off the step critical path (after the step's
        result is already out); infeasible neighbors (a lost tile, or fewer
        than 1+S holders) are skipped. Returns the number of plans added."""
        N = self.placement.n_machines
        S = self._master.stragglers
        cur = set(avail)
        cand: List[Tuple[int, ...]] = [
            tuple(x for x in avail if x != n) for n in avail if len(avail) > 1
        ]
        cand += [
            tuple(sorted(cur | {n})) for n in range(N) if n not in cur
        ]
        todo = []
        for nb in cand:
            if nb in self._plan_cache or nb in todo:
                continue
            try:
                restricted = self.placement.restrict(nb)
            except LostTileError:
                continue
            if restricted.replication < 1 + S:
                continue
            todo.append(nb)
        cap = self.cfg.plan_cache_size
        if cap is not None:
            # Never speculate past the LRU budget: plans that would evict
            # existing entries (or each other) before they can be hit are
            # pure waste. Under memory pressure, speculation simply stops.
            budget = max(int(cap), 1) - len(self._plan_cache)
            if budget <= 0:
                return 0
            todo = todo[:budget]
        if not todo:
            return 0
        s_hat = self._master.speeds
        try:
            splans = self._master.plan_batch(todo)
        except Exception:
            # Speculation must never take down a live run: a neighbor whose
            # LP/filling hits a numerical edge is simply not cached (it will
            # be solved on demand — and raise there — only if actually
            # visited).
            return 0
        stored = 0
        for nb, splan in zip(todo, splans):
            try:
                self._store_entry(nb, splan, s_hat)
            except Exception:
                # Same contract as the batch solve above: a neighbor whose
                # block expansion or device upload fails is simply not
                # cached — the live step that triggered the speculation
                # must not die for it. _store_entry leaves nothing partial
                # behind (the cache insert is its commit point), so the
                # remaining neighbors still store cleanly.
                continue
            self.plans_precompiled += 1
            stored += 1
        return stored

    def _check_straggler_ids(self, stragglers: Sequence[int]) -> None:
        """Reject out-of-range straggler ids in EVERY driver. Historically
        the stepwise path passed them through (a phantom id was a silent
        no-op in ``include_mask``) while the fused window filtered them
        before building its bitmask — the same typo behaved differently
        per driver. Both now land here."""
        N = self.placement.n_machines
        for s in stragglers:
            if not 0 <= int(s) < N:
                raise ValueError(
                    f"straggler id {int(s)} out of range: machine ids are "
                    f"0..{N - 1}")

    # ------------------------------------------------------------------ #
    # Unannounced-failure seams (repro.faults). Faults are consulted and
    # consumed at each step's head; a fault the S budget cannot absorb
    # raises FaultAbort BEFORE any state-mutating dispatch, so the caller's
    # operand/carry stays valid and the step can re-execute after a replan.
    # ------------------------------------------------------------------ #
    def _consult_planning_faults(self, t: int) -> None:
        """Fire planning-path faults scheduled at absolute step ``t``:
        ``scheduler_kill`` tombstones the central master (the decentral
        replica keeps the run alive), ``stale_plan_table`` drops every
        replicated planning artifact. Both are consumed one-shot."""
        inj = self.fault_injector
        if inj is None:
            return
        from repro.faults.chaos import PLANNING_KINDS

        for spec in inj.take(t, kinds=PLANNING_KINDS):
            if spec.kind == "scheduler_kill":
                if self.scheduler_killed:
                    inj.record(spec, "noop", "scheduler already dead")
                else:
                    self.kill_scheduler(
                        f"chaos: scheduler_kill before step {t}")
                    inj.record(
                        spec, "killed",
                        f"central master tombstoned before step {t}")
            else:  # stale_plan_table
                n_plans = len(self._plan_cache)
                n_table = self.invalidate_plan_state()
                detail = f"dropped {n_plans} cached plan(s)"
                if n_table:
                    detail += f" + {n_table} table entr(ies)"
                inj.record(spec, "invalidated", detail)

    def _take_dispatch_faults(self, t: int):
        """Consume the dispatch faults (crash / result drop) scheduled at
        absolute step ``t``; a target outside the membership is a recorded
        noop (it is already gone). Returns ``[(spec, worker), ...]``."""
        inj = self.fault_injector
        if inj is None:
            return []
        from repro.faults.chaos import DISPATCH_KINDS

        out = []
        for spec in inj.take(t, kinds=DISPATCH_KINDS):
            n = int(spec.worker)
            if n not in self._membership:
                inj.record(spec, "noop",
                           f"worker {n} not in the membership")
                continue
            out.append((spec, n))
        return out

    def _coverable(self, entry: _CacheEntry, bad: Set[int]) -> bool:
        """Can this step proceed with every worker in ``bad`` silent? True
        when the plan's S budget covers the set (include_mask finds a
        surviving copy of every segment) AND at least one loaded worker
        remains to be consumed."""
        if not bad:
            return True
        if len(bad) > entry.stragglers:
            return False
        loaded = [n for n in self._membership
                  if entry.block.n_blocks[n] > 0]
        if len(set(loaded) - bad) < 1:
            return False
        try:
            entry.step_plan.plan.include_mask(tuple(sorted(bad)))
        except Exception:
            return False
        return True

    def _resolve_lost(
        self,
        t: int,
        entry: _CacheEntry,
        dfaults,
        injected: Optional[Tuple[int, ...]],
    ) -> Tuple[int, ...]:
        """Classify this step's dispatch faults against the S budget.

        Covered: the lost workers become realized stragglers — the fault
        is *masked* (and a crash queues its demotion for the caller).
        Not covered: record the demotions and raise :class:`FaultAbort`
        before anything dispatches — the caller demotes, replans, and
        re-executes this step. Returns the loaded lost set to mask."""
        from repro.faults.chaos import FaultAbort

        inj = self.fault_injector
        loaded = {n for n in self._membership
                  if entry.block.n_blocks[n] > 0}
        lost = tuple(sorted({n for _, n in dfaults if n in loaded}))
        bad_all = set(injected or ()) | set(lost)
        if self._coverable(entry, bad_all):
            for spec, n in dfaults:
                if n not in loaded:
                    inj.record(spec, "noop",
                               f"worker {n} holds no rows this step")
                    continue
                inj.record(
                    spec, "masked",
                    f"step {t}: silent worker {n} covered by S="
                    f"{entry.stragglers}; realized straggler")
                if spec.kind == "worker_crash":
                    self.pending_demotions.add(n)
            return lost
        demote = tuple(sorted({n for _, n in dfaults}))
        for spec, n in dfaults:
            inj.record(
                spec, "demoted",
                f"step {t}: loss of worker {n} exceeds S="
                f"{entry.stragglers}; abort, demote, replan, re-execute")
        raise FaultAbort(
            t, dfaults[0][0].kind, lost=lost, demote=demote,
            detail=f"S={entry.stragglers} cannot cover {sorted(bad_all)}")

    def _take_speed_loss(self, t: int) -> bool:
        """Fire a scheduled ``speed_report_loss`` at absolute step ``t``:
        the step's measured durations never reach the master, so its EWMA
        feed is dropped by the caller. Output bits are already final —
        this only perturbs future planning inputs. Returns True when a
        loss fired (one-shot)."""
        inj = self.fault_injector
        if inj is None:
            return False
        fired = False
        for spec in inj.take(t, kinds=("speed_report_loss",)):
            inj.record(
                spec, "report_dropped",
                f"step {t}: measured durations lost in transit; "
                f"EWMA update skipped")
            fired = True
        return fired

    def _timeout_check(
        self,
        t: int,
        entry: _CacheEntry,
        durations: Dict[int, float],
        already_bad: Set[int],
    ) -> Tuple[int, ...]:
        """Apply ``cfg.dispatch_timeout`` to modeled durations: workers
        past the deadline are silent as far as this step's master is
        concerned. Covered → returned (to mask as realized stragglers and
        censor from the EWMA). Not covered → FaultAbort with the timed-out
        set demoted (a worker this late is treated as dead)."""
        timeout = self.cfg.dispatch_timeout
        if timeout is None:
            return ()
        timed = tuple(sorted(
            n for n, d in durations.items()
            if d > timeout and n not in already_bad))
        if not timed:
            return ()
        if not self._coverable(entry, already_bad | set(timed)):
            from repro.faults.chaos import FaultAbort

            raise FaultAbort(
                t, "dispatch_timeout", lost=timed, demote=timed,
                detail=f"worker(s) {list(timed)} exceeded "
                       f"dispatch_timeout={timeout} beyond the S budget")
        if self.fault_injector is not None:
            from repro.faults.chaos import FaultSpec

            for n in timed:
                self.fault_injector.record(
                    FaultSpec("result_drop", max(t, 0), worker=n),
                    "masked",
                    f"step {t}: worker {n} past dispatch_timeout="
                    f"{timeout}; realized straggler",
                    detect_s=float(timeout))
        return timed

    def _derive_realized(
        self,
        durations: Dict[int, float],
        forced: Sequence[int] = (),
    ) -> Tuple[int, ...]:
        """Realized straggler set from modeled arrival order: the master
        consumes the first ``n_loaded - S`` completions, so the slowest S
        loaded workers (ties broken by id) are this step's stragglers. At
        least one worker is always consumed. ``forced`` pins workers whose
        results are already known lost (faults/timeouts) into the set —
        they spend budget first; only the remainder of S is derived from
        arrival order."""
        S = self._master.stragglers
        forced = tuple(sorted({int(n) for n in forced}))
        pool = sorted(set(durations) | set(forced))
        s_eff = min(S, max(len(pool) - 1, 0))
        extra = s_eff - len(forced)
        if extra <= 0:
            return forced
        rest = [n for n in sorted(durations) if n not in set(forced)]
        order = sorted(rest, key=lambda n: (durations[n], n))
        derived = order[len(order) - extra:]
        return tuple(sorted(set(forced) | {int(n) for n in derived}))

    def _winner_combine(
        self,
        parts: List[np.ndarray],
        loaded: List[int],
        entry: _CacheEntry,
        include: np.ndarray,
    ) -> np.ndarray:
        """Host-side first-arrival combine: gather each output row from its
        winning holder's partial. ``include`` (the ordinary refresh_include
        weights) marks exactly one surviving copy per segment, so every row
        has exactly one contributor — the gather returns the same bits the
        psum barrier would (the sum of the winner and zeros)."""
        bp = entry.block
        win = (include > 0) & (bp.blk_seg_t >= 0)
        n_idx, b_idx = np.nonzero(win)
        br = self.cfg.block_rows
        rows = (
            bp.blk_goff[n_idx, b_idx][:, None]
            + np.arange(br, dtype=np.int64)
        ).reshape(-1)
        winner = np.full(self.rows_total, -1, dtype=np.int64)
        winner[rows] = np.repeat(n_idx, br)
        if (winner < 0).any():  # pragma: no cover - plans cover every row
            missing = int(np.flatnonzero(winner < 0)[0])
            raise RuntimeError(
                f"no surviving holder delivered output row {missing}")
        pos = np.full(self.placement.n_machines, -1, dtype=np.int64)
        for i, n in enumerate(loaded):
            pos[n] = i
        stack = np.stack(parts)
        return stack[pos[winner], np.arange(self.rows_total)]

    # ------------------------------------------------------------------ #
    # Silent-corruption defense (cfg.verify_results)
    # ------------------------------------------------------------------ #
    def _verifying(self, t: int) -> bool:
        """Does ``verify_results`` check absolute step ``t``?"""
        if self._integrity is None:
            return False
        from repro.faults.integrity import should_verify

        return should_verify(self.cfg.verify_results, t)

    def _consume_tile_corruption(self, t: int) -> None:
        """Fire scheduled ``tile_corruption`` faults: flip bits in the
        target's first stored replica tile (host + device copies). The
        fault is silent — detection is the fingerprint audit's job."""
        inj = self.fault_injector
        if inj is None:
            return
        from repro.faults.integrity import corrupt_tile

        for spec in inj.take(t, kinds=("tile_corruption",)):
            n = int(spec.worker)
            stored = np.flatnonzero(self._staged.slot_of[n] >= 0)
            if n not in self._membership or stored.size == 0:
                inj.record(spec, "noop",
                           f"worker {n} stores no tiles")
                continue
            slot = int(self._staged.slot_of[n, int(stored[0])])
            corrupt_tile(self._staged.staged[n, slot])
            self._staged_dev = self._put_workers(self._staged.staged)
            self._live_tile_specs[n] = spec

    def _audit_and_restage(self, t: int) -> None:
        """Pre-dispatch tile audit: re-checksum every staged replica
        against its staging-time fingerprint. A corrupt tile is repaired
        IN PLACE from a surviving replica holder whose own copy still
        matches — the uncoded-redundancy recovery: full capacity is
        restored, the plan (and therefore the output bits) is untouched,
        and nobody is demoted. Only when no clean replica survives does
        the holder get demoted via :class:`FaultAbort`."""
        chk = self._integrity
        if chk is None or not chk.fingerprints:
            return
        mismatches = chk.audit_tiles(self._staged.staged)
        if not mismatches:
            return
        from repro.faults.chaos import FaultAbort, FaultSpec

        inj = self.fault_injector
        restaged = False
        for n, slot, g in mismatches:
            spec = self._live_tile_specs.pop(n, None) or FaultSpec(
                "tile_corruption", max(t, 0), worker=n)
            donor = chk.find_donor(
                self._staged.staged, g, n, self._membership)
            if donor is None:
                if inj is not None:
                    inj.record(
                        spec, "demoted",
                        f"step {t}: tile {g} corrupt on worker {n} with "
                        f"no clean surviving replica; demote")
                raise FaultAbort(
                    t, "tile_corruption", lost=(n,), demote=(n,),
                    detail=f"tile {g} has no clean surviving replica")
            chk.restage(self._staged.staged, n, slot, g, donor)
            restaged = True
            self.integrity["restaged"] += 1
            if inj is not None:
                inj.record(
                    spec, "restaged",
                    f"step {t}: tile {g} on worker {n} failed its "
                    f"staging fingerprint; re-staged from replica holder "
                    f"{donor} — capacity restored, plan untouched")
        if restaged:
            self._staged_dev = self._put_workers(self._staged.staged)

    def _graylist_forced(self, t: int, entry: _CacheEntry,
                         already: Set[int]) -> Set[int]:
        """Graylisted workers (repeat corruption offenders on probation)
        to force into this step's realized straggler set. Probation is
        best-effort: when the S budget cannot cover the distrusted
        worker, its (sketch-verified) result is consumed anyway."""
        chk = self._integrity
        if chk is None:
            return set()
        gray = chk.health.graylisted(t) & set(self._membership)
        gray -= set(already)
        if not gray or not self._coverable(entry, set(already) | gray):
            return set()
        return gray

    def _note_quarantine(self, t: int, workers: Set[int]) -> Set[int]:
        """Strike each corrupt worker's health ledger; returns the subset
        this strike newly graylisted."""
        gray = set()
        for n in sorted(workers):
            if self._integrity.health.strike(n, t):
                gray.add(n)
                self.integrity["graylist_events"] += 1
        return gray

    def _first_winner_row(self, entry: _CacheEntry, bad: Set[int],
                          n: int) -> Optional[int]:
        """First global output row worker ``n`` delivers under the
        current include weights (None when it wins no rows)."""
        from .executor import refresh_include

        include = refresh_include(
            entry.block, entry.step_plan.plan, tuple(sorted(bad)))
        win = (include[n] > 0) & (entry.block.blk_seg_t[n] >= 0)
        bs = np.nonzero(win)[0]
        if bs.size == 0:
            return None
        return int(entry.block.blk_goff[n, int(bs[0])])

    def _chunk_winners(self, entry: _CacheEntry, bad: Set[int],
                       chunks) -> Set[int]:
        """The workers that delivered the given ``block_rows`` row chunks
        under the current include weights — the localization step that
        turns a failed sketch into a named culprit."""
        from .executor import refresh_include

        include = refresh_include(
            entry.block, entry.step_plan.plan, tuple(sorted(bad)))
        bp = entry.block
        win = (include > 0) & (bp.blk_seg_t >= 0)
        n_idx, b_idx = np.nonzero(win)
        chunk_of = bp.blk_goff[n_idx, b_idx] // self.cfg.block_rows
        want = {int(c) for c in chunks}
        return {int(n) for n, c in zip(n_idx, chunk_of) if int(c) in want}

    def _integrity_first(
        self,
        t: int,
        entry: _CacheEntry,
        parts: List[np.ndarray],
        loaded: List[int],
        w,
        silent: Set[int],
        durations: Dict[int, float],
        injected,
    ) -> Tuple[Set[int], Dict[int, float]]:
        """First-arrival corruption seam: inject scheduled
        ``result_corruption`` into the fetched partials, then Freivalds-
        check each loaded worker's rows. A corrupt worker becomes a
        realized straggler — its rows are served by a surviving holder
        through the ordinary winner gather, its timing is censored from
        the EWMA — or, past the S budget, it is demoted via FaultAbort
        before the combine."""
        from repro.faults.chaos import FaultAbort, FaultSpec
        from repro.faults.integrity import corrupt_result

        inj = self.fault_injector
        bp = entry.block
        if inj is not None:
            for spec in inj.take(t, kinds=("result_corruption",)):
                n = int(spec.worker)
                if n not in loaded:
                    inj.record(spec, "noop",
                               f"worker {n} has no partial this step")
                    continue
                # np.asarray of a device buffer is read-only; corrupt a copy.
                i = loaded.index(n)
                p = np.array(parts[i])
                corrupt_result(p, int(bp.blk_goff[n, 0]))
                parts[i] = p
                self._live_result_specs[n] = spec
        chk = self._integrity
        if chk is None or not chk.linear or not self._verifying(t):
            return silent, durations
        br = self.cfg.block_rows
        corrupt: Set[int] = set()
        for i, n in enumerate(loaded):
            nb = int(bp.n_blocks[n])
            chunks = (bp.blk_goff[n, :nb] // br).tolist()
            if not chk.check_chunks(t, parts[i], w, chunks):
                corrupt.add(n)
        if not corrupt:
            return silent, durations
        newly_gray = self._note_quarantine(t, corrupt)
        lost = tuple(sorted(corrupt))
        if not self._coverable(
                entry, silent | corrupt | set(injected or ())):
            for n in lost:
                spec = self._live_result_specs.pop(n, None) or FaultSpec(
                    "result_corruption", max(t, 0), worker=n)
                if inj is not None:
                    inj.record(
                        spec, "demoted",
                        f"step {t}: corrupt partial from worker {n} "
                        f"exceeds S={entry.stragglers}; abort, demote, "
                        f"replan, re-execute")
            raise FaultAbort(
                t, "result_corruption", lost=lost, demote=lost,
                detail=f"S={entry.stragglers} cannot cover corrupt "
                       f"worker(s) {list(lost)}")
        self.integrity["quarantined"] += len(corrupt)
        for n in lost:
            spec = self._live_result_specs.pop(n, None) or FaultSpec(
                "result_corruption", max(t, 0), worker=n)
            if inj is not None:
                inj.record(
                    spec, "quarantined",
                    f"step {t}: worker {n}'s partial failed the "
                    f"Freivalds sketch; realized straggler, rows served "
                    f"by a surviving holder, timing censored"
                    + (", graylisted" if n in newly_gray else ""))
        return silent | corrupt, {
            n: d for n, d in durations.items() if n not in corrupt}

    def _integrity_barrier(
        self,
        t: int,
        entry: _CacheEntry,
        y: np.ndarray,
        w,
        bad: Tuple[int, ...],
        durations: Dict[int, float],
    ) -> Tuple[np.ndarray, Dict[int, float], Tuple[int, ...]]:
        """Barrier corruption seam: inject scheduled
        ``result_corruption`` into the fetched output, Freivalds-check
        it, and on failure localize the corrupt row chunks to their
        producing worker. Recovery mirrors the covered-timeout template:
        the SAME compiled executor re-dispatches with the culprit's
        copies masked out of the include weights (bit-identical output,
        jit cache untouched); past the S budget the culprit is demoted
        via FaultAbort."""
        from repro.faults.chaos import FaultAbort, FaultSpec
        from repro.faults.integrity import corrupt_result
        from .executor import refresh_include

        inj = self.fault_injector
        bad_set = set(bad)
        if inj is not None:
            for spec in inj.take(t, kinds=("result_corruption",)):
                n = int(spec.worker)
                row = (self._first_winner_row(entry, bad_set, n)
                       if n in self._membership else None)
                if row is None:
                    inj.record(spec, "noop",
                               f"worker {n} delivers no output rows "
                               f"this step")
                    continue
                # The fetched output may be a read-only device view.
                y = np.array(y)
                corrupt_result(y, row)
                self._live_result_specs[n] = spec
        chk = self._integrity
        if chk is None or not chk.linear or not self._verifying(t):
            return y, durations, tuple(sorted(bad_set))
        if chk.check_output(t, y, w):
            return y, durations, tuple(sorted(bad_set))
        bad_chunks = chk.locate(t, y, w)
        culprits = self._chunk_winners(entry, bad_set, bad_chunks)
        culprits -= bad_set
        if not culprits:
            # Defensive: a tripped sketch with no attributable producer.
            # Abort with nothing demoted — the engine's recovery loop
            # re-executes the step (the injection, being one-shot, is
            # already consumed).
            raise FaultAbort(
                t, "result_corruption", lost=(), demote=(),
                detail="sketch failure with no attributable producer")
        newly_gray = self._note_quarantine(t, culprits)
        lost = tuple(sorted(culprits))
        bad_new = bad_set | culprits
        if not self._coverable(entry, bad_new):
            for n in lost:
                spec = self._live_result_specs.pop(n, None) or FaultSpec(
                    "result_corruption", max(t, 0), worker=n)
                if inj is not None:
                    inj.record(
                        spec, "demoted",
                        f"step {t}: corrupt output rows from worker {n} "
                        f"exceed S={entry.stragglers}; abort, demote, "
                        f"replan, re-execute")
            raise FaultAbort(
                t, "result_corruption", lost=lost, demote=lost,
                detail=f"S={entry.stragglers} cannot cover corrupt "
                       f"worker(s) {list(lost)}")
        slot_d, off_d, goff_d, _inc0, nblk_d = entry.dev
        include_d = self._put_workers(refresh_include(
            entry.block, entry.step_plan.plan, tuple(sorted(bad_new))))
        y2 = self._executor(
            self._staged_dev,
            slot_d, off_d, goff_d, include_d, nblk_d,
            self._put_replicated(w),
        )
        y2.block_until_ready()
        self.device_dispatches += 1
        y = np.asarray(y2)
        durations = {n: d for n, d in durations.items()
                     if n not in culprits}
        self.integrity["quarantined"] += len(culprits)
        for n in lost:
            spec = self._live_result_specs.pop(n, None) or FaultSpec(
                "result_corruption", max(t, 0), worker=n)
            if inj is not None:
                inj.record(
                    spec, "quarantined",
                    f"step {t}: worker {n}'s output rows failed the "
                    f"Freivalds sketch; masked and re-dispatched without "
                    f"it, timing censored"
                    + (", graylisted" if n in newly_gray else ""))
        if not chk.check_output(t, y, w):  # pragma: no cover - belt
            raise FaultAbort(
                t, "result_corruption", lost=lost, demote=lost,
                detail="re-dispatched output still fails the sketch")
        return y, durations, tuple(sorted(bad_new))

    def _integrity_window(
        self,
        base: int,
        n_active: int,
        metas,
        sets,
        ys: np.ndarray,
        ws: np.ndarray,
    ) -> List[Set[int]]:
        """Fused-window corruption seam (post-fetch): inject scheduled
        ``result_corruption`` into each active step's fetched output,
        Freivalds-check each step, and repair corrupt row chunks by
        recomputing them from a surviving replica holder's staged tile
        (float64, exact on the integer grid) — the realized include is
        baked into the already-dispatched graph, and a stepwise fallback
        would break the one-compiled-program contract. The device carry
        is computed from the device partials, which the (host-side)
        corruption never touched, so subsequent windows stay clean.
        Returns the per-step quarantined sets (censored from the EWMA)."""
        from repro.faults.chaos import FaultAbort, FaultSpec
        from repro.faults.integrity import corrupt_result

        inj = self.fault_injector
        chk = self._integrity
        out: List[Set[int]] = [set() for _ in range(n_active)]
        for k in range(n_active):
            tk = base + k
            entry = metas[k][1]
            rspecs = metas[k][8]
            bad_set = set(sets[k])
            for spec in rspecs:
                n = int(spec.worker)
                row = (self._first_winner_row(entry, bad_set, n)
                       if n in metas[k][0] else None)
                if row is None:
                    if inj is not None:
                        inj.record(spec, "noop",
                                   f"worker {n} delivers no output rows "
                                   f"this step")
                    continue
                corrupt_result(ys[k], row)
                self._live_result_specs[n] = spec
            if chk is None or not chk.linear or not self._verifying(tk):
                continue
            if chk.check_output(tk, ys[k], ws[k]):
                continue
            bad_chunks = chk.locate(tk, ys[k], ws[k])
            culprits = self._chunk_winners(entry, bad_set, bad_chunks)
            culprits -= bad_set
            if not culprits:  # pragma: no cover - defensive
                raise FaultAbort(
                    tk, "result_corruption", lost=(), demote=(),
                    detail="sketch failure with no attributable producer")
            newly_gray = self._note_quarantine(tk, culprits)
            alive = set(metas[k][0]) - culprits
            for c in bad_chunks:
                owners = self._chunk_winners(entry, bad_set, [c])
                owner = sorted(owners)[0] if owners else -1
                g = (c * self.cfg.block_rows) // self.rows_per_tile
                donor = chk.find_donor(
                    self._staged.staged, g, owner, alive)
                if donor is None:
                    lost = tuple(sorted(culprits))
                    raise FaultAbort(
                        tk, "result_corruption", lost=lost, demote=lost,
                        detail=f"no clean replica holder covers tile {g}")
                fixed = chk.replica_recompute(
                    self._staged.staged, donor, c, ws[k],
                    self.rows_per_tile)
                ys[k][chk.chunk_rows(c)] = fixed.astype(ys.dtype)
                self.integrity["repaired_rows"] += self.cfg.block_rows
            self.integrity["quarantined"] += len(culprits)
            for n in sorted(culprits):
                spec = self._live_result_specs.pop(n, None) or FaultSpec(
                    "result_corruption", max(tk, 0), worker=n)
                if inj is not None:
                    inj.record(
                        spec, "quarantined",
                        f"step {tk}: worker {n}'s rows failed the "
                        f"Freivalds sketch inside a fused window; "
                        f"recomputed from a replica holder's tile, "
                        f"timing censored"
                        + (", graylisted" if n in newly_gray else ""))
            out[k] |= culprits
            if not chk.check_output(tk, ys[k], ws[k]):  # pragma: no cover
                raise RuntimeError(
                    f"step {tk}: repaired window output still fails the "
                    f"integrity sketch")
        return out

    def _step_first(
        self,
        w: np.ndarray,
        entry: _CacheEntry,
        cache_hit: bool,
        replanned: bool,
        waste: int,
        t0: float,
        injected: Optional[Tuple[int, ...]],
        lost: Tuple[int, ...] = (),
    ) -> Tuple[np.ndarray, StepReport]:
        """First-arrival step: per-worker dispatch, consume-first combine.

        Every loaded worker's partial is dispatched as its own fetchable
        device call (unmasked — arrival order is not known yet). The clock
        then models arrival order; the slowest S loaded workers become the
        realized straggler set (unless ``injected`` pins one, for tests),
        the ordinary include weights mask their copies out, and the output
        is assembled by gathering each row from its winning holder. Late
        workers are measurements, not losses: every loaded duration feeds
        the EWMA. Modeled completion is the (n_loaded - S)-th order
        statistic — the barrier's max only at S=0.

        ``lost`` (pre-classified, covered dispatch faults) are workers
        whose partial never arrives: they are not dispatched, spend the S
        budget first in the realized set, and are censored from the EWMA.
        """
        from .executor import refresh_include

        t = self._step
        slot_d, off_d, goff_d, _include0_d, _nblk_d = entry.dev
        valid_d = entry.dev_valid
        replan_s = time.perf_counter() - t0

        silent = set(lost)
        loaded = [
            n for n in self._membership
            if entry.block.n_blocks[n] > 0 and n not in silent
        ]
        # A worker that is not dispatched runs a zero trip count.
        nblk = np.zeros_like(entry.block.n_blocks)
        nblk[loaded] = entry.block.n_blocks[loaded]
        self._operand_shape = np.shape(w)
        t1 = time.perf_counter()
        parts_d = self._worker_exec(
            self._staged_dev, slot_d, off_d, goff_d, valid_d,
            self._put_workers(nblk), self._put_replicated(w),
        )
        # Each worker's partial is its own device's shard: fetch the
        # loaded workers' shards one by one, never the whole array.
        shard_of = {s.index[0].start or 0: s.data
                    for s in parts_d.addressable_shards}
        for n in loaded:
            shard_of[n].block_until_ready()
        wall = time.perf_counter() - t1
        self.device_dispatches += 1
        self._last_step_wall = wall

        row_loads = entry.block_loads * self.rows_per_tile
        # The clock still models EVERY loaded worker (the lost one was
        # assigned its rows and the speed process must keep its cadence);
        # censoring happens after the draw — the measurement never arrives.
        durations = self.clock.durations(row_loads, self._membership, wall)
        for n in silent:
            durations.pop(n, None)
        timed = self._timeout_check(
            t, entry, durations, silent | set(injected or ()))
        if timed:
            silent |= set(timed)
            for n in timed:
                durations.pop(n, None)
        parts = [np.asarray(shard_of[n])[0] for n in loaded]
        silent, durations = self._integrity_first(
            t, entry, parts, loaded, w, silent, durations, injected)
        forced = tuple(sorted(silent))
        if injected is None:
            realized = self._derive_realized(durations, forced=forced)
        else:
            realized = tuple(sorted(set(injected) | silent))
        # Host-side feasibility + winner weights: include_mask raises when a
        # segment lost every holder, exactly like the barrier path.
        include = refresh_include(
            entry.block, entry.step_plan.plan, realized)
        y = self._winner_combine(parts, loaded, entry, include)

        self._pending_loads = {
            n: float(entry.block_loads[n]) for n in durations
        }
        self._pending_durations = durations
        if self._take_speed_loss(t):
            self._pending_loads, self._pending_durations = {}, {}
        skipped = set(realized)
        consumed = [d for n, d in durations.items() if n not in skipped]
        modeled = max(consumed) if consumed else 0.0

        if self.cfg.verify:
            self._verify(y, w)

        self._step += 1
        report = StepReport(
            step=self._step,
            available=self._membership,
            replanned=replanned,
            plan_cache_hit=cache_hit,
            replan_s=replan_s,
            wall_s=wall,
            modeled_completion=modeled,
            straggled=realized,
            waste=waste,
            jit_cache_size=self.executor_cache_size,
            measured=durations,
            speeds_hat=entry.s_plan,
        )
        if self.cfg.precompile_neighbors and not cache_hit:
            t2 = time.perf_counter()
            self._precompile_neighbors(self._membership)
            self.precompile_s += time.perf_counter() - t2
        self._notify_completion([report])
        return y, report

    def step(
        self,
        w: np.ndarray,
        event: Optional[ElasticEvent] = None,
        stragglers: Optional[Sequence[int]] = None,
    ) -> Tuple[np.ndarray, StepReport]:
        """Execute one elastic step ``y = X @ w`` under the current plan.

        ``event`` (if any) is applied before planning. ``stragglers=None``
        means "no injection": under ``arrival="barrier"`` no copies are
        masked, under ``arrival="first"`` the realized straggler set is
        derived from modeled arrival order. An explicit sequence (possibly
        empty) *injects* that set in either mode — the test/replay hook.
        Masked copies are dropped from the combine (include weights),
        exactly one surviving holder per segment delivers. Raises
        ``ValueError`` on an out-of-range id and errors out if the set
        exceeds the plan's tolerance.

        With a :attr:`fault_injector` installed, faults scheduled at this
        step fire here: planning faults before the EWMA ingest, dispatch
        faults (crash / result drop) classified against the S budget —
        covered losses are masked as realized stragglers (censored from
        the EWMA), uncovered losses raise
        :class:`~repro.faults.chaos.FaultAbort` before anything
        dispatches.
        """
        from .executor import refresh_include

        if event is not None:
            self.apply_event(event)
        t = self._step
        self._consult_planning_faults(t)
        # Tile corruption fires (and is audited + re-staged) BEFORE the
        # dispatch touches the staged bits: repair is a host copy from a
        # replica holder, uniform across arrival modes.
        self._consume_tile_corruption(t)
        if self._verifying(t):
            self._audit_and_restage(t)
        t0 = time.perf_counter()
        # Feed last step's measured durations into the EWMA (Alg. 1 line 4)
        # BEFORE planning, so the plan sees the freshest estimates.
        self.ingest_pending()
        injected: Optional[Tuple[int, ...]] = None
        if stragglers is not None:
            injected = tuple(sorted({int(s) for s in stragglers}))
            self._check_straggler_ids(injected)
        lost: Tuple[int, ...] = ()
        dfaults = self._take_dispatch_faults(t)
        if dfaults:
            # Peek the plan BEFORE adoption: an uncovered fault must abort
            # with the plan/waste accounting untouched, so the re-executed
            # step replans cleanly after the caller's demotion event.
            peek, _ = self._plan_for(self._membership)
            lost = self._resolve_lost(t, peek, dfaults, injected)
        entry, cache_hit, replanned, waste = self._adopt_plan()
        gray = self._graylist_forced(
            t, entry, set(injected or ()) | set(lost))
        if gray:
            # Probation: a graylisted worker is a forced realized
            # straggler — excluded from the combine and the EWMA, plan
            # (and bits) untouched.
            lost = tuple(sorted(set(lost) | gray))
        if self.cfg.arrival == "first":
            return self._step_first(
                w, entry, cache_hit, replanned, waste, t0, injected, lost)
        bad = tuple(sorted(set(injected or ()) | set(lost)))
        slot_d, off_d, goff_d, include0_d, nblk_d = entry.dev
        include_d = (
            include0_d if not bad
            else self._put_workers(
                refresh_include(entry.block, entry.step_plan.plan, bad))
        )
        replan_s = time.perf_counter() - t0

        self._operand_shape = np.shape(w)
        t1 = time.perf_counter()
        w_dev = self._put_replicated(w)
        y = self._executor(
            self._staged_dev,
            slot_d, off_d, goff_d, include_d, nblk_d, w_dev,
        )
        y.block_until_ready()
        wall = time.perf_counter() - t1
        self.device_dispatches += 1
        self._last_step_wall = wall
        y = np.asarray(y)

        row_loads = entry.block_loads * self.rows_per_tile
        durations = self.clock.durations(row_loads, self._membership, wall)
        if lost:
            # A silent worker's duration is censored — its result never
            # arrived, so there is no measurement to feed the EWMA (a dead
            # worker must not poison the estimates it can no longer match).
            durations = {n: d for n, d in durations.items()
                         if n not in set(lost)}
        timed = self._timeout_check(t, entry, durations, set(bad))
        if timed:
            # Covered timeout: the barrier master gave up on the late
            # workers and re-collected from the survivors — one recovery
            # re-dispatch with the refreshed include weights (same bits:
            # exactly one surviving copy of every segment delivers).
            bad = tuple(sorted(set(bad) | set(timed)))
            include_d = self._put_workers(
                refresh_include(entry.block, entry.step_plan.plan, bad))
            t1b = time.perf_counter()
            y = self._executor(
                self._staged_dev,
                slot_d, off_d, goff_d, include_d, nblk_d, w_dev,
            )
            y.block_until_ready()
            wall += time.perf_counter() - t1b
            self.device_dispatches += 1
            y = np.asarray(y)
            durations = {n: d for n, d in durations.items()
                         if n not in set(timed)}
        y, durations, bad = self._integrity_barrier(
            t, entry, y, w, bad, durations)
        # The EWMA is fed tile-unit loads (the LP's unit), so estimated
        # speeds stay consistent with the planner; clocks see row units.
        self._pending_loads = {
            n: float(entry.block_loads[n]) for n in durations
        }
        self._pending_durations = durations
        if self._take_speed_loss(t):
            self._pending_loads, self._pending_durations = {}, {}
        modeled = max(durations.values()) if durations else 0.0

        if self.cfg.verify:
            self._verify(y, w)

        self._step += 1
        report = StepReport(
            step=self._step,
            available=self._membership,
            replanned=replanned,
            plan_cache_hit=cache_hit,
            replan_s=replan_s,
            wall_s=wall,
            modeled_completion=modeled,
            straggled=bad,
            waste=waste,
            jit_cache_size=self.executor_cache_size,
            measured=durations,
            speeds_hat=entry.s_plan,
        )
        if self.cfg.precompile_neighbors and not cache_hit:
            # The step's result is already computed — spend the idle tail
            # batch-compiling the churn neighborhood of the new membership
            # so the NEXT membership change is a cache hit. This is the
            # amortized cost that replaces the per-event replan miss.
            t2 = time.perf_counter()
            self._precompile_neighbors(self._membership)
            self.precompile_s += time.perf_counter() - t2
        self._notify_completion([report])
        return y, report

    def ingest_pending(self) -> None:
        """Fold any pending measured durations into the EWMA (Algorithm 1
        line 4). Idempotent; the stepwise path does this inline at the top
        of :meth:`step`. The engine calls it BEFORE assembling a fused
        window so :meth:`plan_is_ready` (the flush rule) and
        :meth:`_plan_for` (the adoption gate inside the window) judge
        drift against the same estimator state."""
        if not self._pending_durations:
            return
        self._master.report(self._pending_loads, self._pending_durations)
        self._measured_ever.update(int(n) for n in self._pending_durations)
        if not self._speed_seeded and self._measured_ever:
            est = self._master.estimator
            s = est.speeds
            known = sorted(self._measured_ever)
            anchor = float(np.exp(np.mean(np.log(s[known]))))
            for n in range(self.placement.n_machines):
                if n not in self._measured_ever:
                    est.set_speed(n, anchor)
        self._pending_loads, self._pending_durations = {}, {}

    def plan_is_ready(self, avail: Sequence[int]) -> bool:
        """True when adopting ``avail`` would be a plan-cache HIT (no
        compile on the step path). The engine's window assembler uses this
        as the flush rule: churn onto a ready membership is in-window
        data; churn onto a miss flushes the window so the assembled steps
        dispatch immediately instead of queueing behind a multi-ms solve.
        Mirrors :meth:`_plan_for` exactly —
        including the c*-pricing fallback past the drift tolerance (a
        cheap probe solve is still far cheaper than the extra dispatch a
        spurious flush would cost). No scheduler/cache state is touched;
        a drift re-baseline happens later, in ``_plan_for`` — which on a
        genuine-drift step repeats the ~1 ms probe. That duplicate solve
        is confined to churn events with past-tolerance drift, the same
        trade the scheduler's waste-averse path already makes."""
        master = self._master
        key = tuple(sorted(int(a) for a in avail))
        entry = self._plan_cache.get(key)
        if entry is None:
            return False
        if entry.stragglers != master.stragglers:
            # Stale tolerance (see _plan_for): adopting would recompile.
            return False
        if master.homogeneous:
            # Membership-only planning: drift cannot stale the entry.
            return True
        s_hat = master.speeds
        if self._plan_drift(entry, key, s_hat) <= self.cfg.speed_tolerance:
            return True
        c_new = master.probe_c_star(key)
        self.probe_solves += 1
        old_c = entry.step_plan.solution.time_of(master.plan_speeds)
        return bool(
            old_c <= (1.0 + self.cfg.speed_tolerance) * c_new + 1e-12)

    def step_window(
        self,
        w,
        straggler_sets: Sequence[Optional[Sequence[int]]] = ((),),
        events: Optional[Sequence[Optional[ElasticEvent]]] = None,
    ):
        """Execute up to ``fuse_steps`` steps in ONE device dispatch.

        A ``None`` entry in ``straggler_sets`` means "no injection" for
        that step — under ``arrival="first"`` its realized straggler set
        is derived from modeled arrival order at assembly time (and masked
        in-graph through the include gather); under ``arrival="barrier"``
        it is an empty set. Explicit sequences inject, as in :meth:`step`.

        The fused fast path. Each active step carries its OWN event,
        straggler set and (cached) plan: the per-step plan arrays are
        stacked into (K, N, B) scan inputs, so churn inside the window is
        data, not a flush — the engine only flushes early (``len(sets) <
        K``) when a step's membership is a plan-cache miss, so the steps
        already assembled dispatch immediately instead of queueing behind
        a multi-ms solve. The dispatched window is ALWAYS K steps
        (inactive tail steps have zeroed trip counts/includes and their
        outputs are discarded), so the jitted window driver compiles
        exactly once for the whole run.

        ``w`` is the iterate carry — a NumPy array on the first window, the
        device array returned by the previous window afterwards (the carry
        and the per-window plan/mask buffers are donated to the dispatch:
        successive windows rewrite the same allocations, and the caller
        must not touch a carry it has handed back). Per-step straggler sets
        become an in-graph bitmask gather, not a host mask rebuild.

        Returns ``(w_carry, ys, ws, reports)``: the next carry (device),
        the per-active-step raw outputs and consumed operands (NumPy — one
        fetch for the whole window), and one :class:`StepReport` per active
        step.

        Speed measurements are ingested ONCE per window (the per-window
        per-worker feed: window wall / active steps, in tile-units/s), so
        the EWMA and its c*-priced drift re-plan gate keep working at any
        ``fuse_steps``; while the device runs the window, the host overlaps
        the speculative neighbor precompile of the newest membership.
        """
        if self._fused is None:
            raise RuntimeError(
                "step_window needs fuse_steps > 1 and a fusable workload "
                "(workload.fused_update returned None)")
        K = self.cfg.fuse_steps
        sets = [
            None if bad is None else tuple(sorted({int(s) for s in bad}))
            for bad in straggler_sets
        ]
        n_active = len(sets)
        if not 1 <= n_active <= K:
            raise ValueError(
                f"window wants {n_active} active steps, fuse_steps={K}")
        if events is None:
            events = [None] * n_active
        if len(events) != n_active:
            raise ValueError("events and straggler_sets must align per step")
        # Feed last window's measured durations into the EWMA before any of
        # this window's planning (Alg. 1 line 4, at window rate). The
        # engine already did this before assembling the window (so its
        # plan_is_ready flush decisions see the same estimates _plan_for
        # will); the call is idempotent for direct step_window users.
        self.ingest_pending()

        N = self.placement.n_machines
        bad = np.zeros((K, N), dtype=bool)
        metas = []
        had_miss = False
        base = self._step
        for k in range(n_active):
            t0 = time.perf_counter()
            tk = base + k
            if events[k] is not None:
                self.apply_event(events[k])
            # Fault seams fire at assembly time, per step: nothing has
            # dispatched yet, so an uncovered loss aborts the WHOLE window
            # cleanly (FaultAbort) with the carry untouched — the engine
            # demotes, replans, and re-assembles from this window's head.
            self._consult_planning_faults(tk)
            # Tile corruption fires (and is audited + re-staged) at
            # assembly, BEFORE the window dispatches: the engine breaks
            # windows at fault steps, so a corrupt tile always lands at
            # a window head and the repair reaches the device copy.
            self._consume_tile_corruption(tk)
            if self._verifying(tk):
                self._audit_and_restage(tk)
            dfaults = self._take_dispatch_faults(tk)
            # Result corruption is consumed at assembly but applied (and
            # detected) post-fetch — the injection perturbs the fetched
            # host copy, as a corrupt wire transfer would.
            rspecs = (
                () if self.fault_injector is None
                else tuple(self.fault_injector.take(
                    tk, kinds=("result_corruption",)))
            )
            forced: Tuple[int, ...] = ()
            if dfaults:
                peek, _ = self._plan_for(self._membership)
                forced = self._resolve_lost(tk, peek, dfaults, sets[k])
            entry, cache_hit, replanned, waste = self._adopt_plan()
            gray = self._graylist_forced(
                tk, entry, set(forced) | set(sets[k] or ()))
            if gray:
                forced = tuple(sorted(set(forced) | gray))
            had_miss = had_miss or not cache_hit
            durs_k = None
            if sets[k] is None:
                if self.cfg.arrival == "first":
                    # Derive this step's realized stragglers at assembly
                    # time: the in-graph include gather needs the bitmask
                    # before dispatch, so the clock is sampled here (once
                    # per step, in step order — the cadence the stepwise
                    # path uses) against the previous dispatch's per-step
                    # wall as the wall estimate. Silent workers are drawn
                    # (cadence) then censored (no measurement arrives).
                    row_loads = entry.block_loads * self.rows_per_tile
                    durs_k = self.clock.durations(
                        row_loads, self._membership, self._last_step_wall)
                    for n in forced:
                        durs_k.pop(n, None)
                    timed = self._timeout_check(
                        tk, entry, durs_k, set(forced))
                    if timed:
                        forced = tuple(sorted(set(forced) | set(timed)))
                        for n in timed:
                            durs_k.pop(n, None)
                    sets[k] = self._derive_realized(durs_k, forced=forced)
                else:
                    sets[k] = tuple(forced)
            else:
                self._check_straggler_ids(sets[k])
                if forced:
                    sets[k] = tuple(sorted(set(sets[k]) | set(forced)))
            if sets[k]:
                # Host-side feasibility check (the device gather cannot
                # raise): include_mask errors out when a segment lost every
                # holder, exactly like the stepwise path.
                entry.step_plan.plan.include_mask(sets[k])
                bad[k, list(sets[k])] = True
            metas.append((self._membership, entry, replanned, cache_hit,
                          time.perf_counter() - t0, waste, durs_k, forced,
                          rspecs))
        # Pad inactive tail slots with the last entry's arrays (masked out
        # in-graph) so the window's shapes never change. The stacked plan
        # buffers are cached ON DEVICE in a small LRU keyed by the
        # window's entry sequence: revisited signatures (steady state,
        # churn/steady alternation) re-upload nothing but the small
        # mask/carry buffers — the fused analogue of the stepwise path's
        # per-entry ``_CacheEntry.dev``.
        pad_entry = metas[-1][1]
        entries = tuple([m[1] for m in metas] + [pad_entry] * (K - n_active))
        key = tuple(id(e) for e in entries)
        cached = self._window_dev.get(key)
        if cached is None:
            blocks = [e.block for e in entries]
            stacks = tuple(
                self._jax.device_put(np.stack(a), self._by_step_worker)
                for a in (
                    [b.blk_slot for b in blocks],
                    [b.blk_off for b in blocks],
                    [b.blk_goff for b in blocks],
                    [b.n_blocks for b in blocks],
                    [b.blk_prio for b in blocks],
                    [b.blk_seg_t >= 0 for b in blocks],
                )
            )
            cached = (entries, stacks)
            self._window_dev[key] = cached
            while len(self._window_dev) > self._window_dev_cap:
                self._window_dev.popitem(last=False)
        else:
            self._window_dev.move_to_end(key)
        active = np.zeros((K,), dtype=bool)
        active[:n_active] = True

        t1 = time.perf_counter()
        w_dev = (
            w if hasattr(w, "block_until_ready") else self._put_replicated(w)
        )
        self._operand_shape = tuple(w_dev.shape)
        w_carry, ys_d, ws_d = self._fused(
            self._staged_dev, *cached[1],
            self._put_replicated(bad), self._put_replicated(active), w_dev,
        )
        self.device_dispatches += 1
        # Overlap: the dispatch above is asynchronous — spend the device
        # time on the churn neighborhood's speculative compile instead of
        # blocking immediately (stepwise pays this after the fetch).
        pre_s = 0.0
        if self.cfg.precompile_neighbors and had_miss:
            t2 = time.perf_counter()
            self._precompile_neighbors(self._membership)
            pre_s = time.perf_counter() - t2
            self.precompile_s += pre_s
        ys_d.block_until_ready()
        wall = time.perf_counter() - t1
        # wall_s means "executor time" (the stepwise path measures exactly
        # that and precompiles after the fetch). On the forced-host-device
        # setups the overlapped precompile contends for the same CPU, so
        # subtract it rather than bill planning to the clock/EWMA on miss
        # windows; genuine overlap on a real accelerator only makes this
        # an under- rather than over-estimate.
        wall = max(wall - pre_s, 1e-9)
        ys = np.asarray(ys_d)[:n_active]
        ws = np.asarray(ws_d)[:n_active]
        if self._integrity is not None or self.fault_injector is not None:
            # The integrity seam injects / repairs rows in place; a device
            # fetch view is read-only, so give it a writable copy.
            ys = np.array(ys)
        quarantined = self._integrity_window(
            base, n_active, metas, sets, ys, ws)

        # Per-window per-worker times: the window wall divided over its
        # active steps is the per-step equivalent the EWMA expects — speeds
        # stay in tile-units/s, so the drift-invalidation gate keeps
        # working at any fuse_steps. Loads/durations accumulate over the
        # window's (possibly different) per-step plans and are reported as
        # ONE measurement at the next window.
        per_step_wall = wall / n_active
        self._last_step_wall = per_step_wall
        loads_sum: Dict[int, float] = {}
        dur_sum: Dict[int, float] = {}
        per_step_durs = []
        for k in range(n_active):
            entry = metas[k][1]
            durs = metas[k][6]
            forced_k = metas[k][7]
            if durs is None:
                row_loads = entry.block_loads * self.rows_per_tile
                durs = self.clock.durations(
                    row_loads, metas[k][0], per_step_wall)
                for n in forced_k:
                    # Censor silent workers (covered faults): their result
                    # — and therefore their measurement — never arrived.
                    durs.pop(n, None)
            for n in quarantined[k]:
                # Censor quarantined workers: a corrupt result's timing
                # is as untrustworthy as its payload.
                durs.pop(n, None)
            per_step_durs.append(durs)
            if self._take_speed_loss(base + k):
                # This step's report was lost in transit: its durations
                # stay out of the window's accumulated EWMA feed.
                continue
            for n, d in durs.items():
                loads_sum[n] = loads_sum.get(n, 0.0) \
                    + float(entry.block_loads[n])
                dur_sum[n] = dur_sum.get(n, 0.0) + d
        self._pending_loads = loads_sum
        self._pending_durations = dur_sum

        if self.cfg.verify:
            for k in range(n_active):
                self._verify(ys[k], ws[k])

        reports = []
        for k, (avail, entry, replanned, cache_hit, replan_s, waste, _d,
                _f, _r) in enumerate(metas):
            self._step += 1
            durs = per_step_durs[k]
            if self.cfg.arrival == "first":
                # First-arrival completion: the master stops at the last
                # CONSUMED worker — realized stragglers finish later but
                # are not waited on (their durations still feed the EWMA).
                skipped = set(sets[k])
                consumed = [d for n, d in durs.items() if n not in skipped]
            else:
                consumed = list(durs.values())
            reports.append(StepReport(
                step=self._step,
                available=avail,
                replanned=replanned,
                plan_cache_hit=cache_hit,
                replan_s=replan_s,
                wall_s=per_step_wall,
                modeled_completion=max(consumed) if consumed else 0.0,
                straggled=sets[k],
                waste=waste,
                jit_cache_size=self.executor_cache_size,
                measured=durs,
                speeds_hat=entry.s_plan,
            ))
        self._notify_completion(reports)
        return w_carry, ys, ws, reports

    def _verify(self, y: np.ndarray, w: np.ndarray) -> None:
        # The reference is the workload's business: X @ w for matvec,
        # X @ W for matmat, the NumPy row map for map-reduce.
        self.workload.verify(y, w, self._x64, mode=self.cfg.verify,
                             atol=self.cfg.allclose_atol)


# ---------------------------------------------------------------------- #
# Power-iteration driver (shared by the example and the benchmark)
# ---------------------------------------------------------------------- #
def _tree_sumsq(v, xp):
    """Sum of squares by an explicit binary tree of elementwise adds.

    ``xp`` is the array module (numpy or jax.numpy). Library reductions
    (``np.linalg.norm``, ``jnp.sum``) choose their own accumulation order —
    pairwise in NumPy, backend-dependent in XLA — so a host value and its
    device twin can disagree in the last ulp. This reduction pins the order:
    square, zero-pad to a power of two, halve by adding strided slices.
    Every step is an elementwise IEEE op, so NumPy and jax produce the SAME
    bits — the foundation of the fused window's bitwise parity with the
    stepwise host path (see :func:`quantize_unit` and
    :meth:`repro.api.workload.MatVecPowerIteration.fused_update`).
    """
    s = v * v
    n = 1
    while n < s.shape[0]:
        n *= 2
    if n != s.shape[0]:
        s = xp.concatenate([s, xp.zeros(n - s.shape[0], s.dtype)])
    while s.shape[0] > 1:
        s = s[0::2] + s[1::2]
    return s[0]


def _bits(x, xp):
    """float32 -> int32 bit pattern (numpy or jax)."""
    if xp is np:
        return np.asarray(x, np.float32).view(np.int32)
    import jax

    return jax.lax.bitcast_convert_type(x.astype(xp.float32), xp.int32)


def _float(b, xp):
    """int32 bit pattern -> float32 (numpy or jax)."""
    if xp is np:
        return np.asarray(b, np.int32).view(np.float32)
    import jax

    return jax.lax.bitcast_convert_type(b, xp.float32)


def _rn_sqrt(s, xp):
    """Correctly rounded float32 ``sqrt`` of a positive normal scalar, by
    integer digit-by-digit square root.

    A TPU's float32 ``sqrt`` and divide are not correctly rounded (they
    refine a hardware estimate), so a device normalize built on them
    disagrees with NumPy in the last ulp. This routine and
    :func:`_rn_div` use only int32 shifts, adds and compares — exact on
    every backend — and return the IEEE round-to-nearest-even result, the
    same bits as ``np.sqrt``."""
    b = _bits(s, xp)
    e = (b >> 23) & 0xFF
    m = (b & 0x7FFFFF) | 0x800000               # s = m * 2^(e - 150)
    odd = (e & 1) == 1                          # e - 150 odd
    m = xp.where(odd, m << 1, m << 2)           # m in [2^24, 2^26)
    f = xp.where(odd, e - 151, e - 152)         # s = m * 2^f, f even
    # root = isqrt(m * 2^24), 25 bits: 24 significant + 1 guard.
    root = xp.zeros_like(m)
    rem = xp.zeros_like(m)
    for i in range(25):
        pair = (m >> (24 - 2 * i)) & 3 if i < 13 else 0
        rem = (rem << 2) | pair
        trial = (root << 2) | 1
        take = rem >= trial
        rem = xp.where(take, rem - trial, rem)
        root = (root << 1) | take.astype(root.dtype)
    mant = root >> 1
    up = ((root & 1) == 1) & ((rem != 0) | ((mant & 1) == 1))
    mant = mant + up.astype(mant.dtype)
    exp = 139 + (f >> 1)
    carry = mant >> 24                          # rounded up to 2^24
    mant, exp = mant >> carry, exp + carry
    return _float((exp << 23) | (mant & 0x7FFFFF), xp)


def _rn_div(a, d, xp):
    """Correctly rounded float32 ``a / d`` for normal or zero ``a`` and a
    positive normal scalar ``d``, by integer long division (see
    :func:`_rn_sqrt` for why). Same bits as NumPy's ``a / d`` whenever the
    quotient is normal."""
    ba, bd = _bits(a, xp), _bits(d, xp)
    ea, ed = (ba >> 23) & 0xFF, (bd >> 23) & 0xFF
    ma = (ba & 0x7FFFFF) | 0x800000
    md = (bd & 0x7FFFFF) | 0x800000
    # 26 quotient bits of ma / md: 1 integer bit + 25 fraction bits.
    take = ma >= md
    q = take.astype(ma.dtype)
    r = xp.where(take, ma - md, ma)
    for _ in range(25):
        r = r << 1
        take = r >= md
        q = (q << 1) | take.astype(q.dtype)
        r = xp.where(take, r - md, r)
    big = q >= (1 << 25)                        # quotient in [1, 2)
    mant = xp.where(big, q >> 2, q >> 1)
    guard = xp.where(big, (q >> 1) & 1, q & 1)
    sticky = (r != 0) | (big & ((q & 1) == 1))
    up = (guard == 1) & (sticky | ((mant & 1) == 1))
    mant = mant + up.astype(mant.dtype)
    exp = ea - ed + xp.where(big, 127, 126)
    carry = mant >> 24
    mant, exp = mant >> carry, exp + carry
    out = (ba & (-0x7FFFFFFF - 1)) | (exp << 23) | (mant & 0x7FFFFF)
    return xp.where((ba & 0x7FFFFFFF) == 0, _float(ba, xp), _float(out, xp))


def _normalize(v, xp):
    """``v / ||v||`` in float32 with a pinned schedule: the
    :func:`_tree_sumsq` norm, then correctly rounded sqrt and divide
    (:func:`_rn_sqrt`, :func:`_rn_div`). Every op is exact given its
    inputs on every backend, so NumPy and a TPU produce the SAME bits —
    the fused window's device update is bitwise-equal to the host's."""
    return _rn_div(v, _rn_sqrt(_tree_sumsq(v, xp), xp), xp)


def make_exact_matrix(
    dim: int, seed: int = 0, lo: int = -3, hi: int = 3, diag: int = 40
) -> np.ndarray:
    """Symmetric integer-valued float32 matrix with a dominant eigenvalue.

    Entries are small integers (plus an integer diagonal boost), so with a
    :func:`quantize_unit` iterate every partial sum of ``X @ w`` stays an
    exact multiple of the grid well inside float32's mantissa — the
    construction the runner's ``verify="exact"`` mode relies on. Keep the
    entry range modest: the exactness argument needs
    ``dim * max|X| * max|w|`` comfortably below ``2^24 / 2^bits``.
    """
    rng = np.random.default_rng(seed)
    a = rng.integers(lo, hi + 1, size=(dim, dim))
    return (a + a.T + diag * np.eye(dim, dtype=np.int64)).astype(np.float32)


def quantize_unit(v: np.ndarray, bits: int = 8) -> np.ndarray:
    """Normalize then snap to the 2^-bits grid (entries exactly representable).

    With integer-valued X and a grid-valued w, every partial sum of
    ``X @ w`` is an exact multiple of 2^-bits well inside float32's 24-bit
    mantissa — so the distributed combine is bit-identical to a float64 host
    reference regardless of block order, and the runner's ``verify="exact"``
    mode holds at every step.

    The math is float32 with a :func:`_normalize` norm: a fully explicit
    schedule that jax reproduces bit for bit on every backend, so the fused
    device driver can run the SAME update in-graph
    (:meth:`~repro.api.workload.MatVecPowerIteration.fused_update`) and a
    K-step window stays bitwise-equal to K stepwise host updates. (Snapping
    to the grid makes the precision difference vs the old float64 normalize
    immaterial; the grid exactness argument above is unchanged.)
    """
    v = np.asarray(v, dtype=np.float32)
    u = _normalize(v, np)
    q = (np.round(u * (1 << bits)) / np.float32(1 << bits)).astype(np.float32)
    if not np.any(q):
        q = np.zeros_like(u)
        q[int(np.argmax(np.abs(v)))] = 1.0
    return q


def unit_vector(v: np.ndarray) -> np.ndarray:
    """Float32 normalize with the :func:`_normalize` schedule — the
    unquantized iterate update, bitwise-reproducible on device."""
    v = np.asarray(v, dtype=np.float32)
    return _normalize(v, np)


@dataclass
class PowerIterationResult:
    reports: List[StepReport]
    eigvec: np.ndarray
    eigval: float
    residuals: List[float]          # ||X w - lambda w|| / ||X w|| per step
    churn_events: int
    plans_compiled: int
    cache_hits: int
    total_waste: int
    executor_cache_size: int

    @property
    def total_modeled_latency(self) -> float:
        return float(sum(r.modeled_completion for r in self.reports))

    @property
    def steps_per_sec(self) -> float:
        wall = sum(r.wall_s for r in self.reports)
        return len(self.reports) / wall if wall > 0 else float("inf")


def run_power_iteration(
    runner: ElasticRunner,
    n_steps: int,
    events: Optional[Iterable[ElasticEvent]] = None,
    w0: Optional[np.ndarray] = None,
    straggler_sets=None,
    quantize_bits: Optional[int] = 8,
    seed: int = 0,
) -> PowerIterationResult:
    """Deprecated shim: drive elastic power iteration through a churn trace.

    The loop now lives in :class:`repro.api.workload.MatVecPowerIteration`
    driven by :class:`repro.api.ElasticEngine` (``backend="device"``); this
    wrapper adopts the given runner and delegates, returning the same
    :class:`PowerIterationResult` bit for bit. New code should call the
    engine directly — it runs the same config on either backend.

    ``events`` yields at most one :class:`ElasticEvent` per step (e.g.
    :func:`repro.core.elastic.scripted_trace` or a stepped
    :class:`~repro.core.elastic.MarkovChurnTrace`); ``straggler_sets`` is
    either an indexable of per-step straggler sets or a callable
    ``(step, membership) -> sequence`` evaluated *after* the step's event is
    applied (so stragglers can be drawn from the live membership). With
    ``quantize_bits`` the iterate stays on an exactly-representable grid
    (see :func:`quantize_unit`), which is what makes the runner's exact
    verification meaningful.
    """
    import warnings

    from repro.api import ElasticEngine, MatVecPowerIteration

    warnings.warn(
        "run_power_iteration is deprecated; use repro.api.ElasticEngine("
        "MatVecPowerIteration(...), ..., backend='device')",
        DeprecationWarning, stacklevel=2,
    )
    workload = MatVecPowerIteration(w0=w0, quantize_bits=quantize_bits,
                                    seed=seed)
    res = ElasticEngine.from_runner(runner, workload).run(
        n_steps=n_steps, events=events, straggler_sets=straggler_sets)
    return res.result
