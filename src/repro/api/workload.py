"""Workload protocol: what a computation must provide to run elastically.

The paper's framework (Algorithm 1 + eq. (8)) never looks inside the
computation — it only needs the work to split into *tiles* over an uncoded
placement, with any row of a stored tile computable by any holder. This
module captures that contract as a small protocol so the same elastic
machinery (planning, churn, straggler masking, simulation, live execution)
drives arbitrary workloads:

- :meth:`Workload.stage`       — data -> the (q, r) row matrix to tile,
- :meth:`Workload.tile_compute`— the per-block pure function a worker runs
  on its plan slice (jax; plugged into the shard_map executor),
- :meth:`Workload.combine`     — assembled per-row partials -> step result
  (host side; identity for linear workloads, a monoid fold for map-reduce),
- :meth:`Workload.verify`      — step result vs a float64 host reference.

Three concrete workloads ship here:

- :class:`MatVec` / :class:`MatVecPowerIteration` — the paper's §V
  application (``y = X @ w`` per step, power-iteration driver extracted
  verbatim from the legacy ``run_power_iteration`` loop),
- :class:`MatMat` — multi-column ``Y = X @ W`` (the linear-regression /
  gradient workhorse of the heterogeneous CEC literature,
  arXiv:2008.05141), dispatched through the blocked
  :func:`repro.kernels.ops.usec_matmat` path,
- :class:`MapReduceRows` — an arbitrary per-row pure function plus a monoid
  combine (the "beyond linear computations" direction of decentralized
  USEC, arXiv:2403.00585).

Host-side methods are pure NumPy; jax is only touched by ``tile_compute`` /
``executor_fn`` (so the simulate backend never imports it).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import numpy as np

__all__ = [
    "MapReduceRows",
    "MatMat",
    "MatVec",
    "MatVecPowerIteration",
    "Workload",
]


class Workload:
    """Protocol + shared plumbing for elastic workloads.

    Subclasses override the four protocol methods (``stage``,
    ``tile_compute`` / ``executor_fn``, ``combine``, ``verify``) plus the
    iterative-driver hooks (``init_operand``, ``consume``, ``finalize``)
    as needed. A workload instance carries per-run state (see
    :meth:`reset`); the engine resets it at the start of every run.

    Attributes:
      name: short identifier (benchmark/sweep axis labels).
      out_cols: static per-row output width of ``tile_compute`` when it
        differs from the operand's column count (None = follows operand —
        the matvec/matmat case).
      linear: True when the per-step result is a linear map of the
        operand (``y = X @ w``), which makes it eligible for Freivalds
        result verification (``verify_results``; see
        :class:`repro.faults.integrity.IntegrityChecker`). Tile
        fingerprint auditing applies regardless.
    """

    name: str = "workload"
    out_cols: Optional[int] = None
    linear: bool = False

    # ------------------------------------------------------------------ #
    # The protocol
    # ------------------------------------------------------------------ #
    def stage(self, data: Any) -> np.ndarray:
        """Return the (q, r) row matrix whose rows are tiled over the
        placement (the paper's X). The default accepts a 2-d array."""
        x = np.asarray(data)
        if x.ndim != 2:
            raise ValueError(f"{self.name}: data must be a (q, r) matrix, "
                             f"got shape {x.shape}")
        return x

    def tile_compute(self, staged_block, operand):
        """Compute one staged plan slice: ``partial = f(block rows, operand)``.

        THE protocol hook: jax arrays in ((block_rows, r) block, the 2-d
        operand), jax array out ((block_rows, cols)). Must be pure — the
        elastic machinery recomputes rows on any holder. Overriding this
        alone is enough for a custom workload; the device executor routes
        through it via the default :meth:`executor_fn`."""
        raise NotImplementedError

    def executor_fn(self, mode: Optional[str] = None) -> Callable:
        """The jax block function ``f(xb, w2) -> (block_rows, cols)`` the
        device executor binds once at build time. The default wraps
        :meth:`tile_compute`; workloads with kernel dispatch (``mode`` =
        Pallas/interpret/ref) override this instead."""
        del mode  # the default tile_compute path has no kernel dispatch
        return self.tile_compute

    def fused_update(self, mode: Optional[str] = None) -> Optional[Callable]:
        """The in-graph iterate update ``f(raw_result, operand) -> next
        operand`` the fused device driver applies between the K steps of a
        window (jax; runs inside ``lax.scan``, so the whole window is one
        dispatch). ``raw_result`` is the assembled pre-``combine`` output —
        identical to ``combine``'s input, so for identity-combine workloads
        it is the step result itself.

        Returning None opts the workload out of fusion (the engine falls
        back to stepwise dispatch). The default is the fixed-point identity,
        but ONLY when :meth:`consume` is not overridden — a workload with
        custom host-side consume logic and no device twin must not silently
        diverge under fusion. Overrides must be **bitwise-identical** to the
        host ``consume`` operand chain (see
        :class:`MatVecPowerIteration.fused_update` and the tree-reduction
        normalize it shares with
        :func:`repro.runtime.elastic_runner.quantize_unit`)."""
        del mode
        if type(self).consume is not Workload.consume:
            return None
        return lambda y, w: w

    def segmented_fn(
        self, mode: Optional[str] = None, block_rows: int = 16,
    ) -> Optional[Callable]:
        """The whole-block-list compute of the segment-aware executor path:
        ``f(staged, blk_slot, blk_off, blk_include, w2) -> (B, block_rows,
        cols)`` compact per-block partials (the executor scatter-adds them
        to global rows). None disables the path for this workload.

        The default gathers every block's rows once and vmaps
        :meth:`executor_fn` over the block axis — correct for any pure
        ``tile_compute``. The linear workloads override this with the
        scalar-prefetched Pallas kernel dispatch
        (:func:`repro.kernels.ops.usec_segmented`)."""
        import jax

        from repro.kernels.usec_segmented import gather_block_rows

        fn = self.executor_fn(mode)

        def seg(staged, blk_slot, blk_off, blk_include, w2):
            xg = gather_block_rows(staged, blk_slot, blk_off, block_rows)
            part = jax.vmap(lambda xb: fn(xb, w2))(xg)
            return part * blk_include[:, None, None]

        return seg

    def combine(self, partials: np.ndarray):
        """Host-side combine of the fully-reduced per-row partials into the
        step result. Identity for linear workloads (the psum already summed
        exactly one copy of every row)."""
        return partials

    def verify(self, result, operand: np.ndarray, x64: Optional[np.ndarray],
               mode: str, atol: float) -> None:
        """Check the step result against a float64 host reference.

        mode: ``"exact"`` (bitwise) or ``"allclose"``. Raises
        AssertionError on mismatch, ValueError on unknown mode."""
        raise NotImplementedError

    # ------------------------------------------------------------------ #
    # Iterative-driver hooks (the engine's per-step loop)
    # ------------------------------------------------------------------ #
    def reset(self) -> None:
        """Clear per-run state; called by the engine before every run."""

    def init_operand(self, rows_total: int,
                     operand: Optional[np.ndarray] = None) -> np.ndarray:
        """The step-0 operand. ``operand`` is the caller-supplied override
        (``ElasticEngine.run(operand=...)``)."""
        if operand is None:
            raise ValueError(
                f"{self.name}: an operand is required "
                "(pass operand= to run(), or use a workload that owns one)")
        return np.asarray(operand)

    def consume(self, result, operand: np.ndarray) -> np.ndarray:
        """Fold one step result into the driver state; returns the next
        step's operand (default: operand unchanged — fixed-point reruns)."""
        return operand

    def finalize(self, runner, reports: List, last_result,
                 last_operand: np.ndarray):
        """Build the run-level result object (default: last step result)."""
        return last_result

    # ------------------------------------------------------------------ #
    # Analytical model hooks (the simulate backend)
    # ------------------------------------------------------------------ #
    def cost_scale(self) -> float:
        """Per-row work relative to a single matvec row (scales analytical
        completion times; 1.0 keeps them bitwise equal to the matvec
        simulator)."""
        return 1.0


def _segmented_linear(mode: Optional[str], block_rows: int) -> Callable:
    """The linear workloads' segmented dispatch: the scalar-prefetched
    Pallas kernel on TPU, the gathered flat matmul elsewhere — ONE binding
    shared by :class:`MatVec` and :class:`MatMat`."""
    import functools

    from repro.kernels.ops import usec_segmented

    return functools.partial(usec_segmented, block_rows=block_rows,
                             mode=mode)


def _verify_linear(y, ref: np.ndarray, what: str, mode: str,
                   atol: float) -> None:
    """Shared exact/allclose check used by the linear workloads."""
    if mode == "exact":
        y64 = np.asarray(y, dtype=np.float64)
        if not np.array_equal(y64, ref):
            flat = int(np.argmax(np.asarray(y64 != ref).ravel()))
            raise AssertionError(
                f"y != {what} (exact): first mismatch at flat index {flat}: "
                f"{np.asarray(y).ravel()[flat]!r} vs {ref.ravel()[flat]!r}"
            )
    elif mode == "allclose":
        err = float(np.max(np.abs(y - ref)))
        scale = float(np.max(np.abs(ref))) or 1.0
        if err > atol * scale:
            raise AssertionError(
                f"y != {what}: max abs err {err} (scale {scale})")
    else:
        raise ValueError(f"unknown verify mode {mode!r}")


class MatVec(Workload):
    """``y = X @ w`` per step — the workload the legacy runner hard-wired.

    The device executor's fast path: the Pallas ``usec_matvec`` kernel on
    TPU, the fused jnp dot on CPU (``repro.kernels.ops.executor_matmul``)."""

    name = "matvec"
    linear = True

    def tile_compute(self, staged_block, operand):
        return self.executor_fn(None)(staged_block, operand)

    def executor_fn(self, mode: Optional[str] = None) -> Callable:
        from repro.kernels.ops import executor_matmul

        return executor_matmul(mode)

    def segmented_fn(self, mode: Optional[str] = None,
                     block_rows: int = 16) -> Optional[Callable]:
        return _segmented_linear(mode, block_rows)

    def verify(self, result, operand, x64, mode, atol) -> None:
        if x64 is None:
            raise ValueError("verify requires the staged matrix (x64)")
        ref = x64 @ np.asarray(operand, dtype=np.float64)
        _verify_linear(result, ref, "X @ w", mode, atol)


class MatVecPowerIteration(MatVec):
    """Power iteration driven through elastic matvec steps (paper §V).

    Extracted from the legacy ``run_power_iteration`` loop, bit for bit:
    the iterate is normalized and snapped to a 2^-bits grid each step
    (:func:`repro.runtime.elastic_runner.quantize_unit`), so with
    integer-valued X the distributed combine verifies bit-exactly, and the
    per-step Rayleigh quotient / residual bookkeeping matches the legacy
    :class:`~repro.runtime.elastic_runner.PowerIterationResult` exactly.
    """

    name = "power_iteration"

    def __init__(self, w0: Optional[np.ndarray] = None,
                 quantize_bits: Optional[int] = 8, seed: int = 0):
        self.w0 = w0
        self.quantize_bits = quantize_bits
        self.seed = seed
        self.reset()

    def reset(self) -> None:
        self.residuals: List[float] = []
        self.eigval: float = 0.0

    def init_operand(self, rows_total, operand=None):
        from repro.runtime.elastic_runner import quantize_unit

        w0 = operand if operand is not None else self.w0
        rng = np.random.default_rng(self.seed)
        w = (
            np.asarray(w0, dtype=np.float32) if w0 is not None
            else rng.normal(size=rows_total).astype(np.float32)
        )
        if self.quantize_bits:
            w = quantize_unit(w, self.quantize_bits)
        return w

    def consume(self, result, operand):
        from repro.runtime.elastic_runner import quantize_unit, unit_vector

        w64 = operand.astype(np.float64)
        self.eigval = float(w64 @ result) / float(w64 @ w64)
        num = float(np.linalg.norm(result - self.eigval * w64))
        den = float(np.linalg.norm(result)) or 1.0
        self.residuals.append(num / den)
        if self.quantize_bits:
            return quantize_unit(result, self.quantize_bits)
        return unit_vector(result)

    def fused_update(self, mode: Optional[str] = None) -> Optional[Callable]:
        """The device twin of the host iterate chain: normalize (+ snap to
        the 2^-bits grid) **in-graph**, bitwise-identical to
        :func:`~repro.runtime.elastic_runner.quantize_unit` /
        :func:`~repro.runtime.elastic_runner.unit_vector` by construction —
        both sides square, tree-reduce, sqrt, divide and round with the same
        explicit schedule in float32 (the binary-tree reduction pins the
        order; sqrt and divide are the integer-exact correctly rounded
        routines of :func:`~repro.runtime.elastic_runner._normalize`,
        because a TPU's own float32 sqrt and divide are not correctly
        rounded). This is what makes a fused window's outputs bit-equal to
        K stepwise steps on every backend.

        The per-step residual/eigenvalue *statistics* stay host-side: the
        engine replays :meth:`consume` on the window's (ys, ws) outputs and
        discards its returned operand (the device already carried it)."""
        del mode
        if type(self).consume is not MatVecPowerIteration.consume:
            # A subclass with its own host consume chain has no device
            # twin here — same safety rule as the base class: do not
            # silently diverge under fusion, fall back to stepwise.
            return None
        bits = self.quantize_bits

        def upd(y, w):
            import jax.numpy as jnp

            from repro.runtime.elastic_runner import _normalize

            del w
            v = y.astype(jnp.float32)
            u = _normalize(v, jnp)
            if not bits:
                return u
            q = (jnp.round(u * (1 << bits)) /
                 np.float32(1 << bits)).astype(jnp.float32)
            fallback = jnp.zeros_like(u).at[jnp.argmax(jnp.abs(v))].set(1.0)
            return jnp.where(jnp.any(q != 0), q, fallback)

        return upd

    def finalize(self, runner, reports, last_result, last_operand):
        from repro.runtime.elastic_runner import PowerIterationResult

        return PowerIterationResult(
            reports=reports,
            eigvec=last_operand,
            eigval=self.eigval,
            residuals=self.residuals,
            churn_events=runner.churn_events,
            plans_compiled=runner.plans_compiled,
            cache_hits=runner.cache_hits,
            total_waste=runner.total_waste,
            executor_cache_size=runner.executor_cache_size,
        )


class MatMat(Workload):
    """``Y = X @ W`` per step, W multi-column (r, c).

    The matrix-matrix workhorse of the heterogeneous CEC papers (linear
    regression / batched gradients): rows of X split over the elastic
    placement exactly as for matvec, each worker computes its block against
    the full W, and the psum assembles Y. Dispatched through the blocked
    :func:`repro.kernels.ops.usec_matmat` path (wide W is processed in
    column chunks on TPU).

    ``w`` fixes the operand at construction (elastic re-serving of one
    matmul across churn); pass ``operand=`` to ``run()`` to override.
    Analytical completion times scale by c (each row costs c matvec rows).
    """

    name = "matmat"
    linear = True

    def __init__(self, w: Optional[np.ndarray] = None):
        self.w = None if w is None else np.asarray(w, dtype=np.float32)
        if self.w is not None and self.w.ndim != 2:
            raise ValueError(f"MatMat operand must be (r, c), got {self.w.shape}")
        self._cols = None if self.w is None else int(self.w.shape[1])

    def tile_compute(self, staged_block, operand):
        return self.executor_fn(None)(staged_block, operand)

    def executor_fn(self, mode: Optional[str] = None) -> Callable:
        from repro.kernels.ops import executor_matmul

        return executor_matmul(mode, workload="matmat")

    def segmented_fn(self, mode: Optional[str] = None,
                     block_rows: int = 16) -> Optional[Callable]:
        return _segmented_linear(mode, block_rows)

    def init_operand(self, rows_total, operand=None):
        w = self.w if operand is None else np.asarray(operand, dtype=np.float32)
        if w is None:
            raise ValueError("MatMat needs W: construct MatMat(w) or pass operand=")
        if w.ndim != 2:
            raise ValueError(f"MatMat operand must be (r, c), got {w.shape}")
        self._cols = int(w.shape[1])
        return w

    def verify(self, result, operand, x64, mode, atol) -> None:
        if x64 is None:
            raise ValueError("verify requires the staged matrix (x64)")
        ref = x64 @ np.asarray(operand, dtype=np.float64)
        _verify_linear(result, ref, "X @ W", mode, atol)

    def cost_scale(self) -> float:
        if self._cols is None:
            # Silently returning 1.0 would label unscaled matvec times as
            # "matmat" on the simulate backend.
            raise ValueError(
                "MatMat cost_scale needs the column count: construct "
                "MatMat(w) (the device backend sets it from the operand)")
        return float(self._cols)


class MapReduceRows(Workload):
    """Arbitrary per-row pure function + monoid combine over all rows.

    The "beyond linear computations" workload: ``row_fn`` maps each staged
    row block to a (block_rows, out_cols) value *in jax* (it must be pure —
    the elastic machinery may recompute rows on any holder), the executor
    assembles the per-row map output with exactly-once semantics across
    churn and stragglers, and ``reduce_fn`` folds the assembled (q,
    out_cols) array into the step result on the host (any monoid: sum, max,
    logsumexp, histogram merge, ...).

    ``ref_row_fn(x64, operand) -> (q, out_cols) float64`` is the NumPy
    reference for ``verify`` (checks the *map* output — the part the
    distributed machinery is responsible for); like ``row_fn``, it receives
    the operand in its executor form (2-d: a 1-d operand arrives as an
    (r, 1) column, exactly what the device executor hands ``row_fn``).
    ``cost`` is the per-row work relative to a matvec row (the simulate
    backend's scaling).
    """

    name = "map_reduce_rows"

    def __init__(
        self,
        row_fn: Callable,
        reduce_fn: Callable[[np.ndarray], Any],
        out_cols: int = 1,
        ref_row_fn: Optional[Callable] = None,
        operand: Optional[np.ndarray] = None,
        cost: float = 1.0,
        name: Optional[str] = None,
    ):
        self.row_fn = row_fn
        self.reduce_fn = reduce_fn
        self.out_cols = int(out_cols)
        self.ref_row_fn = ref_row_fn
        self.operand = (
            None if operand is None else np.asarray(operand, dtype=np.float32)
        )
        self.cost = float(cost)
        if name:
            self.name = name

    def tile_compute(self, staged_block, operand):
        return self.row_fn(staged_block, operand)

    def executor_fn(self, mode: Optional[str] = None) -> Callable:
        del mode  # row_fn is user jax code; no kernel dispatch
        return self.row_fn

    def init_operand(self, rows_total, operand=None):
        if operand is not None:
            return np.asarray(operand, dtype=np.float32)
        if self.operand is not None:
            return self.operand
        # row_fn may not use the operand at all; feed a fixed placeholder so
        # the executor signature (and the jit cache) stays uniform.
        return np.zeros((1,), dtype=np.float32)

    def combine(self, partials):
        return self.reduce_fn(np.asarray(partials))

    def verify(self, result, operand, x64, mode, atol) -> None:
        # ``result`` here is the raw assembled map output (the runner
        # verifies before the host-side reduce): that is the quantity the
        # distributed machinery must deliver exactly once per row.
        if self.ref_row_fn is None:
            raise ValueError(
                f"{self.name}: verify requires ref_row_fn (a NumPy reference "
                "of row_fn)")
        if x64 is None:
            raise ValueError("verify requires the staged matrix (x64)")
        # Hand the reference the SAME operand shape row_fn sees in the
        # executor (1-d operands arrive column-expanded).
        op = np.asarray(operand)
        op2 = op if op.ndim == 2 else op[:, None]
        ref = np.asarray(self.ref_row_fn(x64, op2), dtype=np.float64)
        ref = ref.reshape(x64.shape[0], self.out_cols)
        _verify_linear(result, ref, f"{self.name} map", mode, atol)

    def cost_scale(self) -> float:
        return self.cost
