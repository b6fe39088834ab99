"""Distributed USEC executors (shard_map over the worker axis).

The executor realizes the paper's computation assignment on an SPMD mesh:

- every worker stages verbatim copies of the tiles its placement Z_n assigns
  (uncoded storage),
- the compiled plan gives each worker a *block list* (fixed-size row blocks of
  its stored tiles) plus an inclusion weight per block,
- workers run a ``fori_loop`` with their **own trip count** — uneven loads
  execute as different iteration counts of the same compiled program — then
  meet at a single ``psum`` (the "master combine").

Redundant (1+S) blocks are computed by all their holders; the inclusion mask
(0/1) selects exactly one surviving copy per block, so the psum reconstructs
``y = X w`` exactly even when straggler contributions are dropped.

The worker axis is *manual* (shard_map) while any other mesh axes stay under
GSPMD — so the same executor works on (data,) meshes and (data, model) meshes.

Three step drivers share one per-worker math (so their per-step results are
the same compiled computation, bit for bit):

- :func:`make_matvec_executor` — one dispatch per step (the K=1 path);
- :func:`make_fused_executor`  — a ``lax.scan`` window of ``fuse_steps``
  iterations per dispatch. The iterate update runs **on device** (the
  workload's ``fused_update`` hook), include masks are computed **in-graph**
  from a per-step straggler bitmask (:func:`device_include_weights`, the
  device-side twin of :func:`refresh_include`), and the iterate carry is
  donated — so a window costs ONE host round-trip for K steps;
- :func:`make_worker_executor` — the first-arrival variant: the same
  per-worker body WITHOUT the psum. Every worker's unmasked partial stays
  on the device that holds its staged shard (output sharded over the
  worker axis, no collective), and each shard is independently fetchable,
  so the master can consume completions in arrival order (the paper's
  "first N_t − S results" semantics) instead of blocking on the collective
  psum barrier; the combine weights are applied host-side *after* the
  realized straggler set is known (:meth:`ElasticRunner.step` with
  ``arrival="first"``).

On TPU a one-column linear step streams each worker's block list from its
staged tiles in one Pallas kernel (``usec_stream``) instead of the loop:
the same rows, the same products, the same include order.

The three programs are named ``usec_step``, ``usec_window`` and
``usec_partials``, and each worker's block list runs under the named scope
``usec_blocks``: a profiler trace shows ``jit_usec_step`` and the op names
below it, whatever the Python around them is called.

Every array a step reads is placed once, where the program expects it:
staged tiles and per-worker plan rows sharded over the worker axis
(:func:`worker_sharding`), the operand replicated — so no dispatch moves
a worker's data to another device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.plan import CompiledPlan


# ---------------------------------------------------------------------- #
# Staging (host-side): uncoded copies per placement
# ---------------------------------------------------------------------- #
@dataclass
class StagedMatrix:
    """Per-worker staged tile copies of the data matrix X.

    staged:    (N, T_stage, rows_per_tile, r) — worker n's local tile copies
               (zeros in unused slots). This J-fold duplication *is* the
               paper's uncoded storage cost.
    slot_of:   (N, G) int32 — staged slot of tile g on worker n (-1 if absent).
    """

    staged: np.ndarray
    slot_of: np.ndarray

    @property
    def t_stage(self) -> int:
        return self.staged.shape[1]


def stage_matrix(x: np.ndarray, placement, rows_per_tile: int) -> StagedMatrix:
    """Copy each tile of X onto its placement holders (host memory)."""
    n = placement.n_machines
    g_total = placement.n_tiles
    q, r = x.shape
    if q != g_total * rows_per_tile:
        raise ValueError(f"X has {q} rows != G*rows_per_tile = {g_total * rows_per_tile}")
    z = placement.storage_sets()
    t_stage = max(len(s) for s in z)
    staged = np.zeros((n, t_stage, rows_per_tile, r), dtype=x.dtype)
    slot_of = np.full((n, g_total), -1, dtype=np.int32)
    for worker in range(n):
        for slot, g in enumerate(sorted(z[worker])):
            staged[worker, slot] = x[g * rows_per_tile: (g + 1) * rows_per_tile]
            slot_of[worker, g] = slot
    return StagedMatrix(staged, slot_of)


# ---------------------------------------------------------------------- #
# Block plans: segments -> fixed-size work units
# ---------------------------------------------------------------------- #
@dataclass
class BlockPlan:
    """Per-worker fixed-size block lists (padded).

    blk_slot:    (N, B) int32  — staged slot holding the block's tile
    blk_off:     (N, B) int32  — row offset within the tile
    blk_goff:    (N, B) int32  — global output row offset
    blk_include: (N, B) float32 — combine weight (1 = this copy is used)
    n_blocks:    (N,)  int32  — per-worker trip count
    block_rows:  rows per block (static)
    blk_seg_t:   (N, B) int32 — the plan slot ``t`` each block came from
                 (-1 on padding). Lets :func:`refresh_include` recompute the
                 combine weights for a new straggler set without re-expanding
                 the block lists (the elastic runner's per-step hot path).
    blk_prio:    (N, B, 1+S) int32 — the combine-priority order of the
                 block's segment group (-1 on padding). The fused executor
                 gathers include weights for ANY straggler bitmask straight
                 from this array on device (:func:`device_include_weights`),
                 so mid-window stragglers never touch the host.
    """

    blk_slot: np.ndarray
    blk_off: np.ndarray
    blk_goff: np.ndarray
    blk_include: np.ndarray
    n_blocks: np.ndarray
    block_rows: int
    blk_seg_t: Optional[np.ndarray] = None
    blk_prio: Optional[np.ndarray] = None

    @property
    def b_max(self) -> int:
        return self.blk_slot.shape[1]


def _empty_block_plan(n: int, cap: int, block_rows: int, width: int) -> BlockPlan:
    return BlockPlan(
        blk_slot=np.zeros((n, cap), np.int32),
        blk_off=np.zeros((n, cap), np.int32),
        blk_goff=np.zeros((n, cap), np.int32),
        blk_include=np.zeros((n, cap), np.float32),
        n_blocks=np.zeros((n,), np.int32),
        block_rows=block_rows,
        blk_seg_t=np.full((n, cap), -1, np.int32),
        blk_prio=np.full((n, cap, width), -1, np.int32),
    )


def block_plan(
    plan: CompiledPlan,
    slot_of: np.ndarray,
    block_rows: int,
    stragglers: Sequence[int] = (),
    b_max: Optional[int] = None,
) -> BlockPlan:
    """Expand a CompiledPlan's segments into per-worker block lists.

    Requires the plan to have been compiled with ``row_align == block_rows``
    (and ``block_rows | rows_per_tile``) so every segment is block-aligned.

    Vectorized NumPy segment expansion: every (worker, slot) segment emits
    ``seg_len // block_rows`` blocks via one repeat/cumsum pass, in the same
    (worker, slot, block) order as the original triple loop —
    :func:`block_plan_reference` keeps that loop form as the bitwise test
    oracle.
    """
    if plan.rows_per_tile % block_rows:
        raise ValueError(
            f"block_rows={block_rows} must divide rows_per_tile={plan.rows_per_tile}"
        )
    inc = plan.include_mask(stragglers)
    n, t_cap = plan.seg_len.shape
    ln = plan.seg_len.astype(np.int64)
    live = ln > 0
    if np.any(ln[live] % block_rows):
        raise ValueError(
            "segment not block-aligned; compile the plan with "
            f"row_align={block_rows}"
        )
    nb = ln // block_rows                       # (N, T) blocks per segment
    # Flatten row-major: per-worker segments stay contiguous and ordered by
    # slot, so per-worker block positions are a simple offset subtraction.
    nb_flat = nb.ravel()
    total = int(nb_flat.sum())
    per_worker = nb.sum(axis=1)
    cap = int(per_worker.max()) if n else 0
    if b_max is not None:
        if b_max < cap:
            raise ValueError(f"b_max={b_max} < needed {cap}")
        cap = b_max
    cap = max(cap, 1)
    _, _, _, _, prio = plan.seg_arrays()
    width = prio.shape[1] if prio.size else 1 + plan.stragglers
    bp = _empty_block_plan(n, cap, block_rows, width)
    bp.n_blocks[:] = per_worker.astype(np.int32)
    if total == 0:
        return bp

    seg_idx = np.repeat(np.arange(n * t_cap, dtype=np.int64), nb_flat)
    # Within-segment block index: position minus the segment's first position.
    seg_starts = np.concatenate(([0], np.cumsum(nb_flat)))[:-1]
    b_in_seg = np.arange(total, dtype=np.int64) - seg_starts[seg_idx]
    w_of = seg_idx // t_cap
    # Per-worker slot index: position minus the worker's first position.
    w_starts = np.concatenate(([0], np.cumsum(per_worker)))[:-1]
    pos = np.arange(total, dtype=np.int64) - w_starts[w_of]

    g = plan.seg_tile.ravel()[seg_idx].astype(np.int64)
    off = plan.seg_start.ravel()[seg_idx].astype(np.int64) + b_in_seg * block_rows
    slot = slot_of[w_of, g]
    if np.any(slot < 0):
        w_bad = int(w_of[np.argmax(slot < 0)])
        g_bad = int(g[np.argmax(slot < 0)])
        raise RuntimeError(f"worker {w_bad} assigned tile {g_bad} it does not store")
    t_of = seg_idx % t_cap

    bp.blk_slot[w_of, pos] = slot.astype(np.int32)
    bp.blk_off[w_of, pos] = off.astype(np.int32)
    bp.blk_goff[w_of, pos] = (g * plan.rows_per_tile + off).astype(np.int32)
    bp.blk_include[w_of, pos] = inc.ravel()[seg_idx].astype(np.float32)
    bp.blk_seg_t[w_of, pos] = t_of.astype(np.int32)
    sid = plan.seg_id.ravel()[seg_idx]
    if prio.size:
        bp.blk_prio[w_of, pos] = prio[sid]
    return bp


def block_plan_reference(
    plan: CompiledPlan,
    slot_of: np.ndarray,
    block_rows: int,
    stragglers: Sequence[int] = (),
    b_max: Optional[int] = None,
) -> BlockPlan:
    """The original triple-loop block expansion — the test oracle for the
    vectorized :func:`block_plan` (bitwise-identical output, asserted by
    ``tests/test_executor_blocks.py``)."""
    if plan.rows_per_tile % block_rows:
        raise ValueError(
            f"block_rows={block_rows} must divide rows_per_tile={plan.rows_per_tile}"
        )
    inc = plan.include_mask(stragglers)
    _, _, _, _, prio = plan.seg_arrays()
    width = prio.shape[1] if prio.size else 1 + plan.stragglers
    n = plan.n_machines
    lists = [[] for _ in range(n)]
    for w in range(n):
        for t in range(plan.t_max):
            ln = int(plan.seg_len[w, t])
            if ln == 0:
                continue
            if ln % block_rows:
                raise ValueError(
                    "segment not block-aligned; compile the plan with "
                    f"row_align={block_rows}"
                )
            g = int(plan.seg_tile[w, t])
            st = int(plan.seg_start[w, t])
            slot = int(slot_of[w, g])
            if slot < 0:
                raise RuntimeError(f"worker {w} assigned tile {g} it does not store")
            use = float(inc[w, t])
            sid = int(plan.seg_id[w, t])
            for b in range(ln // block_rows):
                off = st + b * block_rows
                lists[w].append(
                    (slot, off, g * plan.rows_per_tile + off, use, t, sid)
                )
    cap = max((len(l) for l in lists), default=0)
    if b_max is not None:
        if b_max < cap:
            raise ValueError(f"b_max={b_max} < needed {cap}")
        cap = b_max
    cap = max(cap, 1)
    bp = _empty_block_plan(n, cap, block_rows, width)
    for w in range(n):
        for i, (slot, off, goff, use, t, sid) in enumerate(lists[w]):
            bp.blk_slot[w, i] = slot
            bp.blk_off[w, i] = off
            bp.blk_goff[w, i] = goff
            bp.blk_include[w, i] = use
            bp.blk_seg_t[w, i] = t
            if prio.size:
                bp.blk_prio[w, i] = prio[sid]
        bp.n_blocks[w] = len(lists[w])
    return bp


def refresh_include(
    bp: BlockPlan, plan: CompiledPlan, stragglers: Sequence[int] = ()
) -> np.ndarray:
    """Recompute ``blk_include`` for a new per-step straggler set.

    The block *geometry* (slots, offsets, trip counts) depends only on the
    plan; the combine weights depend on which holders straggled this step.
    Gathering the plan's (N, T_max) include mask through ``blk_seg_t`` turns
    a straggler change into an O(N·B) array swap — no block re-expansion, no
    recompilation. Returns a fresh (N, B) float32 array; ``bp`` is unchanged.
    """
    if bp.blk_seg_t is None:
        raise ValueError("BlockPlan was built without blk_seg_t; rebuild via block_plan()")
    inc = plan.include_mask(stragglers)                      # (N, T_max)
    t = np.maximum(bp.blk_seg_t, 0)
    rows = np.arange(bp.blk_slot.shape[0])[:, None]
    out = inc[rows, t].astype(np.float32)
    out[bp.blk_seg_t < 0] = 0.0
    return out


def device_include_weights(
    blk_prio: jnp.ndarray, blk_valid: jnp.ndarray, bad: jnp.ndarray
) -> jnp.ndarray:
    """In-graph twin of :func:`refresh_include`: (N, B) combine weights from
    a straggler bitmask.

    For every block, the winner is the first **non-straggling** machine in
    the segment's combine-priority order (the paper's first-arrival master
    semantics, exactly :meth:`CompiledPlan.include_mask`); the block's weight
    is 1.0 iff this worker is that winner. Pure gather/compare on (N, B, 1+S)
    arrays, so per-step straggler churn inside a fused window is device data,
    never a host round-trip.

    Args:
      blk_prio: (N, B, 1+S) int32, -1 on padding (:attr:`BlockPlan.blk_prio`).
      blk_valid: (N, B) bool — real (non-padding) blocks.
      bad: (N,) bool — straggler bitmask over the machine population.

    The caller must have validated feasibility (some non-straggler per
    segment) host-side; with a dead segment this returns winner = its
    highest-priority holder instead of raising.
    """
    ok = jnp.logical_not(bad[jnp.clip(blk_prio, 0, None)])     # (N, B, L)
    first = jnp.argmax(ok, axis=-1)                            # first alive
    winner = jnp.take_along_axis(
        blk_prio, first[..., None], axis=-1)[..., 0]           # (N, B)
    ids = jnp.arange(blk_prio.shape[0], dtype=blk_prio.dtype)[:, None]
    return ((winner == ids) & blk_valid).astype(jnp.float32)


# ---------------------------------------------------------------------- #
# The jitted executors
# ---------------------------------------------------------------------- #
def _default_matmul(xb, wb):
    return jnp.dot(
        xb.astype(jnp.float32), wb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def worker_sharding(mesh: jax.sharding.Mesh, worker_axis: str,
                    lead: int = 0) -> NamedSharding:
    """Sharding of an array whose axis ``lead`` is the worker axis: worker
    n's slice lives on worker n's device (the staged tiles, the (N, B)
    plan rows, and — with ``lead=1`` — the fused window's (K, N, B) plan
    stacks). Placing inputs this way is what keeps every dispatch free of
    resharding: it is exactly the layout the shard_map ``in_specs`` ask
    for."""
    return NamedSharding(mesh, P(*([None] * lead), worker_axis))


def block_list_path(
    stream_fn: Optional[Callable],
    segmented_fn: Optional[Callable],
    cols: int,
    out_cols: Optional[int],
) -> str:
    """Which block-list path a worker body compiles: ``"stream"`` (one
    ``stream_fn`` call over the whole list, for a one-column operand whose
    output width follows it), ``"segmented"`` (``segmented_fn``) or
    ``"loop"`` (the per-block ``fori_loop``)."""
    if stream_fn is not None and cols == 1 and out_cols is None:
        return "stream"
    return "segmented" if segmented_fn is not None else "loop"


def _make_worker_body(
    worker_axis: str,
    rows_total: int,
    block_rows: int,
    mm: Callable,
    out_cols: Optional[int],
    segmented_fn: Optional[Callable],
    combine: bool = True,
    stream_fn: Optional[Callable] = None,
):
    """The per-worker, per-step computation shared by all three executors —
    ONE definition so the drivers are the same compiled math.

    The block-list path is chosen at trace time by :func:`block_list_path`
    and appended to ``body.paths``. With ``stream_fn`` and a one-column
    operand the worker's whole block list is one call,
    ``stream_fn(staged, blk_slot, blk_off, blk_goff, blk_include,
    n_blocks, w2) -> (rows_total, 1)``: the block products times their
    include weights on the blocks' global rows, 0 elsewhere — exactly the
    loop's output (the Pallas ``usec_stream`` kernel).

    With ``segmented_fn`` the per-block ``fori_loop`` is replaced by one
    whole-block-list call (the segment-aware kernel path): ``segmented_fn``
    returns the (B, block_rows, cols) compact partials, which are
    scatter-added into the output rows. Per-worker output rows are disjoint
    (each worker computes an assigned row once), so add equals the loop's
    overwrite; padding blocks carry include == 0 and add exact zeros.

    ``combine`` psums the partials over the worker axis (the barrier
    master); without it each worker returns its own partial with a leading
    worker axis of 1, for an output sharded over the workers (the
    first-arrival master fetches the shards one by one).
    """

    def body(staged, blk_slot, blk_off, blk_goff, blk_include, n_blocks, w):
        # Per-worker shapes: staged (1, T, rows_per_tile, r); plan rows (1, B).
        staged = staged[0]
        blk_slot, blk_off = blk_slot[0], blk_off[0]
        blk_goff, blk_include = blk_goff[0], blk_include[0]
        w2 = w if w.ndim == 2 else w[:, None]
        cols = w2.shape[1] if out_cols is None else out_cols
        path = block_list_path(stream_fn, segmented_fn, w2.shape[1],
                               out_cols)
        body.paths.append(path)

        if path != "loop":
            def _compute():
                if path == "stream":
                    return stream_fn(staged, blk_slot, blk_off, blk_goff,
                                     blk_include, n_blocks, w2)
                compact = segmented_fn(staged, blk_slot, blk_off,
                                       blk_include, w2)
                rows = (
                    blk_goff[:, None]
                    + jnp.arange(block_rows, dtype=jnp.int32)
                ).reshape(-1)
                return jnp.zeros((rows_total, cols), jnp.float32) \
                    .at[rows].add(compact.reshape(-1, cols))

            # Zero-trip workers (preempted machines; inactive padding steps
            # of a fused window, whose trip counts are zeroed in-graph)
            # skip the block list entirely — same contract as the
            # fori_loop path's zero iteration count.
            with jax.named_scope("usec_blocks"):
                y = jax.lax.cond(
                    n_blocks[0] > 0, _compute,
                    lambda: jnp.zeros((rows_total, cols), jnp.float32))
        else:
            y0 = jnp.zeros((rows_total, cols), jnp.float32)

            def step(i, y):
                xb = jax.lax.dynamic_slice(
                    staged[blk_slot[i]],
                    (blk_off[i], 0),
                    (block_rows, staged.shape[-1]),
                )
                yb = mm(xb, w2) * blk_include[i]
                return jax.lax.dynamic_update_slice(y, yb, (blk_goff[i], 0))

            with jax.named_scope("usec_blocks"):
                y = jax.lax.fori_loop(0, n_blocks[0], step, y0)
        if combine:
            y = jax.lax.psum(y, worker_axis)
        # A 1-d operand squeezes back to a vector only when the output width
        # follows the operand; an explicit out_cols keeps its matrix shape.
        y = y if (w.ndim == 2 or out_cols is not None) else y[:, 0]
        return y if combine else y[None]

    body.paths = []
    return body


def _with_paths(jitted, body):
    """Expose the block-list paths the body was traced with (one per
    compile) as ``jitted.block_paths``."""
    jitted.block_paths = body.paths
    return jitted


def _shard(body, mesh, worker_axis, combine: bool = True):
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(worker_axis), P(worker_axis), P(worker_axis), P(worker_axis),
            P(worker_axis), P(worker_axis), P(),
        ),
        out_specs=P() if combine else P(worker_axis),
        axis_names={worker_axis},
        check_vma=False,
    )


def make_matvec_executor(
    mesh: jax.sharding.Mesh,
    worker_axis: str,
    rows_total: int,
    block_rows: int,
    matmul: Optional[Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = None,
    out_cols: Optional[int] = None,
    segmented_fn: Optional[Callable] = None,
    stream_fn: Optional[Callable] = None,
) -> Callable:
    """Build the jitted USEC row-sharded step for a fixed geometry.

    Returns ``usec_step(staged, blk_slot, blk_off, blk_goff, blk_include,
    n_blocks, w) -> y`` where array shapes follow :class:`StagedMatrix` /
    :class:`BlockPlan` and ``w`` is (r,) or (r, c). The output is (rows_total,
    [c]) float32, fully reduced.

    ``matmul`` is the per-block compute ``f(xb, w2) -> (block_rows, cols)``;
    it defaults to a fp32-accumulating dot (``y = X w`` semantics, the USEC
    matvec). On TPU pass ``repro.kernels.ops.usec_matvec`` to run the Pallas
    kernel per block — or any other row-wise map (a workload's
    ``tile_compute``), in which case ``out_cols`` pins the static per-row
    output width when it differs from the operand's column count (the
    map-reduce workloads of :mod:`repro.api`).

    ``segmented_fn`` swaps the per-block ``fori_loop`` for the segment-aware
    whole-block-list path (a workload's ``segmented_fn(mode)`` — the Pallas
    ``usec_segmented`` kernel on TPU, one gathered flat matmul elsewhere).
    ``stream_fn`` takes precedence for a one-column operand: the worker's
    block list streamed from its staged tiles in one call
    (:func:`repro.kernels.ops.usec_stream`). The returned function's
    ``block_paths`` lists the path each compile took
    (:func:`block_list_path`).
    """
    body = _make_worker_body(
        worker_axis, rows_total, block_rows, matmul or _default_matmul,
        out_cols, segmented_fn, stream_fn=stream_fn,
    )
    sharded = _shard(body, mesh, worker_axis)

    def usec_step(staged, blk_slot, blk_off, blk_goff, blk_include,
                  n_blocks, w):
        return sharded(staged, blk_slot, blk_off, blk_goff, blk_include,
                       n_blocks, w)

    return _with_paths(jax.jit(usec_step), body)


def make_worker_executor(
    mesh: jax.sharding.Mesh,
    worker_axis: str,
    rows_total: int,
    block_rows: int,
    matmul: Optional[Callable] = None,
    out_cols: Optional[int] = None,
    segmented_fn: Optional[Callable] = None,
    stream_fn: Optional[Callable] = None,
) -> Callable:
    """Build the jitted per-worker partials for first-arrival execution.

    Returns ``usec_partials(staged, blk_slot, blk_off, blk_goff, blk_include,
    n_blocks, w) -> ys`` with the same arguments as
    :func:`make_matvec_executor`; ``ys`` is (N, rows_total[, c]), sharded
    over the worker axis: row n is worker n's **unmasked** partial,
    computed on the device that holds worker n's staged tiles. Callers
    pass the valid-block mask as ``blk_include`` (every real block
    contributes with weight 1), because the realized straggler set is not
    known at dispatch time — first-arrival masking is the master's
    business, applied host-side per row once arrivals decide the winners
    (:func:`refresh_include` + a winner gather). A worker that is not
    dispatched gets a zero trip count.

    There is no collective: each worker's shard completes on its own
    device and is fetched independently, so the master can consume
    partials in completion order. The per-worker math is
    :func:`_make_worker_body` itself, so a first-arrival combine of the
    winners' rows is bitwise-equal to the barrier psum on the same plan.
    One compiled program serves every worker (the jit cache stays at 1).
    """
    body = _make_worker_body(
        worker_axis, rows_total, block_rows, matmul or _default_matmul,
        out_cols, segmented_fn, combine=False, stream_fn=stream_fn,
    )
    sharded = _shard(body, mesh, worker_axis, combine=False)

    def usec_partials(staged, blk_slot, blk_off, blk_goff, blk_include,
                      n_blocks, w):
        return sharded(staged, blk_slot, blk_off, blk_goff, blk_include,
                       n_blocks, w)

    return _with_paths(jax.jit(usec_partials), body)


def make_fused_executor(
    mesh: jax.sharding.Mesh,
    worker_axis: str,
    rows_total: int,
    block_rows: int,
    fuse_steps: int,
    matmul: Optional[Callable] = None,
    out_cols: Optional[int] = None,
    update: Optional[Callable] = None,
    segmented_fn: Optional[Callable] = None,
    stream_fn: Optional[Callable] = None,
) -> Callable:
    """Build the jitted K-step fused window driver.

    Returns ``usec_window(staged, blk_slot, blk_off, blk_goff, n_blocks,
    blk_prio, blk_valid, bad, active, w) -> (w_out, ys, ws)``:

      blk_*:  (K, N, B[, 1+S]) int32 / n_blocks (K, N) — PER-STEP plan
              arrays, so a membership change inside the window is pure
              data: the runner stacks each step's cached plan and churn
              never breaks a window (only a plan-cache MISS flushes — its
              compile then overlaps the in-flight window).
      bad:    (K, N) bool  — per-step straggler bitmasks
      active: (K,)   bool  — live steps (a flushed/tail window pads with
              inactive steps: their trip counts and include weights are
              zeroed, so the padding costs a psum of zeros and its outputs
              are discarded — window length is always K and the jit cache
              stays at ONE entry across churn)
      w:      the iterate carry, (r,) or (r, c) — donated together with the
              per-window mask buffers, so successive windows rewrite the
              same device allocations. Plan stacks are NOT donated: the
              runner caches them on device per window signature, so a
              steady-state window re-uploads nothing but masks + carry.
      ys:     (K, rows_total[, c]) per-step raw outputs
      ws:     (K, ...) the operand each step consumed (host-side stats /
              verification replay)

    One dispatch runs K steps: include weights are gathered in-graph from
    ``bad`` (:func:`device_include_weights`), and ``update`` (the workload's
    ``fused_update`` hook — e.g. the power-iteration normalize+quantize) is
    applied on device between steps. The per-step body is byte-for-byte the
    stepwise executor's body, so a fused window is bitwise-equal to K
    stepwise dispatches.
    """
    body = _make_worker_body(
        worker_axis, rows_total, block_rows, matmul or _default_matmul,
        out_cols, segmented_fn, stream_fn=stream_fn,
    )
    sharded = _shard(body, mesh, worker_axis)
    upd = update if update is not None else (lambda y, w: w)
    del fuse_steps  # geometry is carried by the (K, ...) operands

    def usec_window(staged, blk_slot, blk_off, blk_goff, n_blocks,
                    blk_prio, blk_valid, bad, active, w):
        def sbody(w, xs):
            slot_k, off_k, goff_k, nblk_k, prio_k, valid_k, bad_k, act_k = xs
            include = device_include_weights(prio_k, valid_k, bad_k)
            # Inactive padding: zero trip counts and weights — the body
            # degenerates to a psum of zeros instead of real block work.
            include = include * act_k.astype(include.dtype)
            nblk_k = nblk_k * act_k.astype(nblk_k.dtype)
            y = sharded(staged, slot_k, off_k, goff_k, include, nblk_k, w)
            w_next = upd(y, w)
            # ... and the padding iterate carries through unchanged (the
            # update of a zero output may be NaN; jnp.where discards it).
            w_next = jnp.where(act_k, w_next, w)
            return w_next, (y, w)

        w_out, (ys, ws) = jax.lax.scan(
            sbody, w,
            (blk_slot, blk_off, blk_goff, n_blocks, blk_prio, blk_valid,
             bad, active),
        )
        return w_out, ys, ws

    return _with_paths(jax.jit(usec_window, donate_argnums=(7, 8, 9)), body)
