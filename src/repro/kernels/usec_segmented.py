"""Segment-aware Pallas TPU kernel: one launch per worker's whole block list.

The stepwise executor's per-worker loop pays one padded ``usec_matvec``
launch per plan block (B launches of a (block_rows, r) x (r, c) matmul).
This kernel consumes the **entire block list in one ``pallas_call``**: the
plan's (slot, offset) indices are scalar-prefetched, so the grid walks the
block list and the BlockSpec index maps DMA each block's rows straight out
of the worker's staged tile buffer — no host-side gather, no per-block
dispatch, and the kernel-launch overhead is paid once per step instead of
once per block.

Tiling:
  grid = (B, K / bk), K innermost so each block's (block_rows, c) output
  stays resident in VMEM while its fp32 K-reduction completes.
  x block  (1, block_rows, bk) — DMA'd from staged[(slot[i], off_u[i], j)]
  w block  (bk, c)             — broadcast along the block grid
  o block  (1, block_rows, c)  — fp32 accumulator, one per plan block

The output is *compact*: (B, block_rows, c) per-block partials. The caller
scatters them to global rows (per-worker output rows are disjoint, so a
scatter-add reproduces the loop's overwrite exactly) and applies the include
weights. Keeping the scatter outside the kernel sidesteps the classic
revisited-output-block hazard: padding blocks would otherwise alias a real
output block and zero it.

Shapes must be pre-padded so ``bk | K`` — ``ops.usec_segmented`` does this
(zero-padding the contraction dim adds exact zeros). Offsets arrive in
*block-row units* (``blk_off // block_rows``): the elastic plans are
compiled with ``row_align == block_rows``, so every block starts on a
block-row boundary by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segmented_kernel(slot_ref, off_ref, x_ref, w_ref, o_ref):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += jnp.dot(
        x_ref[0].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("block_rows", "bk", "interpret"))
def usec_segmented_padded(
    staged: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off_u: jnp.ndarray,
    w: jnp.ndarray,
    block_rows: int,
    bk: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-block partials for a pre-padded worker block list.

    staged: (T, rows_per_tile, K) with block_rows | rows_per_tile, bk | K
    blk_slot: (B,) int32 — staged slot per block
    blk_off_u: (B,) int32 — row offset per block in block_rows units
    w: (K, C)

    Returns (B, block_rows, C) float32 — block i holds
    ``staged[slot[i], off[i]:off[i]+block_rows] @ w`` (fp32 accumulated).
    """
    t, rpt, k = staged.shape
    k2, c = w.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: {staged.shape} @ {w.shape}")
    if rpt % block_rows or k % bk:
        raise ValueError(
            f"staged must be ({block_rows},{bk})-aligned; got {staged.shape}")
    b = blk_slot.shape[0]
    grid = (b, k // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_rows, bk),
                lambda i, j, slot, off: (slot[i], off[i], j)),
            pl.BlockSpec((bk, c), lambda i, j, slot, off: (j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_rows, c), lambda i, j, slot, off: (i, 0, 0)),
    )
    return pl.pallas_call(
        _segmented_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, block_rows, c), jnp.float32),
        interpret=interpret,
    )(blk_slot, blk_off_u, staged, w)


def gather_block_rows(
    staged: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off: jnp.ndarray,
    block_rows: int,
) -> jnp.ndarray:
    """Gather a block list's rows out of the staged tile buffer.

    staged: (T, rows_per_tile, K); blk_slot/blk_off: (B,) plan indices
    (offsets in rows). Returns (B, block_rows, K). The ONE definition of
    the flat-row index arithmetic shared by :func:`segmented_gather_ref`
    and the generic ``Workload.segmented_fn`` fallback, so the two can
    never drift apart.
    """
    t, rpt, k = staged.shape
    b = blk_slot.shape[0]
    flat = staged.reshape(t * rpt, k)
    rows = (
        blk_slot.astype(jnp.int32) * rpt + blk_off.astype(jnp.int32)
    )[:, None] + jnp.arange(block_rows, dtype=jnp.int32)[None, :]
    return flat[rows.reshape(-1)].reshape(b, block_rows, k)


def segmented_gather_ref(
    staged: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off: jnp.ndarray,
    w: jnp.ndarray,
    block_rows: int,
) -> jnp.ndarray:
    """jnp reference: gather all block rows, one flat fp32 matmul.

    The CPU fast path of the segmented dispatch (and the oracle the
    interpret-mode kernel is tested against): (B*block_rows, K) @ (K, C) is
    ONE gemm instead of B kernel launches. Accumulation order over K may
    differ from the per-block loop in the last ulp on non-exact data; on the
    elastic runner's integer-grid matrices every partial sum is exactly
    representable, so all paths agree bitwise (asserted by the parity tests).
    """
    b = blk_slot.shape[0]
    xg = gather_block_rows(staged, blk_slot, blk_off, block_rows)
    y = jnp.dot(
        xg.reshape(b * block_rows, -1).astype(jnp.float32),
        w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return y.reshape(b, block_rows, w.shape[1])


def vmem_bytes(block_rows: int, bk: int, c: int, dtype_bytes: int = 4) -> int:
    """Working-set estimate for the chosen tiling (roofline docs)."""
    return block_rows * bk * dtype_bytes + bk * c * 4 + block_rows * c * 4
