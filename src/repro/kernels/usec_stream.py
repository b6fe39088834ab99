"""Streamed block-list Pallas TPU kernel: a worker's whole one-column
block list in one launch.

The per-block loop of the step programs slices each plan block out of the
worker's staged tiles and runs one matmul launch per block. This kernel
takes the entire block list in one ``pallas_call``: the plan's (slot,
offset) indices are scalar-prefetched, so the grid walks the block list and
the BlockSpec index map DMAs each full-width block straight out of the
staged tile buffer: no gather, no sliced copy, no per-block dispatch.

Offsets arrive in *block-row units* (``blk_off // block_rows``): the
elastic plans are compiled with ``row_align == block_rows``, so every block
starts on a block-row boundary by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
STREAM_VMEM_BYTES = 12 << 20   # of v5e's 16 MiB default scoped VMEM


def stream_vmem_bytes(width: int, block_rows: int, rows_total: int) -> int:
    """VMEM :func:`usec_stream_padded` holds: the x block, the operand row
    (padded to 8 sublanes) and the output, each double-buffered."""
    lines = -(-rows_total // LANES)
    return 2 * 4 * ((block_rows + 8) * width + lines * LANES)


def stream_fits(width: int, block_rows: int, rows_total: int) -> bool:
    """Whether :func:`usec_stream_padded` takes a block list of this row
    width, block size and output length: whole 128-lane chunks of a row,
    blocks of whole 8-row tiles that never straddle a 128-row line of the
    output, and a working set within :data:`STREAM_VMEM_BYTES`."""
    return (width % LANES == 0 and block_rows % 8 == 0
            and LANES % block_rows == 0
            and stream_vmem_bytes(width, block_rows, rows_total)
            <= STREAM_VMEM_BYTES)


def _stream_kernel(nblk_ref, slot_ref, off_ref, goff_ref, inc_ref,
                   x_ref, w_ref, o_ref, *, block_rows, chunk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < nblk_ref[0])
    def _block():
        def mac(c, acc):
            j = pl.multiple_of(c * chunk, chunk)
            return acc + (x_ref[0, :, pl.ds(j, chunk)].astype(jnp.float32)
                          * w_ref[:, pl.ds(j, chunk)].astype(jnp.float32))

        acc = jax.lax.fori_loop(
            0, x_ref.shape[-1] // chunk, mac,
            jnp.zeros((block_rows, chunk), jnp.float32))
        part = acc[:, :LANES]
        for c in range(1, chunk // LANES):
            part = part + acc[:, c * LANES:(c + 1) * LANES]
        # The block's product, then its include weight: the loop's order.
        y = jnp.broadcast_to(
            jnp.sum(part, axis=1, keepdims=True) * inc_ref[i],
            (block_rows, LANES))
        # Rows goff .. goff + block_rows sit in one 128-lane output line:
        # select each row's value onto its lane. Selects, not sums, so
        # every bit is kept (a product times an include of 0 may be -0.0,
        # as in the loop).
        g = goff_ref[i]
        lane = (jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
                - g % LANES)
        row = pl.ds(g // LANES, 1)
        line = o_ref[row, :]
        for r in range(block_rows):
            line = jnp.where(lane == r, y[r:r + 1], line)
        o_ref[row, :] = line


@functools.partial(
    jax.jit, static_argnames=("rows_total", "block_rows", "interpret"))
def usec_stream_padded(
    staged: jnp.ndarray,
    n_blocks: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off_u: jnp.ndarray,
    blk_goff: jnp.ndarray,
    blk_include: jnp.ndarray,
    w_row: jnp.ndarray,
    rows_total: int,
    block_rows: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """A worker's whole one-column block list, streamed from its tiles.

    staged: (T, rows_per_tile, K), with :func:`stream_fits` (K,
    block_rows, rows_total); n_blocks: (1,) int32, the blocks in use; blk_slot,
    blk_off_u (offsets in block_rows units), blk_goff (global rows),
    blk_include: (B,) plan rows; w_row: (1, K), the operand laid along
    lanes.

    Returns the worker's output, (ceil(rows_total / 128), 128) float32 in
    row-major order: row ``goff[i] + r`` of every block ``i < n_blocks``
    holds ``(staged[slot[i], off[i] + r] . w) * include[i]``, every other
    row 0.

    Grid: one step per block, each one DMA of a full-width (1, block_rows,
    K) block straight out of ``staged`` by its scalar-prefetched (slot,
    offset); nothing is gathered or copied first. A padding step (i >=
    n_blocks) maps to the last real block, so Pallas fetches nothing, and
    computes nothing. The product is a VPU multiply of the block by the
    lane-dense operand and a lane reduction, so the one column is never
    padded to an MXU tile. The output stays in VMEM for the whole grid
    (rows_total * 4 bytes) and is written back once.
    """
    t, rpt, k = staged.shape
    if not stream_fits(k, block_rows, rows_total) or rpt % block_rows:
        raise ValueError(
            f"usec_stream needs 128 | K, 8 | block_rows | 128, "
            f"block_rows | rows_per_tile and VMEM for the working set; got "
            f"{staged.shape}, block_rows={block_rows}, "
            f"rows_total={rows_total}")
    if w_row.shape != (1, k):
        raise ValueError(f"w_row must be (1, {k}), got {w_row.shape}")
    lines = -(-rows_total // LANES)
    chunk = next(c for c in (512, 256, LANES) if k % c == 0)

    def x_map(i, nblk, slot, off, goff, inc):
        j = jnp.minimum(i, jnp.maximum(nblk[0] - 1, 0))
        return slot[j], off[j], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(blk_slot.shape[0],),
        in_specs=[
            pl.BlockSpec((1, block_rows, k), x_map),
            pl.BlockSpec((1, k), lambda i, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((lines, LANES), lambda i, *_: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_stream_kernel, block_rows=block_rows,
                          chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((lines, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="usec_stream",
    )(n_blocks, blk_slot, blk_off_u, blk_goff, blk_include, staged, w_row)


def block_list_ref(
    staged: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off: jnp.ndarray,
    w: jnp.ndarray,
    block_rows: int,
) -> jnp.ndarray:
    """jnp reference: gather a block list's rows, one flat fp32 matmul.

    staged: (T, rows_per_tile, K); blk_slot/blk_off: (B,) plan indices
    (offsets in rows); w: (K, C). Returns (B, block_rows, C): block i
    holds ``staged[slot[i], off[i]:off[i]+block_rows] @ w``. The oracle
    the streamed kernel is tested against; on integer-grid data every
    partial sum is exactly representable, so it agrees with the kernel
    and the per-block loop bit for bit.
    """
    t, rpt, k = staged.shape
    b = blk_slot.shape[0]
    flat = staged.reshape(t * rpt, k)
    rows = (
        blk_slot.astype(jnp.int32) * rpt + blk_off.astype(jnp.int32)
    )[:, None] + jnp.arange(block_rows, dtype=jnp.int32)[None, :]
    y = jnp.dot(
        flat[rows.reshape(-1)].astype(jnp.float32),
        w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return y.reshape(b, block_rows, w.shape[1])
