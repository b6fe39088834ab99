"""Pallas TPU kernel for the USEC block-row matvec — the paper's hot loop.

The power-iteration workload is ``y_blk = X_blk @ w`` per assigned row
segment. On TPU this is a memory-bound streaming op (arithmetic intensity
~2 flops/byte for fp32 X), so the kernel's job is to stream X through VMEM in
MXU-aligned tiles with fp32 accumulation over the K dimension, never
re-reading X.

Tiling:
  grid = (m / bm, k / bk), K innermost so each output block stays resident in
  VMEM while its K-reduction completes.
  X block  (bm, bk)  — the streamed operand (bm*bk*dtype bytes of VMEM)
  w block  (bk, c)   — broadcast along the row grid; c is the number of
                       simultaneous vectors (1 for classic power iteration,
                       more for block/subspace iteration)
  y block  (bm, c)   — fp32 accumulator, written once per row tile

Shapes must be pre-padded to (bm, bk) multiples — ``ops.usec_matvec`` does
this (and slices the result back). The default (bm, bk) = (256, 512) keeps
the working set at 256*512*4 + 512*c*4 + 256*c*4 bytes ≈ 0.5 MB ≪ VMEM, and
both dims are multiples of the 8×128 fp32 / 16×128 bf16 register tiles.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _matvec_kernel(x_ref, w_ref, o_ref):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def usec_matvec_padded(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bm: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = X @ w for pre-padded operands. x: (M, K) with bm|M, bk|K; w: (K, C).

    Returns (M, C) float32.
    """
    m, k = x.shape
    k2, c = w.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: {x.shape} @ {w.shape}")
    if m % bm or k % bk:
        raise ValueError(f"operands must be padded to ({bm},{bk}) multiples; got {x.shape}")
    grid = (m // bm, k // bk)
    return pl.pallas_call(
        _matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bk, c), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), jnp.float32),
        interpret=interpret,
        name="usec_matvec",
    )(x, w)


def vmem_bytes(bm: int, bk: int, c: int, dtype_bytes: int = 4) -> int:
    """Working-set estimate for the chosen tiling (for DESIGN/roofline docs)."""
    return bm * bk * dtype_bytes + bk * c * 4 + bm * c * 4
