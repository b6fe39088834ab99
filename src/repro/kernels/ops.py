"""Jitted public wrappers around the Pallas kernels.

Dispatch policy:
  * on TPU               -> compiled Pallas kernels
  * elsewhere (CPU dev)  -> ``interpret=True`` Pallas (exact kernel semantics,
                            slow — used by the allclose tests), or the pure
                            jnp reference for fast functional runs.

The wrappers own all padding/unpadding so kernel code only ever sees
block-aligned shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_padded
from .usec_matvec import usec_matvec_padded
from .usec_segmented import (
    segmented_gather_ref,
    usec_segmented_padded,
    usec_stream_padded,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def usec_matvec(
    x: jnp.ndarray,
    w: jnp.ndarray,
    block_m: int = 256,
    block_k: int = 512,
    mode: Optional[str] = None,
) -> jnp.ndarray:
    """y = X @ w (fp32 accumulate). x: (m, k); w: (k,) or (k, c).

    mode: "pallas" | "interpret" | "ref" | None (auto: pallas on TPU, ref
    elsewhere — tests pass "interpret" explicitly).
    """
    if mode is None:
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        return ref.matvec_ref(x, w)
    squeeze = w.ndim == 1
    w2 = w[:, None] if squeeze else w
    m, k = x.shape
    bm = min(block_m, _round_up(m, 8))
    bk = min(block_k, _round_up(k, 128))
    mp, kp = _round_up(m, bm), _round_up(k, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w2, ((0, kp - k), (0, 0)))
    y = usec_matvec_padded(xp, wp, bm=bm, bk=bk, interpret=(mode == "interpret"))
    y = y[:m]
    return y[:, 0] if squeeze else y


def usec_matmat(
    x: jnp.ndarray,
    w: jnp.ndarray,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 128,
    mode: Optional[str] = None,
) -> jnp.ndarray:
    """Y = X @ W for multi-column W (fp32 accumulate). x: (m, k); w: (k, c).

    The blocked matmat path of the per-workload dispatch: W's columns are
    processed in ``block_n`` chunks through the padded Pallas kernel, so a
    wide right-hand side (the CEC papers' matrix-matrix workloads) never
    materializes one giant kernel invocation while the matvec fast path
    (c == 1) stays exactly :func:`usec_matvec`. A 1-d ``w`` degrades to the
    matvec path unchanged.

    mode: "pallas" | "interpret" | "ref" | None (auto: pallas on TPU, ref
    elsewhere).
    """
    if w.ndim == 1:
        return usec_matvec(x, w, block_m=block_m, block_k=block_k, mode=mode)
    if mode is None:
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        return ref.matvec_ref(x, w)
    c = w.shape[1]
    if c <= block_n:
        return usec_matvec(x, w, block_m=block_m, block_k=block_k, mode=mode)
    outs = [
        usec_matvec(x, w[:, j: j + block_n],
                    block_m=block_m, block_k=block_k, mode=mode)
        for j in range(0, c, block_n)
    ]
    return jnp.concatenate(outs, axis=1)


def usec_segmented(
    staged: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off: jnp.ndarray,
    blk_include: jnp.ndarray,
    w: jnp.ndarray,
    block_rows: int,
    block_k: int = 512,
    mode: Optional[str] = None,
) -> jnp.ndarray:
    """A worker's whole block list in one shot: (B, block_rows, c) partials.

    The segment-aware executor path: instead of B separate padded
    :func:`usec_matvec` launches inside the per-worker loop, the full block
    list runs as ONE ``pallas_call`` whose grid walks the scalar-prefetched
    (slot, offset) plan indices with an fp32 accumulator over the
    contraction dim (:mod:`repro.kernels.usec_segmented`). Include weights
    are applied to the compact partials here (same op order as the loop:
    matmul, then mask), and the caller scatter-adds blocks to their global
    rows.

    staged: (T, rows_per_tile, K) worker tile buffer; blk_slot/blk_off/
    blk_include: (B,) plan arrays (offsets in rows; plans are compiled with
    ``row_align == block_rows`` so offsets are block-aligned); w: (K, C).

    mode: "pallas" | "interpret" | "ref" | None (auto: pallas on TPU, the
    gathered flat-matmul reference elsewhere — tests pass "interpret" for
    exact kernel semantics).
    """
    if mode is None:
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        compact = segmented_gather_ref(staged, blk_slot, blk_off, w,
                                       block_rows)
    else:
        t, rpt, k = staged.shape
        if rpt % block_rows:
            raise ValueError(
                f"block_rows={block_rows} must divide rows_per_tile={rpt}")
        # Largest 128-multiple <= block_k that divides the 128-padded K:
        # the whole contraction dim is covered with ZERO padded columns
        # (e.g. k=768, block_k=512 -> bk=384, not 512-with-256-pad).
        kp = _round_up(k, 128)
        bk = max(128, min(block_k, kp) - min(block_k, kp) % 128)
        while kp % bk:
            bk -= 128
        kp = _round_up(k, bk)
        xp = jnp.pad(staged, ((0, 0), (0, 0), (0, kp - k)))
        wp = jnp.pad(w, ((0, kp - k), (0, 0)))
        compact = usec_segmented_padded(
            xp, blk_slot.astype(jnp.int32),
            (blk_off // block_rows).astype(jnp.int32), wp,
            block_rows=block_rows, bk=bk, interpret=(mode == "interpret"),
        )
    return compact * blk_include[:, None, None]


def usec_stream(
    staged: jnp.ndarray,
    blk_slot: jnp.ndarray,
    blk_off: jnp.ndarray,
    blk_goff: jnp.ndarray,
    blk_include: jnp.ndarray,
    n_blocks: jnp.ndarray,
    w: jnp.ndarray,
    rows_total: int,
    block_rows: int,
    mode: str = "pallas",
) -> jnp.ndarray:
    """A worker's whole block list against a one-column operand, streamed
    from its staged tiles in one ``pallas_call``: (rows_total, 1) float32.

    Row ``blk_goff[i] + r`` of each block ``i < n_blocks`` holds
    ``(staged[blk_slot[i], blk_off[i] + r] . w) * blk_include[i]`` — the
    product, then the include weight, as the per-block loop computes it —
    and every other row 0 (:mod:`repro.kernels.usec_segmented`).

    staged: (T, rows_per_tile, K); the plan rows (B,) (offsets in rows,
    block-aligned); n_blocks: (1,) the blocks in use; w: (K, 1) or (K,).
    Needs :func:`~repro.kernels.usec_segmented.stream_fits`.

    mode: "pallas" | "interpret".
    """
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"usec_stream mode must be 'pallas' or "
                         f"'interpret', got {mode!r}")
    k = staged.shape[-1]
    y = usec_stream_padded(
        staged, jnp.reshape(n_blocks, (1,)).astype(jnp.int32),
        blk_slot.astype(jnp.int32),
        (blk_off // block_rows).astype(jnp.int32),
        blk_goff.astype(jnp.int32), blk_include.astype(jnp.float32),
        jnp.reshape(w, (1, k)), rows_total=rows_total,
        block_rows=block_rows, interpret=(mode == "interpret"),
    )
    return y.reshape(-1)[:rows_total, None]


_EXECUTOR_KERNELS = {
    "matvec": usec_matvec,
    "matmat": usec_matmat,
}


def executor_matmul(mode: Optional[str] = None, workload: str = "matvec"):
    """Block-level matmul for the shard_map executors, with kernel dispatch.

    ``repro.runtime.executor.make_matvec_executor`` takes a ``matmul(xb, w2)``
    callable applied per (block_rows, k) block inside the per-worker
    ``fori_loop``. This returns one routed through the per-workload kernel
    table (``workload="matvec"`` -> :func:`usec_matvec`, ``"matmat"`` ->
    the blocked :func:`usec_matmat`), so the executor runs the Pallas kernel
    on TPU, the jnp reference on CPU, and the interpreted kernel when tests
    ask for exact kernel semantics — the same dispatch policy as every other
    op in this module.
    """
    try:
        kernel = _EXECUTOR_KERNELS[workload]
    except KeyError:
        raise ValueError(
            f"unknown executor workload {workload!r}; "
            f"choose from {sorted(_EXECUTOR_KERNELS)}"
        ) from None
    return functools.partial(kernel, mode=mode)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    mode: Optional[str] = None,
) -> jnp.ndarray:
    """Softmax attention. q: (b, h, sq, d); k/v: (b, hk, skv, d), hk | h.

    Matches :func:`repro.kernels.ref.attention_ref` (which materializes the
    full score matrix; this never does).
    """
    if mode is None:
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        b, h, sq, d = q.shape
        hk = k.shape[1]
        if hk != h:  # broadcast grouped KV for the reference path
            k = jnp.repeat(k, h // hk, axis=1)
            v = jnp.repeat(v, h // hk, axis=1)
        return ref.attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k, _round_up(skv, 128))
    sqp, skvp = _round_up(sq, bq), _round_up(skv, bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
    o = flash_attention_padded(
        qp, kp, vp, sq=sq, skv=skv, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=(mode == "interpret"),
    )
    return o[:, :, :sq, :]
