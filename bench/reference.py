"""The plain reference: float64 products and the iterate update, in NumPy.

Nothing here imports the program or takes anything it made; it sees the
operand as the benchmark generated it (host int8, see :mod:`data`).

- :func:`products` is ``X @ W`` in float64 over row blocks. Every operand
  the traffic sends is integer or on the 2^-bits grid and every entry of X
  a small integer, so the float64 sum is exact and any correct float32
  program agrees with it to the bit.
- :func:`snap` is the power-iteration update as the configuration states
  it: float32 ``y / ||y||`` with the norm summed by a pairwise tree
  (square, zero-pad to a power of two, add the even and odd halves until
  one value is left), IEEE sqrt and divide, then rounded half-to-even onto
  the 2^-bits grid. NumPy's float32 sqrt and divide are correctly rounded,
  so this is the exact value a correct program must carry.
- :func:`grid_gap` is the widest entrywise gap in grid units: 0 when exact.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def products(x8: np.ndarray, w: np.ndarray, block_rows: int = 2048,
             threads: int = 4) -> np.ndarray:
    """float64 ``X @ W``; ``w`` is (n,) or (n, k)."""
    w64 = np.asarray(w, dtype=np.float64)
    squeeze = w64.ndim == 1
    w2 = w64[:, None] if squeeze else w64
    n = x8.shape[0]
    out = np.empty((n, w2.shape[1]), np.float64)

    def block(i):
        sl = slice(i, min(i + block_rows, n))
        out[sl] = x8[sl].astype(np.float64) @ w2

    with ThreadPoolExecutor(threads) as ex:
        for f in [ex.submit(block, i) for i in range(0, n, block_rows)]:
            f.result()
    return out[:, 0] if squeeze else out


def tree_sumsq32(v: np.ndarray) -> np.float32:
    s = np.asarray(v, np.float32) ** 2
    size = 1
    while size < s.shape[0]:
        size *= 2
    s = np.concatenate([s, np.zeros(size - s.shape[0], np.float32)])
    while s.shape[0] > 1:
        s = s[0::2] + s[1::2]
    return s[0]


def snap(y: np.ndarray, bits: int = 8) -> np.ndarray:
    """The grid-snapped unit iterate of ``y`` (a unit vector at the largest
    entry if every entry rounds to 0)."""
    v = np.asarray(y, np.float64).astype(np.float32)
    u = v / np.sqrt(tree_sumsq32(v))
    scale = np.float32(1 << bits)
    q = (np.round(u * scale) / scale).astype(np.float32)
    if not np.any(q):
        q = np.zeros_like(q)
        q[int(np.argmax(np.abs(v)))] = 1.0
    return q


NO_MATCH = 1e30   # the gap of an answer of the wrong shape or not finite


def grid_gap(got, want, bits: int = 0) -> float:
    """max |got - want| in units of 2^-bits (bits=0: plain units)."""
    g = np.asarray(got, np.float64)
    r = np.asarray(want, np.float64)
    if g.shape != r.shape:
        return NO_MATCH
    if g.size == 0:
        return 0.0
    d = np.abs(g - r)
    if not np.all(np.isfinite(d)):
        return NO_MATCH
    return float(d.max()) * float(1 << bits)
