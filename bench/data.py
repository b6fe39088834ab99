"""The benchmark's operand: a symmetric integer matrix made from the seed.

The construction is that of the exact-arithmetic test matrix of the
paper's power iteration (arXiv:2107.09657 §V, as the repo stages it):
``X = A + A^T + 40 I`` with ``A`` uniform integers in [-3, 3]. Entries are
small integers, so every product with a 2^-8-grid vector or an integer
vector is exact in float32, and a float64 host product is an exact
reference.

Each entry is a counter-based hash of its unordered index pair and the
seed, so any tile can be made on its own, on the device, in one fused
elementwise program, with no transpose and no random state to carry. The
device makes int8 (a quarter of the float32 bytes), the host receives it
and widens it to float32 with a few threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

LO, HI, DIAG = -3, 3, 40
_M1, _M2 = 0x7FEB352D, 0x846CA68B          # lowbias32 multipliers


def seed_words(seed: int):
    """Two uint32 words from a seed of any size (seeds may pass
    2**31; a plain ``int32`` would overflow)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return int(w[0]), int(w[1])


def _mix(v, xp):
    """lowbias32 integer hash on uint32 arrays (NumPy or jax.numpy)."""
    u32 = xp.uint32
    v = v ^ (v >> u32(16))
    v = v * u32(_M1)
    v = v ^ (v >> u32(15))
    v = v * u32(_M2)
    return v ^ (v >> u32(16))


def entries(rows, cols, n: int, seed: int, xp=np, words=None):
    """Entries X[rows, cols] as int8, for broadcastable index arrays.

    ``xp`` is numpy or jax.numpy; both give the same bits. ``words`` are
    the seed's two words when already made (traced values, under jit)."""
    s0, s1 = seed_words(seed) if words is None else words
    u32 = xp.uint32
    r = xp.asarray(rows).astype(u32)
    c = xp.asarray(cols).astype(u32)
    lo, hi = xp.minimum(r, c), xp.maximum(r, c)
    pair = lo * u32(n) + hi                       # unique for n <= 65536
    span = u32(HI - LO + 1)
    a = (_mix(pair ^ u32(s0), xp) % span).astype(xp.int32) + LO
    b = (_mix(pair ^ u32(s1), xp) % span).astype(xp.int32) + LO
    # Off the diagonal A[lo, hi] + A[hi, lo]; on it 2 A[i, i] + 40.
    diag = r == c
    v = xp.where(diag, 2 * a + DIAG, a + b)
    return v.astype(xp.int8)


def make_operand_int8(n: int, seed: int, tile_rows: int = 4096) -> np.ndarray:
    """The (n, n) operand as a host int8 array, made on the default device
    tile by tile (one compiled program per tile shape)."""
    import jax
    import jax.numpy as jnp

    if n > 65536:
        raise ValueError(f"n={n}: the pair index needs n <= 65536")
    tile_rows = min(tile_rows, n)
    if n % tile_rows:
        raise ValueError(f"tile_rows={tile_rows} must divide n={n}")

    # The seed goes in as data, so one compiled program serves every seed.
    @jax.jit
    def tile(r0, s0, s1):
        rows = r0 + jnp.arange(tile_rows, dtype=jnp.uint32)[:, None]
        cols = jnp.arange(n, dtype=jnp.uint32)[None, :]
        return entries(rows, cols, n, seed, jnp, words=(s0, s1))

    words = [jnp.uint32(w) for w in seed_words(seed)]
    out = np.empty((n, n), np.int8)
    pending = None
    for i in range(n // tile_rows):
        t = tile(jnp.uint32(i * tile_rows), *words)   # dispatch the next tile
        if pending is not None:
            j, p = pending
            out[j * tile_rows:(j + 1) * tile_rows] = np.asarray(p)
        pending = (i, t)
    j, p = pending
    out[j * tile_rows:(j + 1) * tile_rows] = np.asarray(p)
    return out


def widen(x8: np.ndarray, dtype=np.float32, threads: int = 8,
          chunk_rows: int = 1024) -> np.ndarray:
    """``x8.astype(dtype)`` with row chunks cast on a few threads (the cast
    releases the interpreter lock)."""
    out = np.empty(x8.shape, dtype)
    n = x8.shape[0]

    def cast(i):
        sl = slice(i, min(i + chunk_rows, n))
        np.copyto(out[sl], x8[sl], casting="unsafe")

    with ThreadPoolExecutor(threads) as ex:
        for f in [ex.submit(cast, i) for i in range(0, n, chunk_rows)]:
            f.result()
    return out
