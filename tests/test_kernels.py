"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize(
    "m,k,c,dtype,rtol",
    [
        (256, 512, 1, jnp.float32, 1e-4),
        (300, 517, 1, jnp.float32, 1e-4),   # non-divisible -> padding path
        (64, 100, 3, jnp.float32, 1e-4),
        (256, 512, 4, jnp.bfloat16, 2e-2),
        (128, 128, 1, jnp.bfloat16, 2e-2),
        (1000, 96, 1, jnp.float32, 1e-4),
    ],
)
def test_usec_matvec_vs_ref(m, k, c, dtype, rtol):
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * 7 + k))
    x = jax.random.normal(k1, (m, k), dtype)
    w = jax.random.normal(k2, (k, c), dtype) if c > 1 else jax.random.normal(k2, (k,), dtype)
    got = ops.usec_matvec(x, w, mode="interpret")
    want = ref.matvec_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol, atol=rtol)


@pytest.mark.parametrize(
    "b,h,hk,sq,skv,d,causal,window,dtype",
    [
        (1, 2, 2, 128, 128, 64, True, None, jnp.float32),
        (2, 4, 2, 100, 260, 64, True, None, jnp.float32),    # GQA + padding
        (1, 2, 1, 64, 300, 32, False, None, jnp.float32),    # bidirectional
        (1, 2, 2, 256, 256, 64, True, 128, jnp.float32),     # sliding window
        (1, 4, 4, 1, 384, 64, True, None, jnp.float32),      # decode shape
        (1, 2, 2, 200, 200, 128, True, 64, jnp.float32),
        (1, 2, 2, 128, 128, 64, True, None, jnp.bfloat16),
    ],
)
def test_flash_attention_vs_ref(b, h, hk, sq, skv, d, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b + h + sq + skv), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hk, skv, d), dtype)
    v = jax.random.normal(ks[2], (b, hk, skv, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, mode="interpret")
    kr = jnp.repeat(k, h // hk, axis=1)
    vr = jnp.repeat(v, h // hk, axis=1)
    want = ref.attention_ref(q, kr, vr, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_chunked_attention_matches_kernel_semantics():
    """The pure-jnp chunked path (used by models) == the Pallas kernel."""
    from repro.models.attention import chunked_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, hk, s, d = 2, 4, 2, 192, 32
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, hk, d))
    v = jax.random.normal(ks[2], (b, s, hk, d))
    got = chunked_attention(q, k, v, causal=True, chunk=64)
    want = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, mode="interpret",
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_matvec_auto_mode_dispatches_to_ref_on_cpu():
    x = jnp.ones((32, 32))
    w = jnp.ones((32,))
    y = ops.usec_matvec(x, w)  # mode=None -> ref on CPU
    np.testing.assert_allclose(np.asarray(y), 32.0)


# ---------------------------------------------------------------------- #
# Streamed block list: one pallas_call over a worker's whole block list
# ---------------------------------------------------------------------- #
def _stream_case(name):
    """A worker's block list over G tiles of 64 rows, K = 384, blocks of
    16 rows: (staged, slot, off, goff, include, n_blocks, rows_total)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    br, rpt = 16, 64
    if name == "n1_contiguous":     # N=1: one run over the whole operand
        g, tiles, n, b = 4, [0, 1, 2, 3], 16, 16
        order = np.arange(16)
    else:                           # J=3 cyclic: tiles 2, 3, 0 of 4
        g, tiles = 4, [2, 3, 0]
        n = {"j3_cyclic": 12, "padded": 5, "empty": 0}[name]
        b = 12
        order = rng.permutation(12)[:n]   # non-monotone, several slots
    t = len(tiles)
    staged = rng.integers(-3, 4, size=(t, rpt, 384)).astype(np.float32)
    slot = np.zeros(b, np.int32)
    off = np.zeros(b, np.int32)
    goff = np.zeros(b, np.int32)
    bpt = rpt // br
    slot[:n] = order[:n] // bpt
    off[:n] = order[:n] % bpt * br
    goff[:n] = np.asarray(tiles)[slot[:n]] * rpt + off[:n]
    inc = np.zeros(b, np.float32)
    inc[:n] = rng.choice([0.0, 1.0], size=n)
    if name == "n1_contiguous":
        inc[:n] = 1.0
    return staged, slot, off, goff, inc, np.int32(n), g * rpt


@pytest.mark.parametrize(
    "case", ["n1_contiguous", "j3_cyclic", "padded", "empty"])
def test_usec_stream_interpret_matches_gather_ref_bitwise(case):
    """The streamed block list equals the gather reference and the
    per-block loop bit for bit on integer-grid data: products on their
    global rows, then the include weight (an include of 0 keeps the
    loop's -0.0), padding blocks and rows of no block left at zero."""
    from repro.kernels.usec_stream import block_list_ref

    staged, slot, off, goff, inc, n, rows = _stream_case(case)
    br = 16
    rng = np.random.default_rng(7)
    w = (rng.integers(-256, 257, size=(staged.shape[-1], 1))
         / 256.0).astype(np.float32)
    got = np.asarray(ops.usec_stream(
        staged, slot, off, goff, inc, np.array([n]), w, rows_total=rows,
        block_rows=br, mode="interpret"))
    assert got.shape == (rows, 1)

    compact = np.asarray(block_list_ref(staged, slot, off, w, br))
    want = np.zeros((rows, 1), np.float32)
    loop = np.zeros((rows, 1), np.float32)
    for i in range(int(n)):
        want[goff[i]:goff[i] + br] = compact[i] * inc[i]
        xb = staged[slot[i], off[i]:off[i] + br].astype(np.float64)
        loop[goff[i]:goff[i] + br] = (
            (xb @ w.astype(np.float64)).astype(np.float32) * inc[i])
    assert np.array_equal(got.view(np.int32), want.view(np.int32))
    assert np.array_equal(got.view(np.int32), loop.view(np.int32))
    if 0 < n and inc[:n].min() == 0.0:
        assert np.signbit(got).any()   # the loop's -0.0 rows survive
    held = np.zeros(rows, bool)
    for i in range(int(n)):
        held[goff[i]:goff[i] + br] = True
    assert not got[~held].view(np.int32).any()


@pytest.mark.parametrize("width,block_rows,rows,fits", [
    (32768, 16, 32768, True),     # the benchmark's operand: 6.25 MiB
    (384, 16, 256, True),
    (65536, 16, 65536, False),    # 12.5 MiB of x, operand row and output
    (32768, 16, 1 << 22, False),  # the output alone is 16 MiB
    (32768, 12, 32768, False),    # not whole 8-row tiles
    (32768, 256, 32768, False),   # a block straddles output lines
    (32700, 16, 32768, False),    # not whole 128-lane chunks
])
def test_usec_stream_fits_bounds_shape_and_vmem(width, block_rows, rows,
                                                 fits):
    """Only shapes the streamed kernel can tile, within its VMEM budget,
    take it; the runner keeps the loop for the rest."""
    from repro.api.workload import MatVecPowerIteration
    from repro.kernels.usec_stream import (
        STREAM_VMEM_BYTES,
        stream_fits,
        stream_vmem_bytes,
    )
    from repro.runtime.executor import stream_block_fn

    assert stream_fits(width, block_rows, rows) == fits
    if width % 128 == 0 and block_rows % 8 == 0 and 128 % block_rows == 0:
        assert fits == (stream_vmem_bytes(width, block_rows, rows)
                        <= STREAM_VMEM_BYTES)
    fn = stream_block_fn(MatVecPowerIteration().linear, None, "tpu", width,
                         rows, block_rows)
    assert (fn is not None) == fits


def _map_rows():
    from repro.api.workload import MapReduceRows

    return MapReduceRows(
        row_fn=lambda xb, w2: jnp.sum(xb, axis=1, keepdims=True),
        reduce_fn=lambda mapped: float(mapped.sum()), out_cols=1)


@pytest.mark.parametrize("workload,platform,mode,cols,path,kernel_mode", [
    ("powit", "tpu", None, 1, "stream", "pallas"),
    ("powit", "tpu", "pallas", 1, "stream", "pallas"),
    ("powit", "cpu", None, 1, "loop", None),
    ("powit", "cpu", "interpret", 1, "stream", "interpret"),
    ("powit", "tpu", "ref", 1, "loop", None),
    ("maprows", "tpu", None, 1, "loop", None),
    ("matmat", "tpu", None, 4, "loop", "pallas"),
])
def test_block_list_path_decision(workload, platform, mode, cols, path,
                                  kernel_mode):
    """The executor's one block-list decision, from what the runner
    observes: a linear workload whose kernels run as Pallas (TPU by
    default, or an explicit "pallas"/"interpret") streams a one-column
    operand; the CPU default, "ref", a non-linear workload and an operand
    of several columns (decided at trace time) keep the per-block loop."""
    from repro.api.workload import MatMat, MatVecPowerIteration
    from repro.runtime.executor import block_list_path, stream_block_fn

    wl = {"powit": MatVecPowerIteration, "maprows": _map_rows,
          "matmat": MatMat}[workload]()
    fn = stream_block_fn(wl.linear, mode, platform, 384, 256, 16)
    assert (None if fn is None else fn.keywords["mode"]) == kernel_mode
    assert block_list_path(fn, cols, wl.out_cols) == path
