"""The host's correctly rounded divide and sqrt against the integer routines.

On NumPy, :func:`_rn_div` and :func:`_rn_sqrt` take the native IEEE op and
repair, by the integer routine, only the entries their masks mark; the
device (jax.numpy) runs the integer routine. The fused window's parity with
the stepwise host path rests on the two giving the same bits on every
input, so these cases feed adversarial bit patterns — subnormals, ±0, inf,
NaN, quotients that underflow or overflow — and compare int32 patterns.
"""

import json
import zlib

import numpy as np
import pytest

from conftest import run_with_devices
from repro.runtime.elastic_runner import (
    _div_repair,
    _rn_div,
    _rn_div_int,
    _rn_sqrt,
    _rn_sqrt_int,
    _sqrt_repair,
)

_EXP = 0x7F800000


def _i32(x):
    return np.asarray(x, np.float32).view(np.int32)


def _f32(b):
    return np.asarray(b, np.int32).view(np.float32)


def _pattern(rng, n, exps):
    """Float32 values with random sign and mantissa bits, exponent fields
    drawn from ``exps``."""
    sign = rng.integers(0, 2, n, dtype=np.int32) << 31
    exp = rng.choice(np.asarray(exps, np.int32), n) << 23
    return _f32(sign | exp | rng.integers(0, 1 << 23, n, dtype=np.int32))


def _dividends(rng, n=1 << 15):
    a = _f32(rng.integers(-(1 << 31), 1 << 31, n, dtype=np.int64)
             .astype(np.int32))
    a[:8] = _f32(np.array([0, 0x80000000, _EXP, 0xFF800000, 0x7FC00000,
                           1, 0x007FFFFF, 0x80000001],
                          np.uint32).view(np.int32))
    return a


def _divisors(rng):
    """Positive normals from 2^-126 to 2^127, both ends included."""
    d = _f32(rng.integers(1, 255, 24, dtype=np.int32) << 23
             | rng.integers(0, 1 << 23, 24, dtype=np.int32))
    return [np.float32(2.0 ** -126), np.float32(2.0 ** 127),
            np.float32(np.finfo(np.float32).max)] + list(d)


def _div_case(name, rng):
    """(a, d) pairs of one adversarial class."""
    if name == "div_whole_range":
        return [(_dividends(rng), d) for d in _divisors(rng)]
    if name == "div_underflow":
        # Quotients around and below 2^-126, where IEEE rounds to the
        # subnormal grid and the integer routine to 24 bits; the first
        # entries round up to exactly 2^-126.
        out = []
        for d in np.abs(_pattern(rng, 16, np.arange(127, 255))):
            e = int((_i32(d) >> 23) & 0xFF)
            lo = max(1, e - 126 - 24)
            hi = min(254, e - 126 + 2)
            a = _pattern(rng, 1 << 13, np.arange(lo, hi + 1))
            out.append((a, d))
        a = _f32(np.array([0x3FFFFFFF, 0x3FFFFFFE, 0x3F800000], np.int32))
        out.append((a, np.float32(2.0 ** 127)))
        return out
    if name == "div_overflow":
        out = []
        for d in np.abs(_pattern(rng, 16, np.arange(1, 128))):
            e = int((_i32(d) >> 23) & 0xFF)
            lo = max(1, e + 127 - 2)
            a = _pattern(rng, 1 << 13, np.arange(lo, 255))
            out.append((a, d))
        a = _f32(np.array([0x7F7FFFFF, 0x7F7FFFFE, 0x7F000000], np.int32))
        out.append((a, np.float32(np.nextafter(np.float32(1),
                                               np.float32(0)))))
        return out
    if name == "div_bad_divisor":
        a = _dividends(rng, 1 << 12)
        return [(a, _f32(np.int32(b))) for b in
                (0, -(1 << 31), 1, 0x007FFFFF, _EXP, 0x7FC00000,
                 _i32(np.float32(-3.0)))]
    raise ValueError(name)


def _div_expected_mask(a, d, q):
    """The repair rule, restated from its documentation."""
    bd = int(_i32(d))
    if not 0x00800000 <= bd < _EXP:
        return np.ones(a.shape, bool)
    ba, bq = _i32(a), _i32(q)
    ea, eq = ba & _EXP, (bq >> 23) & 0xFF
    subnormal_or_special = (ea == 0) | (ea == _EXP)
    flushed_or_edge = (eq == 0) | (eq == 1) | (eq == 255)
    return ((ba & 0x7FFFFFFF) != 0) & (subnormal_or_special | flushed_or_edge)


@pytest.mark.parametrize("case", ["div_whole_range", "div_underflow",
                                  "div_overflow", "div_bad_divisor"])
def test_host_divide_matches_integer_routine(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    mismatches = 0
    for a, d in _div_case(case, rng):
        want = _i32(_rn_div_int(a, d, np))
        assert np.array_equal(_i32(_rn_div(a, d, np)), want), (case, d)
        with np.errstate(all="ignore"):
            q = a / d
        fix = _div_repair(a, d, q)
        assert np.array_equal(fix, _div_expected_mask(a, d, q)), (case, d)
        # The native op disagrees only where the mask repairs.
        differ = _i32(q) != want
        assert not np.any(differ & ~fix), (case, d)
        mismatches += int(np.count_nonzero(differ))
    # Each class reaches entries the native op alone would get wrong.
    assert mismatches > 0


_SQRT_EXPS = {
    "sqrt_normal": np.arange(1, 255),
    "sqrt_subnormal": np.array([0]),
    "sqrt_huge": np.arange(240, 255),
    "sqrt_specials": np.array([0, 255]),
}


@pytest.mark.parametrize("case", sorted(_SQRT_EXPS))
def test_host_sqrt_matches_integer_routine(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    s = np.abs(_pattern(rng, 1 << 15, _SQRT_EXPS[case]))
    if case == "sqrt_specials":
        s[:6] = _f32(np.array([0, -(1 << 31), _EXP, 0x7FC00000,
                               0x007FFFFF, _i32(np.float32(-4.0))],
                              np.int32))
    want = _i32(_rn_sqrt_int(s, np))
    assert np.array_equal(_i32(_rn_sqrt(s, np)), want)
    for x in s[:64]:                       # the scalar the normalize takes
        assert _i32(_rn_sqrt(x, np)) == _i32(_rn_sqrt_int(x, np))
    fix = _sqrt_repair(s)
    b = _i32(s)
    assert np.array_equal(fix, ~((b >= 0x00800000) & (b < _EXP)))
    with np.errstate(all="ignore"):
        differ = _i32(np.sqrt(s)) != want
    assert not np.any(differ & ~fix)
    assert fix.any() == (case != "sqrt_normal" and case != "sqrt_huge")


_GRID = """
import glob
import json
import tempfile
import warnings

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.api.workload import MatVecPowerIteration
from repro.runtime.elastic_runner import _rn_div, make_exact_matrix, quantize_unit

# A 32768-entry grid product: eight 4096-row exact operands, each times a
# quantized iterate, so every entry is an integer multiple of 2^-8.
rng = np.random.default_rng(7)
y = np.concatenate([
    (make_exact_matrix(4096, k).astype(np.float64)
     @ quantize_unit(rng.normal(size=4096))).astype(np.float32)
    for k in range(8)])
upd = jax.jit(MatVecPowerIteration().fused_update())
d = tempfile.mkdtemp()
with jax.profiler.trace(d):
    host = quantize_unit(y)
    sub = np.array([1e-40, 1.0, -2.0], np.float32)
    _rn_div(sub, np.float32(3.0), np)          # one subnormal: one repair
device = np.asarray(upd(y, y))
path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
repairs = []
for plane in ProfileData.from_file(path).planes:
    for line in plane.lines:
        for e in line.events:
            if e.name == "usec.rn_repair":
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    repairs.append(dict(e.stats)["entries"])
print(json.dumps({
    "n": int(y.size),
    "on_grid": bool(np.array_equal(y * 256, np.round(y * 256))),
    "same": bool(np.array_equal(host.view(np.int32), device.view(np.int32))),
    "repairs": repairs,
}))
"""


def test_quantize_unit_matches_fused_update_without_repair():
    """The cell's case: on a grid product the host takes the native ops
    alone (no ``usec.rn_repair`` span, while a subnormal entry does open
    one) and equals the jitted device update bit for bit."""
    out = json.loads(run_with_devices(_GRID, n_devices=1)
                     .strip().splitlines()[-1])
    assert out["n"] == 32768 and out["on_grid"]
    assert out["same"]
    assert out["repairs"] == [1]
